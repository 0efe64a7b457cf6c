"""The set-up timeline's readers (`chipbench/setup_timeline.py`): each of the
five on hand-built rings (overlaps counted once, what a `compile_resolve`
holds kept out of ``jit``, None on a parent's ring, None after an earlier run
in the process), what the three readers they replaced read, each one's entry
in `BENCHMARK.json` by name, the identity ``preprogram + spanned + unspanned
= extent`` on a real rehearsal run in a process of its own, the five in the
traced rehearsal line of all five cells, and the probe's report."""

import copy
import importlib
import json
import subprocess
import sys
import types

import pytest

from chipbench import program_spans as ps
from chipbench import rehearse, run
from chipbench import setup_timeline as st
from chipbench.trace_reduce import union_length

FAMILY = tuple(f"setup_timeline_{part}_s.train" for part in
               ("preprogram", "data", "resolve", "jit", "unspanned"))


class Ring:
    """A synthetic span ring, appended in the order spans end."""

    def __init__(self):
        self.spans, self._ids = [], iter(range(1, 10_000))

    def add(self, name, t0, dur, parent=None, **args):
        span = types.SimpleNamespace(name=name, t0=t0, dur=dur, args=args,
                                     id=next(self._ids), parent=parent)
        self.spans.append(span)
        return span


def one_run(ring, t, steps, jit=True):
    """Set-up from time ``t`` on, then ``steps`` window calls; -> when the
    last call ended.  Laid out in seconds after ``t``:

    [0, 1] module_import; [1, 3] corpus_simulate; [2.5, 5.5] trace_lower with
    a graph_lower inside; [6, 6.5] step_build around a dataset_upload;
    [7, 8] and [7.5, 9] two lone jit_compiles that overlap; [10, 14] call 0
    around a compile_resolve [10, 13.5] whose lower stage holds a
    jit_compile; calls 1 and 2; the window from 14.03.
    """
    ring.add("module_import", t, 1.0, module="nerrf_tpu.models")
    ring.add("corpus_simulate", t + 1.0, 2.0, trace=0)
    lower = next(ring._ids)
    ring.add("graph_lower", t + 3.0, 0.5, parent=lower)
    ring.add("trace_lower", t + 2.5, 3.0)
    ring.spans[-1].id = lower
    build = next(ring._ids)
    ring.add("dataset_upload", t + 6.1, 0.3, parent=build, bytes=1 << 20)
    ring.add("step_build", t + 6.0, 0.5)
    ring.spans[-1].id = build
    if jit:
        ring.add(st.JIT, t + 7.0, 1.0, stage="backend_compile", fun="jit(a)")
        ring.add(st.JIT, t + 7.5, 1.5, stage="trace", fun="b")
    call, resolve, stage = (next(ring._ids) for _ in range(3))
    if jit:
        ring.add(st.JIT, t + 10.5, 2.0, parent=stage, stage="trace",
                 fun="flat_step")
    ring.add("compile_resolve.lower", t + 10.2, 2.5, parent=resolve)
    ring.spans[-1].id = stage
    ring.add(ps.RESOLVE, t + 10.0, 3.5, parent=call, program="train_step")
    ring.spans[-1].id = resolve
    ring.add(ps.STEP_CALL, t + 10.0, 4.0, call=0)
    ring.spans[-1].id = call
    at = t + 14.0
    for k in range(1, ps.WARMUP_CALLS + steps):
        ring.add(ps.STEP_CALL, at, 0.008, call=k)
        at += 0.010
    return at


# what `one_run` lays out, by hand
DATA = 4.5 + 0.3           # [1, 5.5] once, the upload
RESOLVE = 3.5
JIT = 2.0                  # [7, 9]: the two overlap; the third is a resolve's
SPANNED = 1.0 + 4.5 + 0.5 + 2.0 + 4.0 + 2 * 0.008


def test_the_five_parts_on_a_hand_built_ring():
    ring = Ring()
    one_run(ring, 0.0, steps=5)
    got = st.timeline(ring.spans, 5, process_start=-7.5)
    start = 14.0 + 2 * 0.010
    assert got["preprogram"] == 7.5
    assert got["data"] == pytest.approx(DATA)
    assert got["resolve"] == pytest.approx(RESOLVE)
    assert got["jit"] == pytest.approx(JIT)
    assert got["spanned"] == pytest.approx(SPANNED)
    assert got["extent"] == pytest.approx(7.5 + start)
    assert got["unspanned"] == pytest.approx(start - SPANNED)
    assert got["preprogram"] + got["spanned"] + got["unspanned"] == \
        pytest.approx(got["extent"])
    # a jit_compile is a resolve's however deep below it it lies
    assert [s.args["fun"] for s in st.outside_resolve(ring.spans)] == [
        "jit(a)", "b"]


def test_a_platform_without_a_start_time_counts_from_the_epoch():
    ring = Ring()
    one_run(ring, 0.0, steps=5)
    got = st.timeline(ring.spans, 5, process_start=None)
    assert got["preprogram"] is None
    assert got["extent"] == pytest.approx(14.02)
    assert got["unspanned"] == pytest.approx(14.02 - SPANNED)


def test_a_parents_ring_gives_none_five_times(monkeypatch):
    ring = Ring()
    one_run(ring, 0.0, steps=5, jit=False)
    monkeypatch.setattr(ps, "program_ring", lambda: ring.spans)
    monkeypatch.setattr(st, "program_ring", lambda: ring.spans)
    run_ = {"counters": {"steps": 5}}
    # the parent's tracer has no `process_start`: no timeline at all
    monkeypatch.setattr(st, "program_start", lambda: (False, None))
    assert [run.read_metric(name, run_) for name in FAMILY] == [None] * 5
    # PR 26's readers still read that ring
    assert run.read_metric("step_call_ms.train", run_) == pytest.approx(8.0)
    # a program with the timeline but a ring without a single jit_compile
    # (no listener ever fired): ``jit`` alone is silent
    monkeypatch.setattr(st, "program_start", lambda: (True, -7.5))
    got = dict(zip(FAMILY, (run.read_metric(n, run_) for n in FAMILY)))
    assert got["setup_timeline_jit_s.train"] is None
    assert got["setup_timeline_data_s.train"] == pytest.approx(DATA)
    assert got["setup_timeline_preprogram_s.train"] == 7.5
    # fewer calls than the run says it made: silence, in every reader
    for steps in (6, 0):
        assert [run.read_metric(n, {"counters": {"steps": steps}})
                for n in FAMILY] == [None] * 5
    assert [run.read_metric(n, {"counters": {}}) for n in FAMILY] == [None] * 5


def test_after_an_earlier_run_in_the_process(monkeypatch):
    """The second run's set-up holds its own spans alone; where it started
    cannot be told (the time since the first run's last call is that run's
    reference), so ``preprogram`` and ``unspanned`` are silent."""
    ring = Ring()
    end = one_run(ring, 0.0, steps=4)
    ring.add(st.JIT, end + 1.0, 30.0, stage="backend_compile", fun="jit(ref)")
    one_run(ring, end + 40.0, steps=6)
    monkeypatch.setattr(st, "program_ring", lambda: ring.spans)
    monkeypatch.setattr(st, "program_start", lambda: (True, -7.5))
    run_ = {"counters": {"steps": 6}}
    got = dict(zip(FAMILY, (run.read_metric(n, run_) for n in FAMILY)))
    assert got["setup_timeline_preprogram_s.train"] is None
    assert got["setup_timeline_unspanned_s.train"] is None
    assert got["setup_timeline_data_s.train"] == pytest.approx(DATA)
    assert got["setup_timeline_resolve_s.train"] == pytest.approx(RESOLVE)
    # the reference's compile between the runs is this run's by the rule
    # "after the earlier run's last call": counted once, with its own two
    assert got["setup_timeline_jit_s.train"] == pytest.approx(JIT + 30.0)


def _setup_and_timeline():
    ring = Ring()
    one_run(ring, 0.0, steps=5)
    return (ps.split_run(ring.spans, 5)["setup"],
            st.timeline(ring.spans, 5, process_start=-7.5))


def test_resolve_is_what_setup_resolve_s_summed():
    """PR 26's retired `setup_resolve_s.train` summed set-up's
    `compile_resolve` spans; they do not overlap, so the union is the sum."""
    setup, got = _setup_and_timeline()
    assert got["resolve"] == pytest.approx(
        sum(s.dur for s in setup if s.name == ps.RESOLVE))


def test_data_holds_what_setup_data_s_read():
    """PR 26's retired `setup_data_s.train` was the union of
    `corpus_simulate`, `graph_lower` and `dataset_upload`; ``data`` holds
    it and `trace_lower` past its `graph_lower`, [3.5, 5.5]."""
    setup, got = _setup_and_timeline()
    old = union_length((s.t0, s.t0 + s.dur) for s in setup if s.name in (
        "corpus_simulate", "graph_lower", "dataset_upload"))
    assert old == pytest.approx(2.0 + 0.5 + 0.3)
    assert got["data"] == pytest.approx(old + 2.0) == pytest.approx(DATA)


CELLS = ("train-1024", "train-4096", "stream-lm-8k-packed",
         "stream-lm-8k-longdoc", "stream-lm-8k-mla-longdoc")
# the layer each part of set-up is told under in `PERF.md` section 3
LAYERS = {"preprogram": "train loop", "data": "train loop",
          "resolve": "compile and caches", "jit": "compile and caches",
          "unspanned": "train loop"}


@pytest.mark.parametrize("part", list(LAYERS))
def test_each_part_has_its_entry_its_reader_and_its_cells(part):
    """Each of the five by name, wherever in ``per_layer`` it stands: its
    reader, the fields `ROADMAP.md` Queue 1 item 14 gives, and a list that
    holds the five cells of PR 39 (a later cell may append itself)."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    name = f"setup_timeline_{part}_s.train"
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert (run.HERE / "layer_metrics" / f"{name}.py").is_file()
    assert {k: entry[k] for k in ("unit", "better", "source", "moves")} == {
        "unit": "s", "better": "lower", "source": "program_counter",
        "moves": "setup_s"}
    assert entry["layer"] == LAYERS[part]
    assert set(CELLS) <= set(entry["workloads"])


# --- real rehearsal runs -------------------------------------------------------

_FRESH = """
import copy, json, sys
sys.path.insert(0, {root!r})
from chipbench import rehearse, run
from chipbench import program_spans as ps, setup_timeline as st
from chipbench.probes import setup_timeline as probe
toy = copy.deepcopy(rehearse.TOY)
toy["cache_root"] = {cache!r}
res = run.run_cell("train-1024", 3700000177, 0.3, True, rehearsal=toy)
steps = res["attempted"]
ring = ps.program_ring()
from nerrf_tpu.tracing import DEFAULT_TRACER as tracer
parts = st.timeline(ring, steps, tracer.process_start)
split = ps.split_run(ring, steps)
family = {{name: run.read_metric(name, {{"counters": {{"steps": steps}}}})
          for name in {family!r}}}
print(json.dumps({{
    "metrics": {{**{{k: v["value"] for k, v in res["metrics"].items()}},
                **family}},
    "setup_s": res["extras"]["end_to_end"]["setup_s"],
    "parts": parts, "process_start": tracer.process_start,
    "first_call": split["window"][0].t0,
    "names": sorted({{s.name for s in split["setup"]}}),
    "report": probe.report(steps)}}))
"""


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    """One traced `train-1024` rehearsal in a process of its own, so that
    the process's start is the run's."""
    code = _FRESH.format(root=str(run.ROOT), family=FAMILY,
                         cache=str(tmp_path_factory.mktemp("aot")))
    out = subprocess.run([sys.executable, "-c", code], cwd=run.ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_identity_holds_on_a_real_run_in_its_own_process(fresh):
    parts, got = fresh["parts"], fresh["metrics"]
    assert set(FAMILY) <= set(got)
    for part in ("preprogram", "data", "resolve", "jit", "unspanned"):
        assert got[f"setup_timeline_{part}_s.train"] == parts[part]
        assert parts[part] >= 0
    # the extent, told the other way: the window's first call on the
    # tracer's clock less the process's start on it
    extent = fresh["first_call"] - fresh["process_start"]
    assert parts["preprogram"] + parts["spanned"] + parts["unspanned"] == \
        pytest.approx(extent, abs=0.010)
    assert parts["preprogram"] == -fresh["process_start"] > 0
    # a cold run resolves by compiling: the traces and compiles inside the
    # resolution are its, not ``jit``'s
    assert parts["jit"] < parts["resolve"]
    # the benchmark's own clock starts at its first line, a little after
    # the process: the extent is `setup_s` and the interpreter's start
    assert 0 <= extent - fresh["setup_s"] < 2.0
    assert {"corpus_simulate", "trace_lower", "graph_lower", "step_build",
            "dataset_upload", "compile_resolve", "compile_resolve.lower",
            "compile_resolve.compile", "compile_resolve.persist",
            "jit_compile", "module_import"} <= set(fresh["names"])


def test_the_probe_reports_stages_functions_and_holes(fresh):
    report = fresh["report"]
    assert report.startswith("set-up, seconds: preprogram ")
    for needle in ("compile_resolve.lower", "compile_resolve train_step "
                   "fresh:absent", "lower ", "persist ", " B)",
                   "jit_compile by function", "flat_step",
                   "[in compile_resolve.lower]", "(process start) -> "):
        assert needle in report, needle


def test_the_probe_is_a_chip_command_and_says_so_off_a_tpu(capsys):
    from chipbench.probes import setup_timeline as probe

    assert probe.main(["--workload", "train-1024", "--seed", "1"]) == 1
    assert "no accelerator" in capsys.readouterr().err


def _stream_toy(which):
    mod = importlib.import_module(f"test_chipbench_{which}")
    return mod.CELL, mod.TOY


@pytest.fixture()
def small_blocks(monkeypatch):
    """The stream toys' block sizes (their own files' autouse fixtures)."""
    from nerrf_tpu.ops import dsa, mla, moe

    for mod in (dsa, mla):
        monkeypatch.setattr(mod, "QUERY_BLOCK", 64)
        monkeypatch.setattr(mod, "KEY_SPAN", 128)
    monkeypatch.setattr(moe, "TILE", 16)


@pytest.mark.parametrize("which", ["train-1024", "train-4096", "phi4flash",
                                   "keyevl2", "glm47flash"])
def test_every_cells_traced_rehearsal_prints_the_family(
        which, small_blocks, capsys, tmp_path):
    cell, toy = ((which, rehearse.TOY) if which.startswith("train-")
                 else _stream_toy(which))
    toy = copy.deepcopy(toy)
    toy["cache_root"] = str(tmp_path / "aot")
    rc = run.main(["--workload", cell, "--seed", "3700000321", "--seconds",
                   "0.3", "--trace", "1"], rehearsal=toy)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    # the readers, as `run.py` calls them: in the program's process, after
    # the window; where a reader reads, the line has its number
    run_ = {"counters": {"steps": res["attempted"]}}
    got = {name: run.read_metric(name, run_) for name in FAMILY}
    assert {k: v["value"] for k, v in res["metrics"].items()
            if k in FAMILY} == {k: v for k, v in got.items() if v is not None}
    setup_s = res["extras"]["end_to_end"]["setup_s"]
    # the three that an earlier run in this process cannot silence
    assert 0 < got["setup_timeline_data_s.train"] < setup_s
    assert 0 < got["setup_timeline_resolve_s.train"] < setup_s
    # (after an earlier run in the process its reference's compiles are
    # in ``jit``: the rule "after that run's last call")
    assert 0 <= got["setup_timeline_jit_s.train"] < setup_s
    # the summed `compile_resolve` spans of set-up (PR 26's retired rule)
    split = ps.split_run(ps.program_ring(), res["attempted"])
    assert got["setup_timeline_resolve_s.train"] == pytest.approx(sum(
        s.dur for s in split["setup"] if s.name == ps.RESOLVE))
    for name in ("setup_timeline_preprogram_s.train",
                 "setup_timeline_unspanned_s.train"):
        assert got[name] is None or 0 <= got[name]
