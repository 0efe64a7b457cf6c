"""The driver's command line at a toy width on the CPU, in one process:
both cells, both ``--trace`` values, the result line's keys, the refusals
with a reason, and the timed path broken underneath (`correct` must come
out false)."""

import copy
import json

import pytest

from chipbench import rehearse, run
from chipbench.traffic import train_resident as tr

CELLS = ("train-1024", "train-4096")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    toy = copy.deepcopy(rehearse.TOY)
    toy["cache_root"] = str(tmp_path_factory.mktemp("aot"))
    return toy


def bench_with_cell(monkeypatch, **entry):
    """`BENCHMARK.json` as the harness reads it, with one more cell: a copy
    of the first with ``entry`` laid over it."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(dict(bench["workloads"][0], **entry))
    real = run._load_json
    monkeypatch.setattr(run, "_load_json", lambda p, what: (
        bench if p.name == "BENCHMARK.json" else real(p, what)))
    return bench


def last_line(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", (0, 1))
def test_command_line_prints_the_contracts_last_line(toy, capsys, cell, trace):
    # a seed past 2**31, as the driver's are
    rc = run.main(["--workload", cell, "--seed", "2200000321", "--seconds",
                   "0.5", "--trace", str(trace)], rehearsal=toy)
    assert rc == 0
    res, err = last_line(capsys)
    assert list(res)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1 and res["rehearsal"] is True
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    if trace:
        names = {m["name"] for m in bench["per_layer"]}
        assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(res["breakdown"]["device_ops"]) <= 10
        assert res["metrics"]["compiles_in_window.train"]["value"] == 0
        assert 0 < res["metrics"]["step_mfu.train"]["value"] < 100
        # device time by scope group: the program's scopes are found
        groups = dict(res["breakdown"]["device_ops"])
        assert groups["scope:lstm"] > 0 and groups["scope:gnn_layer"] > 0
        assert sum(res["extras"]["scope_s"].values()) == pytest.approx(
            res["extras"]["leaf_op_s"])
        for name in ("gnn_layers_roofline.train", "lstm_roofline.train"):
            assert res["metrics"][name]["value"] > 0
    else:
        names = {m["name"] for m in bench["end_to_end"]}
        assert set(res["metrics"]) == names
    assert set(res["metrics"]) <= names
    for name, m in res["metrics"].items():
        assert m["unit"] == next(
            x["unit"] for x in bench["end_to_end"] + bench["per_layer"]
            if x["name"] == name)
    # every number compared stands beside its limit, on stderr's last lines
    tail = err.strip().splitlines()[-(len(res["compared"]) + 1):]
    for (name, (value, limit)), line in zip(res["compared"].items(), tail):
        assert f"compared {name} = " in line and "limit" in line
        assert value <= limit
    assert tail[-1].endswith("correct = True")


def test_same_seed_same_inputs_and_other_seed_other_inputs(toy):
    a = tr.make_idx_table(2_200_000_001, 4, 8, 4)
    assert (a == tr.make_idx_table(2_200_000_001, 4, 8, 4)).all()
    assert (a != tr.make_idx_table(2_200_000_002, 4, 8, 4)).any()
    assert all(len(set(row)) == 4 for row in a.tolist())
    # epochs: every two rows hold all eight windows once
    assert all(sorted(a[i:i + 2].ravel().tolist()) == list(range(8))
               for i in (0, 2))


def test_refusals_name_their_reason(toy, capsys, monkeypatch):
    def err_of(argv, rehearsal):
        assert run.main(argv, rehearsal=rehearsal) == 1
        out = capsys.readouterr()
        assert out.out.strip() == ""          # no result line
        return out.err
    base = ["--seed", "7", "--seconds", "0.2", "--trace", "0"]
    assert "unknown workload" in err_of(["--workload", "nope"] + base, toy)
    # a cell BENCHMARK.json names but whose file is missing
    real = run._load_json
    bench = bench_with_cell(monkeypatch, name="ghost")
    assert "the cell file of 'ghost' is missing" in err_of(
        ["--workload", "ghost"] + base, toy)
    # ... or whose traffic mix has no data file
    bench["workloads"][-1]["traffic"] = "no-such-mix"
    assert "the traffic mix of 'ghost' is missing" in err_of(
        ["--workload", "ghost"] + base, toy)
    monkeypatch.setattr(run, "_load_json", real)
    # off a TPU the real command gives no number
    assert "no accelerator" in err_of(["--workload", CELLS[0]] + base, None)
    # an unknown device_kind is an error, not a default
    with pytest.raises(run.BenchError, match="not in chipbench/peaks.json"):
        run.load_peaks("TPU v9 imaginary")
    # a dataset smaller than the batch
    small = rehearse.merge(toy, {"cell": {"batch": 64, "windows": 64}})
    with pytest.raises(RuntimeError, match="dataset smaller than the batch"):
        run.run_cell(CELLS[0], 7, 0.2, False, rehearsal=small)
    with pytest.raises(RuntimeError, match="dataset smaller than the batch"):
        tr.make_idx_table(7, 4, windows=3, batch=4)


def test_a_cell_and_its_mix_dropped_in_as_data_files_are_found(
        tmp_path, monkeypatch):
    """A later PR adds a cell of an existing generator with data files and
    `BENCHMARK.json` entries alone: a second mix for a configuration that
    has a cell already."""
    for d in ("traffic", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "traffic" / "resident-b16.json").write_text(json.dumps(
        {"generator": "train_resident", "batch": 16, "windows": 32}))
    (tmp_path / "workloads" / "train-1024-b16.json").write_text(json.dumps(
        {"limits": {"update_gap": 0.05}, "batch": 4}))
    bench_with_cell(monkeypatch, name="train-1024-b16",
                    traffic="resident-b16")
    monkeypatch.setattr(run, "HERE", tmp_path)
    _, entry, cell, config = run.load_cell("train-1024-b16")
    assert entry["config"] == config["name"] == "joint-100h"
    assert cell["generator"] == "train_resident" and cell["windows"] == 32
    assert cell["batch"] == 4          # the cell's own file has the last word
    (tmp_path / "traffic" / "resident-b16.json").write_text("{}")
    with pytest.raises(run.BenchError, match="names no generator"):
        run.load_cell("train-1024-b16")


def _break_state_unchanged(monkeypatch):
    """A step that returns its state unchanged."""
    real = tr.build_step

    def build(*args, **kwargs):
        state, step, infos = real(*args, **kwargs)

        def frozen(st, rng):
            import jax
            import jax.numpy as jnp

            keep = jax.tree_util.tree_map(jnp.copy, st)   # st is donated
            _new, loss, aux, rng2 = step(st, rng)
            return keep, loss, aux, rng2

        return state, frozen, infos

    monkeypatch.setattr(tr, "build_step", build)


def _break_half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    from nerrf_tpu.train import loop

    real = loop.make_loss_fn

    def make(model, cfg):
        inner = real(model, cfg)

        def loss_fn(params, batch, rng):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return inner(params, half, rng)

        return loss_fn

    monkeypatch.setattr(loop, "make_loss_fn", make)


@pytest.mark.parametrize("fault", (_break_state_unchanged, _break_half_batch))
def test_broken_timed_path_comes_out_not_correct(toy, monkeypatch, tmp_path,
                                                 fault):
    fault(monkeypatch)
    broken = dict(toy, cache_root=str(tmp_path))   # never a sound executable
    res = run.run_cell(CELLS[0], 2_200_000_555, 0.3, False, rehearsal=broken)
    assert res["correct"] is False
    over = [k for k, (v, lim) in res["compared"].items() if v > lim]
    assert over, res["compared"]
