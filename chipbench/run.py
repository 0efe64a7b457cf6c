"""The benchmark's command:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data.  This file knows no cell, configuration, traffic kind or
metric by name: it looks the cell up in ``BENCHMARK.json``, reads its
traffic mix from ``chipbench/traffic/<traffic>.json`` (a data file that
names its generator), the cell's own parameters from
``chipbench/workloads/<cell>.json`` and its configuration from the file
``BENCHMARK.json`` names, hands them to the mix's generator,
``chipbench/traffic/<generator>.py``, and, in a ``--trace 1`` run, gives
the reduced trace and the run's counters to each per-layer metric's reader,
``chipbench/layer_metrics/<metric>.py``.  See ``chipbench/README.md``.

The last line of standard output is the result; every number compared for
`correct` is printed beside its limit as the last lines of standard error
and under the result's last key.  Without a TPU (or with fewer chips than
the cell asks for) it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent


class BenchError(RuntimeError):
    """A run that cannot give a number: one line on stderr, exit 1."""


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


class Context:
    """What a traffic kind gets: the cell, its configuration, the run's
    arguments and the device."""

    def __init__(self, cell, config, seed, seconds, trace, device,
                 cache_root=None):
        self.cell, self.config = cell, config
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device, self.cache_root = device, cache_root
        self.t_start = T_START
        self.log = say
        self._tmp = []

    def make_trace_dir(self) -> str:
        path = tempfile.mkdtemp(prefix="chipbench_trace_")
        self._tmp.append(path)
        return path

    def cleanup(self) -> None:
        for path in self._tmp:
            shutil.rmtree(path, ignore_errors=True)


def _load_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise BenchError(f"{what} is missing: {path}")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """-> (BENCHMARK.json, its entry for the cell, the cell's parameters:
    those of its traffic mix under those of its own file, its
    configuration)."""
    bench = _load_json(ROOT / "BENCHMARK.json", "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise BenchError(f"unknown workload {name!r}: BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = entries[0]
    mix = _load_json(HERE / "traffic" / f"{entry['traffic']}.json",
                     f"the traffic mix of {name!r}")
    if "generator" not in mix:
        raise BenchError(f"traffic mix {entry['traffic']!r} names no "
                         "generator")
    cell = {**mix, **_load_json(HERE / "workloads" / f"{name}.json",
                                f"the cell file of {name!r}")}
    conf = [c for c in bench["configs"] if c["name"] == entry["config"]]
    if not conf:
        raise BenchError(f"cell {name!r} names configuration "
                         f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = _load_json(ROOT / conf[0]["file"],
                        f"the configuration file of {entry['config']!r}")
    return bench, entry, cell, config


def load_peaks(device_kind: str) -> dict:
    table = _load_json(HERE / "peaks.json", "the peaks file")
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json: "
            "add its row, with its source, before measuring on it")
    return table[device_kind]


def find_device(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    if not rehearsal and dev.platform != "tpu":
        raise BenchError(f"no accelerator: JAX found platform "
                         f"{dev.platform!r}; this benchmark measures a TPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chip(s), JAX found "
                         f"{len(devices)}")
    return dev, len(devices)


def enable_caches() -> None:
    """JAX's persistent cache where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.compile_cache``),
    with the thresholds lowered for this process so that the sub-second
    programs of set-up are kept too."""
    import jax
    from nerrf_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_metric(name: str, run: dict):
    """The value of per-layer metric ``name`` from its reader file, or None
    where the reader finds nothing to read."""
    path = HERE / "layer_metrics" / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"per-layer metric {name!r} has no reader: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _metrics_of(bench: dict, kind: str, cell_name: str):
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: dict | None = None) -> dict:
    """One run -> the result object.  ``rehearsal`` (tests only, never the
    command line) overrides cell and configuration fields with toy sizes
    and lets the run proceed off a TPU; the result stamps itself."""
    sys.path.insert(0, str(ROOT))
    bench, entry, cell, config = load_cell(workload)
    if rehearsal is not None:
        from chipbench.rehearse import apply as apply_rehearsal

        cell, config = apply_rehearsal(cell, config, rehearsal)
    if not (ROOT / "nerrf_tpu").is_dir():
        raise BenchError(f"the program is missing: {ROOT / 'nerrf_tpu'}")
    traffic = importlib.import_module(f"chipbench.traffic.{cell['generator']}")

    dev, count = find_device(int(entry["chips"]), rehearsal is not None)
    peaks = (rehearsal or {}).get("peaks") or load_peaks(dev.device_kind)
    cache_root = None
    if rehearsal is None:
        enable_caches()
    else:
        cache_root = rehearsal.get("cache_root")
    ctx = Context(cell, config, seed, seconds, trace, dev,
                  cache_root=cache_root)
    try:
        rec = traffic.run(ctx)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": count,
                  "memory_peak_bytes": rec["memory_peak_bytes"]}
        result = {"correct": bool(rec["correct"]),
                  "attempted": int(rec["attempted"]),
                  "failed": int(rec["failed"])}
        if not trace:
            wanted = _metrics_of(bench, "end_to_end", workload)
            result["metrics"] = {
                m["name"]: {"value": rec["end_to_end"][m["name"]],
                            "unit": m["unit"]} for m in wanted}
        else:
            from chipbench import trace_reduce

            raw = trace_reduce.read_xplane(
                trace_reduce.find_xplane(rec["trace_dir"]))
            reduced = trace_reduce.reduce(
                raw, rec["counters"].get("scope_groups"))
            if not reduced.get("busy_s"):
                raise BenchError("the traced window holds no device "
                                 "operation")
            reduced["windows_in_trace"] = (rec["counters"]["steps"]
                                           * rec["counters"]["batch"])
            run = {"trace": reduced, "counters": rec["counters"],
                   "peaks": peaks, "cell": cell, "config": config}
            metrics = {}
            for m in _metrics_of(bench, "per_layer", workload):
                value = read_metric(m["name"], run)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result["metrics"] = metrics
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            by_scope = sorted((reduced["scope_s"] or {}).items(),
                              key=lambda kv: -kv[1])
            result["breakdown"] = {
                # device time by scope group first, then single operations
                "device_ops": ([[f"scope:{k}", v] for k, v in by_scope]
                               + [[k, v] for k, v in reduced["top_ops"]]
                               )[:10],
                "idle_gaps": [[k, v] for k, v in reduced["idle_by_span_s"]]}
            rec["extras"]["leaf_op_s"] = reduced["leaf_op_s"]
            rec["extras"]["scope_s"] = reduced["scope_s"]
            rec["extras"]["step_s_p95"] = (
                reduced["step_s"][int(0.95 * (len(reduced["step_s"]) - 1))]
                if reduced["step_s"] else None)
            rec["extras"]["end_to_end"] = rec["end_to_end"]
        result["device"] = device
        if rehearsal is not None:
            result["rehearsal"] = True
        result["extras"] = rec["extras"]
        result["compared"] = rec["compared"]
        return result
    finally:
        ctx.cleanup()


def main(argv=None, rehearsal: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearsal=rehearsal)
    except BenchError as e:
        say(f"error: {e}")
        return 1
    for name, (value, limit) in result["compared"].items():
        say(f"compared {name} = {value:.6g} (limit {limit:.6g}) "
            f"{'ok' if value <= limit else 'OVER'}")
    say(f"correct = {result['correct']}")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
