"""Experiment runner: named config → corpus → train → checkpoint → report."""

import json

import pytest

from nerrf_tpu.train.run import run_experiment


@pytest.mark.slow
def test_run_toy_experiment_produces_artifacts(tmp_path):
    report = run_experiment("toy-graphsage", tmp_path, num_steps=60)
    assert (tmp_path / "experiment.json").exists()
    assert (tmp_path / "model" / "model_config.json").exists()
    on_disk = json.loads((tmp_path / "metrics.json").read_text())
    assert on_disk["experiment"] == "toy-graphsage"
    assert report["metrics"]["edge_auc"] > 0.5
    # the report names what served the step and where the loss went
    assert report["kernel_path"]["gnn_aggregation"] == "segment"  # CPU
    assert report["loss"]["last"] < report["loss"]["first"]
    # checkpoint round-trips into the undo path's loader
    from nerrf_tpu.train.checkpoint import load_checkpoint

    params, cfg = load_checkpoint(tmp_path / "model")
    assert cfg.gnn.num_layers == 8  # toy experiment's model size


@pytest.mark.slow
def test_run_sharded_experiment_on_virtual_mesh(tmp_path):
    """multihost-online's dp×tp sharded path on the 8-device virtual mesh —
    at test scale.  The registry config's corpus (16×600 s) is a production
    size: building it plus the sharded CPU compile took >20 min and ~22 GB
    in CI, so the test runs the same experiment shrunk via the JSON-config
    path (which doubles as coverage for file-based experiment configs)."""
    import dataclasses

    from nerrf_tpu.config import get_experiment

    exp = get_experiment("multihost-online")
    small = dataclasses.replace(
        exp,
        corpus=dataclasses.replace(exp.corpus, num_traces=4,
                                   duration_sec=90.0, num_target_files=6,
                                   benign_rate_hz=6.0),
        train=dataclasses.replace(exp.train, model=exp.train.model.small,
                                  batch_size=8, num_steps=4, eval_every=0),
    )
    cfg_path = tmp_path / "exp.json"
    small.save(cfg_path)
    report = run_experiment(str(cfg_path), tmp_path / "out", calibrate=False)
    assert report["devices"] == 8
    assert report["steps_per_sec"] > 0
    # the layout as placed: tp really partitions parameters, the batch
    # really spans the mesh, and the ops the partitioned program is made
    # of are named (the routes of this backend, as in a one-device run)
    assert report["sharding"]["mesh"] == {"dp": 4, "tp": 2, "sp": 1}
    assert report["sharding"]["tp_sharded_leaves"] > 0
    assert report["sharding"]["batch_devices"] == 8
    assert set(report["kernel_path"].values()) <= {"xla", "segment", "rnn"}


@pytest.mark.parametrize("backend, nodes, want", [
    ("tpu", 1024, ("xla_selection_matmul", "dense_adj", "fused")),
    ("tpu", 4096, ("xla_selection_matmul", "dense_adj", "fused")),
    ("tpu", 16384, ("xla", "segment", "fused")),
    ("cpu", 4096, ("xla", "segment", "rnn"))])
def test_kernel_path_is_a_function_of_backend_and_bucket(monkeypatch, backend,
                                                         nodes, want):
    """What `train.run` stamps into its log and `metrics.json`: the route
    of every op that has two, at the run's own node bucket, and nothing
    that a registration or a mesh could have changed."""
    import jax

    from nerrf_tpu.models import JointConfig
    from nerrf_tpu.train.run import _kernel_path

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert _kernel_path(JointConfig(), nodes) == dict(
        zip(("gather_rows", "gnn_aggregation", "lstm_impl"), want))


def test_trace_out_writes_the_runs_spans_even_when_the_run_fails(
        tmp_path, monkeypatch):
    """`train.run --trace-out FILE` writes the span ring at exit (the
    set-up timeline of docs/operations.md), whatever the run's end."""
    from nerrf_tpu import tracing
    from nerrf_tpu.train import run as train_run

    def fake_run(name, out, *_a, **_k):
        with tracing.span("train_setup"):
            if name == "boom":
                raise RuntimeError("diverged")
        return {"gates": {"ok": True}}

    monkeypatch.setattr(train_run, "run_experiment", fake_run)
    path = tmp_path / "t" / "trace.json"
    argv = ["--out", str(tmp_path), "--no-aot-cache", "--trace-out",
            str(path)]
    assert train_run.main(["--experiment", "toy"] + argv) == 0
    events = tracing.load_chrome_trace(path)
    assert "train_setup" in {e["name"] for e in events}
    other = json.loads(path.read_text())["otherData"]
    assert "process_start_sec" in other
    path.unlink()
    with pytest.raises(RuntimeError):
        train_run.main(["--experiment", "boom"] + argv)
    assert path.exists()
