"""Config layer: dataclass ⇄ JSON round-trips and the experiment registry."""

import json

import pytest

from nerrf_tpu.config import (
    CONFIG_DIR,
    EXPERIMENTS,
    Experiment,
    from_dict,
    get_experiment,
    to_dict,
)
from nerrf_tpu.models.graphsage import GraphSAGEConfig
from nerrf_tpu.train.loop import TrainConfig


def test_registry_matches_baseline_configs():
    assert set(EXPERIMENTS) == {
        "toy-graphsage", "lstm-impact", "joint-100h", "joint-dense",
        "mcts-lockbit", "multihost-online", "stream-phi4-mini-flash",
        "stream-keye-vl2-30b-a3b",
        "stream-glm-4.7-flash", "stream-laguna-s-2.1",
        "stream-kimi-linear-48b-a3b",
    }


@pytest.mark.parametrize("name", sorted(EXPERIMENTS))
def test_experiment_json_roundtrip(name):
    exp = EXPERIMENTS[name]
    back = Experiment.from_json(exp.to_json())
    assert back == exp
    # JSON form is pure data
    json.loads(exp.to_json())


def test_dtype_roundtrips_as_name():
    import jax.numpy as jnp

    cfg = GraphSAGEConfig(dtype=jnp.float32)
    d = to_dict(cfg)
    assert d["dtype"] == "float32"
    assert from_dict(GraphSAGEConfig, d).dtype is jnp.float32
    # default bfloat16 too
    d2 = to_dict(GraphSAGEConfig())
    assert d2["dtype"] == "bfloat16"
    assert from_dict(GraphSAGEConfig, d2).dtype is jnp.bfloat16


def test_unknown_key_raises():
    d = to_dict(TrainConfig())
    d["not_a_field"] = 1
    with pytest.raises(KeyError, match="not_a_field"):
        from_dict(TrainConfig, d)


def test_checked_in_configs_match_registry():
    """configs/*.json must stay in sync with the registry (run `config sync`)."""
    for name, exp in EXPERIMENTS.items():
        path = CONFIG_DIR / f"{name}.json"
        assert path.exists(), f"missing {path}; run python -m nerrf_tpu.config sync"
        assert Experiment.load(path) == exp, f"{path} is stale"


def test_build_corpus_uses_corpus_config():
    exp = get_experiment("toy-graphsage")
    train, evals = exp.build_corpus()
    assert len(train) + len(evals) == exp.corpus.num_traces
    assert len(evals) == round(exp.corpus.num_traces * exp.corpus.eval_fraction)
    # both classes present in the train split (Bresenham spread)
    assert any(t.ground_truth is not None for t in train)
    assert any(t.ground_truth is None for t in train)


def test_get_experiment_by_name_and_path(tmp_path):
    exp = get_experiment("toy-graphsage")
    assert exp.name == "toy-graphsage"
    p = tmp_path / "x.json"
    exp.save(p)
    assert get_experiment(str(p)) == exp
    with pytest.raises(KeyError):
        get_experiment("no-such-experiment")
