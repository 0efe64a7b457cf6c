"""Model checkpoint save/restore (orbax).

The reference has no model checkpointing (no models existed; SURVEY.md §5).
Here: standard orbax checkpoints of the param pytree plus a JSON sidecar with
the model config, so a checkpoint is self-describing and `nerrf undo
--model-dir` can reconstruct the exact network.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil
from pathlib import Path
from typing import Tuple

import jax
import orbax.checkpoint as ocp

from nerrf_tpu.models import GraphSAGEConfig, JointConfig, LSTMConfig
from nerrf_tpu.tracing import span as trace_span


# Sidecar schema version, stamped into every checkpoint and validated at
# load.  The feature-dim stamp below catches *known* drift axes (node/edge/
# seq widths); the version catches everything else — bump it whenever the
# meaning of stamped fields or the param-tree layout changes such that old
# checkpoints must not load silently.  v2: r4 feature stamp era + the
# three aggregation names (segment / dense_adj / fused: XLA compositions
# of one sum over the same param tree, so no bump for any of them;
# recorded here for the audit trail).
SCHEMA_VERSION = 2
# the oldest stamped schema this code still loads: raise this floor (not
# just SCHEMA_VERSION) when a change means older checkpoints must not load
# silently — only a floor can actually reject them
MIN_SCHEMA_VERSION = 2


@contextlib.contextmanager
def _atomic_dir(path: Path):
    """Write-temp-then-rename checkpoint publish.

    The body saves into a sibling temp directory; only a *complete* save is
    renamed into place (rename(2) is atomic on one filesystem), so a
    concurrent reader — the model registry's poll loop, a serve pod's
    loader — can never observe a torn checkpoint directory: it sees the old
    complete checkpoint, the new complete checkpoint, or nothing.  A crash
    mid-save leaves the temp directory behind (reclaimed by the next save
    to the same path) and the previous checkpoint recoverable: a crash in
    the narrow window between the two final renames parks it at
    ``.<name>.old``, which the next save renames back before starting."""
    path = Path(path).absolute()
    tmp = path.parent / f".{path.name}.tmp"
    old = path.parent / f".{path.name}.old"
    if not path.exists() and old.exists():
        # crashed between the two renames last time: the parked previous
        # checkpoint is the only good copy — restore it, never discard it
        os.rename(old, path)
    for leftover in (tmp, old):
        if leftover.exists():
            shutil.rmtree(leftover)
    tmp.mkdir(parents=True)
    try:
        yield tmp
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    # swap: park the previous checkpoint, rename the new one in, then
    # reclaim — both renames are atomic, so no reader ever sees a mix
    if path.exists():
        os.rename(path, old)
    os.rename(tmp, path)
    shutil.rmtree(old, ignore_errors=True)


def _read_sidecar(path: Path, name: str) -> dict:
    """The checkpoint's JSON sidecar, with the two corruption modes turned
    into one-line actionable errors instead of a raw KeyError/JSONDecodeError
    surfacing deep inside the loader."""
    f = path / name
    try:
        return json.loads(f.read_text())
    except FileNotFoundError:
        raise FileNotFoundError(
            f"not a checkpoint: {path} has no {name} sidecar (wrong "
            f"directory, a torn copy, or a save that never finished)"
        ) from None
    except json.JSONDecodeError as e:
        raise ValueError(
            f"corrupt checkpoint sidecar {f}: not valid JSON ({e})") from None
    except UnicodeDecodeError as e:
        # bit rot rarely respects UTF-8 boundaries: a mangled byte inside
        # a multi-byte sequence fails DECODE before json ever parses —
        # same corruption class, same one-line error
        raise ValueError(
            f"corrupt checkpoint sidecar {f}: not valid UTF-8 ({e})"
        ) from None


def _check_schema_version(meta: dict, path: Path) -> None:
    got = meta.get("schema_version")
    if got is None:
        # legacy unstamped sidecar: falls through to the feature-layout
        # check, which produces its own actionable retrain message
        return
    if got > SCHEMA_VERSION:
        raise ValueError(
            f"retrain or upgrade: checkpoint {path} carries sidecar schema "
            f"v{got}, this code writes v{SCHEMA_VERSION} — it was saved by "
            f"a newer version of the code")
    if got < MIN_SCHEMA_VERSION:
        raise ValueError(
            f"retrain: checkpoint {path} carries sidecar schema v{got}, "
            f"older than the oldest supported v{MIN_SCHEMA_VERSION} — its "
            f"layout predates changes this code cannot load")


def _feature_layout() -> dict:
    """The input-feature layout the current code produces.  Stamped into
    every sidecar and verified at load: NODE_FEATURE_DIM moved 22→24 in r4
    and a stale checkpoint only failed at apply time with an opaque
    dot-dimension shape error deep in Flax/XLA (r4 advisor, medium)."""
    from nerrf_tpu.data.sequences import SEQ_FEATURE_DIM
    from nerrf_tpu.graph.builder import EDGE_FEATURE_DIM, NODE_FEATURE_DIM
    return {"node": NODE_FEATURE_DIM, "edge": EDGE_FEATURE_DIM,
            "seq": SEQ_FEATURE_DIM}


def _check_feature_layout(meta: dict, path: Path, keys: tuple) -> None:
    want = _feature_layout()
    got = meta.get("features")
    if got is None:
        raise ValueError(
            f"checkpoint {path} predates feature-layout versioning (no "
            f"'features' field in its sidecar); the input feature layout "
            f"has since changed (current: {want}) — retrain, or stamp the "
            f"sidecar by hand if you are certain it matches")
    bad = {k: (got.get(k), want[k]) for k in keys if got.get(k) != want[k]}
    if bad:
        raise ValueError(
            f"retrain: feature layout changed — checkpoint {path} was "
            f"trained with {got}, current code produces {want} "
            f"(mismatched: {bad})")


def save_checkpoint(path: str | Path, params, cfg: JointConfig,
                    calibration: dict | None = None,
                    quality_profile: dict | None = None,
                    provenance: dict | None = None) -> None:
    meta = {
        "gnn": {"hidden": cfg.gnn.hidden, "num_layers": cfg.gnn.num_layers,
                "dropout": cfg.gnn.dropout,
                "aggregation": cfg.gnn.aggregation},
        "lstm": {"hidden": cfg.lstm.hidden, "num_layers": cfg.lstm.num_layers,
                 "dropout": cfg.lstm.dropout, "impl": cfg.lstm.impl},
        "fuse": cfg.fuse,
        "features": _feature_layout(),
        "schema_version": SCHEMA_VERSION,
    }
    if calibration:
        # held-out-calibrated operating points (e.g. node_threshold: the
        # probability cut the file-level detector should flag at) — they
        # belong WITH the weights: a checkpoint evaluated at someone else's
        # threshold silently changes its false-positive behavior
        meta["calibration"] = calibration
    if provenance:
        # retrain provenance (nerrf_tpu/learn): which trigger record,
        # which replay-buffer content and which parent version produced
        # these weights — stamped in the meta so `nerrf models status`
        # answers "where did v2 come from" offline
        meta["provenance"] = provenance
    with _atomic_dir(path) as tmp:
        with trace_span("checkpoint", kind="params"):
            with ocp.StandardCheckpointer() as ckptr:
                ckptr.save(tmp / "params", jax.device_get(params), force=True)
        (tmp / "model_config.json").write_text(json.dumps(meta, indent=2))
        if quality_profile:
            # the reference quality profile rides the checkpoint as its
            # own sidecar (nerrf_tpu/quality): the score/feature
            # distribution this model was calibrated against, published
            # with the weights so every serve pod can watch live traffic
            # drift away from it.  Schema-versioned inside the document
            from nerrf_tpu.quality import PROFILE_FILENAME

            (tmp / PROFILE_FILENAME).write_text(
                json.dumps(quality_profile, indent=2))


def load_quality_profile(path: str | Path) -> dict | None:
    """The checkpoint's reference quality profile sidecar, or None when
    the checkpoint predates profiles — callers treat None as "export no
    quality metrics" (null-not-fake), never as an empty distribution.
    Delegates to the quality plane's one loader, so a malformed or
    newer-schema sidecar fails HERE with the one-line ValueError every
    caller already handles — not later inside a serving pod's monitor."""
    from nerrf_tpu.quality import load_profile

    prof = load_profile(Path(path).absolute())
    return prof.to_dict() if prof is not None else None


def load_checkpoint(path: str | Path) -> Tuple[dict, JointConfig]:
    path = Path(path).absolute()
    meta = _read_sidecar(path, "model_config.json")
    _check_schema_version(meta, path)
    _check_feature_layout(meta, path, keys=("node", "edge", "seq"))
    try:
        cfg = JointConfig(
            gnn=GraphSAGEConfig(**meta["gnn"]),
            lstm=LSTMConfig(**meta["lstm"]),
            fuse=meta["fuse"],
        )
    except (KeyError, TypeError) as e:
        raise ValueError(
            f"corrupt checkpoint sidecar {path / 'model_config.json'}: "
            f"missing or malformed model-config field ({e!r})") from None
    with ocp.StandardCheckpointer() as ckptr:
        params = ckptr.restore(path / "params")
    return params, cfg


def load_calibration(path: str | Path) -> dict:
    """The checkpoint's held-out-calibrated operating points ({} when the
    checkpoint predates calibration).  Separate from load_checkpoint so its
    two-tuple contract stays stable for existing callers."""
    return _read_sidecar(Path(path).absolute(),
                         "model_config.json").get("calibration") or {}


def save_stream_checkpoint(path: str | Path, params, cfg,
                           calibration: dict | None = None) -> None:
    """StreamNet checkpoint: params + self-describing config sidecar, with
    the calibrated per-event operating threshold travelling alongside the
    weights exactly like the joint model's node_threshold (VERDICT r3 item
    5: a stream head without an operating point only ever reports best-F1,
    which is an oracle number no deployment can reproduce).

    Calibration-space contract: ``stream_event_threshold`` lives in RAW
    LOGIT space (best_f1 sweeps event_logits, never sigmoided) — unlike the
    joint model's ``node_threshold``, which is a probability.  The sidecar
    records this explicitly as ``stream_event_threshold_space`` so a
    consumer mirroring node_threshold usage cannot mis-apply the cut (r4
    advisor); if the caller's calibration dict carries the threshold but
    omits the space, ``"logit"`` is stamped in here (the only space any
    producer in this repo writes)."""
    import jax.numpy as jnp

    from nerrf_tpu.config import to_dict
    from nerrf_tpu.data.stream import STREAM_FEATURE_DIM

    meta = {
        # every field of the configuration, the layer kinds and the hybrid
        # widths among them
        "stream": to_dict(cfg),
        "features": {"stream": STREAM_FEATURE_DIM},
        "schema_version": SCHEMA_VERSION,
    }
    if calibration:
        if "stream_event_threshold" in calibration:
            calibration = {"stream_event_threshold_space": "logit",
                           **calibration}
        meta["calibration"] = calibration
    with _atomic_dir(path) as tmp:
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(tmp / "params", jax.device_get(params), force=True)
        (tmp / "stream_config.json").write_text(json.dumps(meta, indent=2))


def load_stream_checkpoint(path: str | Path):
    """→ (params, StreamConfig, calibration dict)."""
    import jax.numpy as jnp

    from nerrf_tpu.models import StreamConfig

    path = Path(path).absolute()
    meta = _read_sidecar(path, "stream_config.json")
    _check_schema_version(meta, path)
    from nerrf_tpu.data.stream import STREAM_FEATURE_DIM
    got = (meta.get("features") or {}).get("stream")
    if got is not None and got != STREAM_FEATURE_DIM:
        raise ValueError(
            f"retrain: feature layout changed — stream checkpoint {path} "
            f"was trained with {got}-dim event features, current code "
            f"produces {STREAM_FEATURE_DIM}")
    if "stream" not in meta:
        raise ValueError(
            f"corrupt checkpoint sidecar {path / 'stream_config.json'}: "
            f"missing the 'stream' model-config field")
    s = dict(meta["stream"])
    s["dtype"] = jnp.dtype(s["dtype"]).type
    cfg = StreamConfig(**s)
    with ocp.StandardCheckpointer() as ckptr:
        params = ckptr.restore(path / "params")
    return params, cfg, meta.get("calibration") or {}


def calibrate_and_resave(path: str | Path, params, cfg: JointConfig,
                         node_loss_weight: float = 1.0,
                         log=None, provenance: dict | None = None) -> \
        dict | None:
    """Calibrate the file detector's operating point on held-out incidents
    and re-save the checkpoint sidecar with it.  The ONE implementation of
    the calibrate-then-resave step, shared by `nerrf train-detector`
    (cli.py) and the experiment runner (train/run.py) — the r3 advisor
    found the two inline copies already drifting (run.py guarded on
    node_loss_weight and process_count, cli.py did not).

    Best-effort by contract: the caller must have saved the plain
    checkpoint FIRST; any failure here logs and returns None, leaving that
    checkpoint (and its 0.5 default threshold) intact.  Skips (None) when
    the node head wasn't trained — calibrating an untrained head would
    fabricate a cut — or on multi-controller runs (model_detect pulls
    scores to host numpy, which multi-host sharded params don't support).

    Returns the calibration dict written to the sidecar, or None."""
    if node_loss_weight <= 0 or jax.process_count() != 1:
        return None
    from nerrf_tpu.models import NerrfNet
    from nerrf_tpu.pipeline import calibrate_file_thresholds

    try:
        cals = calibrate_file_thresholds(params, NerrfNet(cfg), log=log)
    except Exception as e:  # noqa: BLE001 — plain checkpoint already safe
        if log:
            log(f"calibration failed ({type(e).__name__}: {e}); "
                "checkpoint keeps the 0.5 default threshold")
        return None
    if not cals.get("max"):
        if log:
            log("calibration unreachable; checkpoint keeps the 0.5 "
                "default threshold")
        return None
    cal = cals["max"]
    calibration = {"node_threshold": round(cal.threshold, 4),
                   "node_threshold_kind": cal.kind,
                   "node_threshold_recall": round(cal.recall, 4)}
    if cals.get("robust"):
        # the robust-aggregation leg runs at its OWN calibrated cut (robust
        # scores sit at/below max scores — r3 advisor)
        r = cals["robust"]
        calibration.update({"node_threshold_robust": round(r.threshold, 4),
                            "node_threshold_robust_kind": r.kind,
                            "node_threshold_robust_recall": round(r.recall, 4)})
    # reference quality profile at the freshly calibrated operating point
    # (nerrf_tpu/quality): the score/feature distribution this model +
    # cut expects, stamped alongside the calibration so every serve pod
    # watching this version has a drift baseline.  Best-effort, same
    # contract as calibration itself — a failed profile never blocks the
    # calibrated checkpoint
    profile = None
    try:
        from nerrf_tpu.data.synth import make_corpus
        from nerrf_tpu.quality import build_reference_profile

        profile = build_reference_profile(
            params, NerrfNet(cfg),
            # held-out benign-weighted mix, seeds disjoint from both the
            # training corpus and the calibration incidents (base 9000)
            traces=make_corpus(4, attack_fraction=0.25, base_seed=9500,
                               duration_sec=120.0),
            threshold=calibration["node_threshold"], log=log).to_dict()
    except Exception as e:  # noqa: BLE001 — profile is advisory
        if log:
            log(f"quality profile build failed ({type(e).__name__}: {e}); "
                "checkpoint ships without a drift baseline")
    # provenance is threaded through the re-save: a retrained checkpoint
    # that gets calibrated must not lose its retrain stamp to this rewrite
    save_checkpoint(path, params, cfg, calibration=calibration,
                    quality_profile=profile, provenance=provenance)
    return calibration
