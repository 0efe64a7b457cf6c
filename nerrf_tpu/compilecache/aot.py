"""AOT executable export: serialize the serve ladder at publish time.

The serve path's cold start is the bucket-ladder compile sweep.  This
module moves that sweep to PUBLISH time: `export_executables` compiles the
vmapped NerrfNet eval program for every configured bucket and serializes
each into an ``executables/`` directory — the sidecar
`ModelRegistry.publish` copies in next to the checkpoint.  (The stream
scorer's step programs reuse the same cache through the train-side
`StepCache` instead of riding the sidecar.)  A serve pod booting that version seeds its local
`CompileCache` from the sidecar and reaches readiness in seconds: no
tracing, no XLA, just deserialize-and-load per bucket.

Sidecar layout (one directory, content-addressed — literally a read-only
`CompileCache` root plus a manifest):

    executables/
        manifest.json        {"schema_version": 1, "env": {...},
                              "programs": {"<tag>": {"fingerprint": ...,
                                                     "program": ...,
                                                     "bytes": ...}}}
        <fingerprint>/       one cache entry per program
            executable.bin   serialized executable (serialize_executable)
            trees.pkl        pickled (in_tree, out_tree)
            meta.json        full key material (see compilecache.cache)

The manifest's ``env`` block records the jax/jaxlib/device identity the
executables were built for; a pod on ANY other identity simply misses (the
fingerprints differ) and compiles live — fail-open, like everything here.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Optional

from nerrf_tpu.compilecache.cache import CompileCache

MANIFEST = "manifest.json"
EXECUTABLES_DIR = "executables"


def serve_program_key(model_cfg, bucket_tag: str) -> dict:
    """The caller-side key material for one serve bucket program: the
    model architecture (same param pytree, different HLO — e.g. fuse mode
    or aggregation routing) plus the routes the backend gives the ops
    (`ops.segment.active_impls`), which the lowered graph depends on.  Warmup and export MUST build keys through here or a
    published executable would never be found at boot."""
    from nerrf_tpu.ops.segment import active_impls

    return {
        "kind": "serve_eval",
        "bucket": bucket_tag,
        "model": repr(model_cfg),
        "ops": repr(sorted(active_impls().items())),
    }


def export_executables(out_dir, params, model, serve_cfg,
                       batch_size: Optional[int] = None,
                       journal=None, registry=None, log=None,
                       tuned_stamp: Optional[dict] = None) -> dict:
    """Compile + serialize the eval program for every ladder bucket into
    ``out_dir`` and return the manifest.  Buckets whose executable cannot
    be serialized on this backend are recorded in the manifest with an
    ``error`` instead of an entry (partial sidecars are still useful)."""
    import numpy as np

    from nerrf_tpu.serve.config import bucket_tag as tag_of
    from nerrf_tpu.train.data import windows_of_trace
    from nerrf_tpu.train.loop import make_eval_fn

    out_dir = Path(out_dir).absolute()
    cache = CompileCache(root=out_dir, max_bytes=1 << 62,
                         journal=journal, registry=registry, log=log)
    eval_fn = make_eval_fn(model)
    bs = batch_size or serve_cfg.batch_size
    # the same shape-donor recipe serve warmup uses — the fingerprint keys
    # on avals, so any tiny trace yielding one sample works
    from nerrf_tpu.serve.service import _tiny_trace

    tiny = _tiny_trace("aot-export")
    programs = {}
    for bucket in serve_cfg.buckets:
        tag = tag_of(bucket)
        samples = windows_of_trace(tiny, serve_cfg.dataset_config(bucket))
        if not samples:
            programs[tag] = {"error": "no shape-donor sample"}
            continue
        s0 = samples[0]
        batch = {k: np.broadcast_to(v, (bs,) + v.shape).copy()
                 for k, v in s0.items()}
        t0 = time.perf_counter()
        _, info = cache.load_or_compile(
            eval_fn, (params, batch), program=f"serve_eval[{tag}]",
            extra=serve_program_key(model.cfg, tag))
        # "absent" is the normal fresh-miss reason; anything else on a
        # fresh compile means the entry never landed on disk (backend
        # can't serialize, or out_dir unwritable) — no sidecar entry
        if info.source == "live" or (info.source == "fresh"
                                     and info.reason != "absent"):
            programs[tag] = {"error": info.reason}
        else:
            programs[tag] = {"fingerprint": info.fingerprint,
                             "program": f"serve_eval[{tag}]",
                             "compile_seconds": round(info.seconds, 3)}
        if log:
            log(f"aot export {tag}: {info.source} "
                f"({time.perf_counter() - t0:.1f}s)")
    manifest = {
        "schema_version": 1,
        "created_at": time.time(),
        "batch_size": bs,
        "env": cache.env(),
        "model": repr(model.cfg),
        "programs": programs,
    }
    if tuned_stamp is not None:
        # provenance for tuned-ladder sidecars: which artifact the
        # exported rung set + routing came from (corpus fingerprint +
        # expected win), so a sidecar is attributable to its fit
        manifest["tuned"] = tuned_stamp
    out_dir.mkdir(parents=True, exist_ok=True)
    # the manifest commits the sidecar: serve boot reads it to decide the
    # bundle is usable, so it must never be observable half-written
    tmp = out_dir / (MANIFEST + ".tmp")
    tmp.write_text(json.dumps(manifest, indent=2))
    tmp.replace(out_dir / MANIFEST)
    return manifest


def export_for_checkpoint(ckpt_dir, serve_cfg=None,
                          journal=None, log=None, tuned=None) -> dict:
    """Load a checkpoint and export its serve-ladder executables into
    ``<ckpt_dir>/executables/`` (the sidecar `ModelRegistry.publish`
    carries along).  Returns the manifest.

    ``tuned`` is an optional tuned-ladder artifact (the dict
    `tune.load_artifact` returns): the export then runs over the TUNED
    rung set with the artifact's routing table stamped into the model
    config — re-exporting a published version onto a fitted ladder is
    exactly this call at publish time (docs/tuning.md)."""
    from nerrf_tpu.models import NerrfNet
    from nerrf_tpu.serve.config import ServeConfig
    from nerrf_tpu.train.checkpoint import load_checkpoint

    ckpt_dir = Path(ckpt_dir).absolute()
    params, model_cfg = load_checkpoint(ckpt_dir)
    serve_cfg = serve_cfg or ServeConfig()
    tuned_stamp = None
    if tuned is not None:
        from nerrf_tpu.tune.artifact import (
            apply_to_model_config,
            apply_to_serve_config,
        )
        serve_cfg = apply_to_serve_config(tuned, serve_cfg)
        model_cfg = apply_to_model_config(tuned, model_cfg)
        tuned_stamp = {
            "corpus_fingerprint": tuned.get("corpus_fingerprint"),
            "expected": tuned.get("expected"),
            "routing": tuned.get("routing"),
        }
    return export_executables(
        ckpt_dir / EXECUTABLES_DIR, params, NerrfNet(model_cfg),
        serve_cfg, journal=journal, log=log, tuned_stamp=tuned_stamp)


def read_manifest(exe_dir) -> Optional[dict]:
    """The sidecar's manifest, or None when ``exe_dir`` is not a sidecar
    (missing/corrupt manifests read as absent — fail-open)."""
    p = Path(exe_dir) / MANIFEST
    try:
        return json.loads(p.read_text())
    except (OSError, ValueError):
        return None
