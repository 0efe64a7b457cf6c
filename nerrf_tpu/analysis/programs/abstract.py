"""Shared plumbing for the deep (jaxpr-level) nerrflint tier.

The AST rules (`nerrf_tpu/analysis/*.py`) see source text; these rules see
the *programs XLA would compile*: every entry point is traced abstractly —
`jax.eval_shape` / `jax.make_jaxpr` / `jit(...).lower(...)` over
`ShapeDtypeStruct` avals, no devices touched, no data materialized — the
execution-free tensor-program regime of TpuGraphs (arXiv:2308.13490) and
the configuration cross-attention predictor (arXiv:2405.16623).  That lets
the chip-queue pre-flight *prove* contracts on CPU in seconds that today
only surface by burning accelerator minutes: warmup signature closure,
donation aliasing, collective axis validity, and compile-cache key
coverage.

Everything here defers its jax import to call time: the base engine (and
the plain ``nerrf lint`` tier-1 gate) must stay importable with no jax on
the path.  `prepare_backend` is called by `engine.main --deep` before any
rule runs — it forces the CPU platform and a virtual multi-device host so
the shard_map shims can be traced on any machine, with or without an
accelerator (a pre-flight must not need the chip it guards).
"""

from __future__ import annotations

import dataclasses
import os
import re
import sys
from typing import Callable, List, Optional, Sequence, Tuple

from nerrf_tpu.analysis.engine import Finding

# virtual host devices for the shard_map trace legs (conftest.py uses the
# same count; any value ≥ 2 works — the ring entry uses two)
_VIRTUAL_DEVICES = 8


def prepare_backend() -> None:
    """Force the deep pass onto a virtual multi-device CPU backend.

    Must run before jax's backend initializes.  The platform choice goes
    through jax.config rather than the environment because an embedder may
    already have imported jax; backend init is lazy, so this works as long
    as nothing has traced yet.  Best-effort by design:
    if a backend is already up (an embedder running lint in-process), the
    rules still trace correctly on whatever platform is live — only the
    multi-device legs may degrade (they check `jax.device_count()`)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count"
                    f"={_VIRTUAL_DEVICES}").strip()
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:  # noqa: BLE001 — backend already initialized
        pass


def aval(shape: Sequence[int], dtype) -> "jax.ShapeDtypeStruct":  # noqa: F821
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def avals_of_spec(spec: dict, batch: int = 0) -> dict:
    """`train.data.sample_spec`-style ``{k: (shape, dtype)}`` → aval dict,
    optionally with a leading batch axis."""
    lead = (batch,) if batch else ()
    return {k: aval(lead + tuple(shape), dtype)
            for k, (shape, dtype) in spec.items()}


# -- micro model: tracing cost control ----------------------------------------


def micro_train_config():
    """A minimal TrainConfig: same program *structure* as the flagship
    (same jit boundaries, donation spec, loss composition — what the deep
    contracts are about), smallest tensors, so each abstract trace costs
    ~1 s instead of ~6 s and the whole pass stays inside its 30 s budget."""
    from nerrf_tpu.models import GraphSAGEConfig, JointConfig, LSTMConfig
    from nerrf_tpu.train.loop import TrainConfig

    model = JointConfig(
        gnn=GraphSAGEConfig(hidden=8, num_layers=1, aggregation="segment"),
        lstm=LSTMConfig(hidden=8, num_layers=1))
    return TrainConfig(model=model, batch_size=2, num_steps=4,
                       warmup_steps=1)


def micro_serve_model():
    """The micro NerrfNet for serve-program traces (shape-polymorphic, so
    the closure/cache-key proofs transfer to any deployed architecture)."""
    from nerrf_tpu.models import NerrfNet

    return NerrfNet(micro_train_config().model)


_PARAM_AVALS_MEMO: dict = {}


def param_avals(model, sample_avals: dict):
    """Abstract param tree for ``model`` at one window sample's shapes —
    `jax.eval_shape` over init: no RNG drawn, no buffer allocated.
    Memoized per (architecture, sample signature): several entries build
    the same micro model, and each eval_shape costs ~0.5 s of the deep
    pass's 30 s budget."""
    import jax
    import jax.numpy as jnp

    from nerrf_tpu.train.loop import model_inputs

    memo_key = (repr(getattr(model, "cfg", model)), tuple(sorted(
        (k, tuple(v.shape), str(v.dtype))
        for k, v in sample_avals.items())))
    hit = _PARAM_AVALS_MEMO.get(memo_key)
    if hit is not None:
        return hit

    def init_fn(rng):
        # canonicalize up front (int64 → int32 under default x64-off) so
        # the zeros don't warn on every bucket traced
        one = {k: jnp.zeros(v.shape, jax.dtypes.canonicalize_dtype(v.dtype))
               for k, v in sample_avals.items()}
        return model.init(rng, *model_inputs(one),
                          deterministic=True)["params"]

    out = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    _PARAM_AVALS_MEMO[memo_key] = out
    return out


# -- lowered-program inspection -----------------------------------------------

_MAIN_SIG = re.compile(
    r"func\.func\s+public\s+@main\((?P<args>.*?)\)\s*->", re.DOTALL)
_ARG_START = re.compile(r"%arg(\d+):")

# markers jax stamps on an argument whose buffer WILL be reused for an
# output: plain lowerings carry ``tf.aliasing_output``; lowerings under
# shardings carry ``jax.buffer_donor`` instead
_DONATED_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def alias_attrs(lowered_text: str) -> Optional[List[bool]]:
    """Per-flat-argument donation verdicts from a lowered StableHLO module:
    ``True`` where jax committed the input's buffer to an output, ``False``
    otherwise.  None when the main signature cannot be found (caller
    degrades gracefully).

    Parses by ``%argN`` chunk rather than a brace-matched attr dict:
    sharded lowerings embed nested braces inside quoted attr strings
    (``mhlo.sharding = "{devices=[2,1]<=[2]}"``), which no flat regex over
    ``{...}`` survives."""
    m = _MAIN_SIG.search(lowered_text)
    if m is None:
        return None
    args_text = m.group("args")
    starts = list(_ARG_START.finditer(args_text))
    out: List[bool] = []
    for i, am in enumerate(starts):
        end = starts[i + 1].start() if i + 1 < len(starts) else len(args_text)
        chunk = args_text[am.start():end]
        out.append(any(marker in chunk for marker in _DONATED_MARKERS))
    return out or None


def leaf_paths(tree) -> List[str]:
    """Human-readable path strings for a pytree's leaves, in flatten order
    (names donation findings by the actual buffer, not a flat index)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(kp) or "<leaf>" for kp, _ in flat]


# -- jaxpr walking ------------------------------------------------------------

COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmax", "pmin", "pbroadcast", "ppermute",
    "all_gather", "all_to_all", "reduce_scatter", "axis_index",
    "psum_invariant",
}


def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and all jaxprs nested in its params (scan
    bodies, cond branches, shard_map bodies, custom-vjp calls...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield from iter_eqns(inner)
            elif hasattr(v, "eqns"):
                yield from iter_eqns(v)
            elif isinstance(v, (tuple, list)):
                for w in v:
                    inner = getattr(w, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        yield from iter_eqns(inner)
                    elif hasattr(w, "eqns"):
                        yield from iter_eqns(w)


def collectives_in(closed_jaxpr) -> List[Tuple[str, Tuple[str, ...], dict]]:
    """(primitive, axis-names, params) for every collective eqn reachable
    in the jaxpr, nested bodies included."""
    out = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name not in COLLECTIVE_PRIMS:
            continue
        axes = eqn.params.get("axis_name", eqn.params.get("axes", ()))
        if isinstance(axes, (str, type(None))):
            axes = (axes,) if axes else ()
        out.append((eqn.primitive.name,
                    tuple(str(a) for a in axes), dict(eqn.params)))
    return out


def program_identity(closed_jaxpr) -> Tuple[str, str]:
    """(jaxpr text, digest of captured constant VALUES) — what actually
    distinguishes one lowered program from another.  ``str(jaxpr)`` alone
    shows constvar *names*, not values, so two programs differing only in
    a small captured array would compare equal without the digest."""
    import hashlib

    import numpy as np

    h = hashlib.blake2s()
    for c in closed_jaxpr.consts:
        try:
            arr = np.asarray(c)
            h.update(str(arr.dtype).encode())
            h.update(str(arr.shape).encode())
            h.update(arr.tobytes())
        except Exception:  # noqa: BLE001 — non-array const: repr is best
            h.update(repr(c).encode())
    return str(closed_jaxpr.jaxpr), h.hexdigest()


def big_consts(closed_jaxpr, min_bytes: int) -> List[Tuple[tuple, str, int]]:
    """(shape, dtype, nbytes) of every closure-captured constant of at
    least ``min_bytes`` baked into the jaxpr — the material a cache
    fingerprint cannot see (it hashes argument avals, and a capture is not
    an argument)."""
    import numpy as np

    out = []
    for c in closed_jaxpr.consts:
        # from shape and dtype, not `.nbytes`: the wrapper jax 0.9 hands a
        # captured numpy array back in (`TypedNdArray`) has no such field
        try:
            nbytes = int(np.prod(c.shape)) * np.dtype(c.dtype).itemsize
        except (AttributeError, TypeError):   # a captured non-array
            continue
        if nbytes >= min_bytes:
            out.append((tuple(getattr(c, "shape", ())),
                        str(getattr(c, "dtype", type(c).__name__)), nbytes))
    return out


# -- entry descriptors (rules consume these; entries.py builds the real ones) --


@dataclasses.dataclass
class DonationEntry:
    """One jitted program whose donation discipline is verified from its
    lowered module.  ``build() -> (jit_fn, args)`` with abstract avals;
    ``donate`` = argnums the jit declares donated; ``must_donate`` =
    argnums holding large reusable state (params/opt_state) that MUST be
    donated or peak memory doubles at flagship shapes."""

    name: str
    path: str                     # repo-relative anchor file
    build: Callable[[], tuple]
    donate: Tuple[int, ...] = ()
    must_donate: Tuple[int, ...] = ()


@dataclasses.dataclass
class CollectiveEntry:
    """One shard_map/pjit program traced to a jaxpr whose collectives must
    only name axes of ``mesh_axes``."""

    name: str
    path: str
    build: Callable[[], tuple]    # () -> (fn, args) for make_jaxpr
    mesh_axes: Tuple[str, ...] = ()
    axis_sizes: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class CacheKeyEntry:
    """One cache-keyed program with variants along a config axis.  Each
    variant is ``(label, build, extra)`` where ``build() -> (fn, args)``;
    the rule traces the jaxpr of each and requires: whenever two variants
    lower different programs, their CompileCache fingerprints differ."""

    name: str
    path: str
    variants: List[tuple]
    min_const_bytes: int = 4096


def finding(rule_id: str, path: str, line: int, anchor: str, message: str,
            hint: str = "") -> Finding:
    return Finding(rule=rule_id, path=path, line=line, message=message,
                   hint=hint, anchor=anchor)


def locate(project, module_name: str, qualname: str) -> Tuple[str, int]:
    """(path, line) anchor for a function in the scanned project; falls
    back to the module path (line 1) or a synthesized path so deep rules
    work even when the AST project was built over a subset."""
    mod = project.modules.get(module_name) if project is not None else None
    if mod is None:
        return module_name.replace(".", "/") + ".py", 1
    for fi in mod.functions:
        if fi.qualname == qualname:
            return mod.path, fi.line
    return mod.path, 1


def note(msg: str) -> None:
    print(f"nerrflint: deep: {msg}", file=sys.stderr)
