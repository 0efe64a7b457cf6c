"""Deep nerrflint tier (nerrf_tpu/analysis/programs/): the tier-1 gate +
per-contract positive/negative fixtures.

Mirrors tests/test_analysis.py one tier down: ``test_deep_repo_is_clean``
runs the full deep pass over the real entry points (serve ladder, flat
train step, ring shard_map, cache keys) and asserts the
<30 s CPU budget the chip-queue pre-flights rely on; the fixture tests
prove each of the four contracts fires on a deliberately broken input and
stays quiet on a clean one.  Runs entirely on the virtual CPU mesh — no
devices, no compiles."""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from nerrf_tpu.analysis import analyze
from nerrf_tpu.analysis.astutil import Project, collect_files
from nerrf_tpu.analysis.programs import DEEP_RULE_IDS
from nerrf_tpu.analysis.programs.abstract import (
    CacheKeyEntry,
    CollectiveEntry,
    DonationEntry,
    aval,
)
from nerrf_tpu.analysis.programs.cachekey import CacheKeyCoverage
from nerrf_tpu.analysis.programs.closure import SignatureClosure
from nerrf_tpu.analysis.programs.collectives import CollectiveConsistency
from nerrf_tpu.analysis.programs.donation import DonationDiscipline


# -- the tier-1 gate ----------------------------------------------------------


def test_deep_repo_is_clean(repo_root):
    """The full deep ruleset over the real entry points: zero findings,
    through the engine's --json schema, inside the 30 s analysis budget
    the queue pre-flights assume (ISSUE 8 acceptance).  The budget is the
    engine-measured elapsed — every abstract trace of every contract —
    so it holds on a loaded CI host where interpreter+jax start-up wall
    time is noise; the subprocess timeout still caps total wall."""
    r = subprocess.run(
        [sys.executable, str(repo_root / "scripts" / "nerrflint.py"),
         "--deep", "--json"],
        capture_output=True, text=True, timeout=120, cwd=repo_root)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    assert doc["ok"] is True, doc["findings"] or doc["errors"]
    assert doc["findings"] == [] and doc["errors"] == []
    assert set(DEEP_RULE_IDS) <= {ru["id"] for ru in doc["rules"]}
    assert doc["elapsed_sec"] < 30.0, \
        f"deep pass took {doc['elapsed_sec']}s of analysis (budget 30s)"


def test_deep_rules_require_the_flag(repo_root):
    """Without --deep, a deep rule id is a usage error (exit 2), proving
    the tier-1 AST gate never pays the jax import."""
    r = subprocess.run(
        [sys.executable, str(repo_root / "scripts" / "nerrflint.py"),
         "--rule", "program-closure"],
        capture_output=True, text=True, timeout=60, cwd=repo_root)
    assert r.returncode == 2


@pytest.fixture(scope="module")
def project(repo_root):
    return Project(repo_root, collect_files(repo_root, ("nerrf_tpu",)))


# -- shape authority ----------------------------------------------------------


def test_sample_spec_matches_window_sample():
    """The static shape authority and the real lowering cannot drift: a
    real window_sample output must match sample_spec key-for-key in shape
    and dtype — the premise of the closure proof."""
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.serve.service import _tiny_trace
    from nerrf_tpu.train.data import DatasetConfig, sample_spec, windows_of_trace

    cfg = DatasetConfig(graph=GraphConfig(max_nodes=64, max_edges=128),
                        seq_len=16, max_seqs=8)
    samples = windows_of_trace(_tiny_trace("spec-check"), cfg)
    assert samples, "donor trace produced no sample at the micro config"
    spec = sample_spec(cfg)
    got = {k: (tuple(np.asarray(v).shape), str(np.asarray(v).dtype))
           for k, v in samples[0].items()}
    want = {k: (tuple(shape), dtype) for k, (shape, dtype) in spec.items()}
    assert got == want


# -- program-closure ----------------------------------------------------------


def test_closure_clean_on_default_ladder(project):
    found = SignatureClosure(trace_extremes=False).run(project)
    assert found == []


def test_closure_flags_unwarmed_bucket(project):
    """A ladder whose donor trace can fill nothing (min_events pushed past
    any donor window) is a deliberately open signature set: every bucket
    is reachable at admission but absent from the warmup-compiled set."""
    import dataclasses

    from nerrf_tpu.serve.config import ServeConfig

    cfg = dataclasses.replace(ServeConfig(), min_events=10 ** 6)
    found = SignatureClosure(serve_cfg=cfg, trace_extremes=False).run(project)
    assert found, "open signature set not flagged"
    assert all(f.rule == "program-closure" for f in found)
    assert any("unwarmed" in f.anchor for f in found)
    assert len({f.anchor for f in found}) == len(cfg.buckets)


def test_closure_flags_warmup_admission_signature_drift(project):
    """If admission lowered a different shape than warmup compiled (the
    hazard sample_spec exists to pin), every live window would recompile:
    simulated by a lying spec (one dtype off)."""
    from nerrf_tpu.train.data import sample_spec

    def lying_spec(ds_cfg):
        spec = dict(sample_spec(ds_cfg))
        shape, _ = spec["node_feat"]
        spec["node_feat"] = (shape, "float16")
        return spec

    found = SignatureClosure(expected_spec=lying_spec,
                             trace_extremes=False).run(project)
    assert found and all("signature" in f.anchor for f in found)
    assert "node_feat" in found[0].message


# -- donation-discipline ------------------------------------------------------


def _entry(name, fn, args, donate=(), must_donate=()):
    return DonationEntry(name=name, path="tests/fixture.py",
                         build=lambda: (fn, args), donate=donate,
                         must_donate=must_donate)


def test_donation_flags_wasted_and_missing_donation():
    import jax

    a = aval((8, 8), np.float32)

    def swallow(x, y):
        # x is donated and used, but no output matches its aval: XLA has
        # nothing to alias the freed buffer onto
        return (y * 2.0 + x.sum(),)

    jitted = jax.jit(swallow, donate_argnums=(0,))
    found = DonationDiscipline(entries=[
        _entry("swallow", jitted, (a, aval((3,), np.float32)),
               donate=(0,), must_donate=(0,)),
    ]).run(project=None)
    assert any("wasted" in f.anchor for f in found), found

    def step(state, batch):
        return state - batch.sum(), batch.mean()

    found = DonationDiscipline(entries=[
        _entry("undonated_step", jax.jit(step), (a, a),
               donate=(), must_donate=(0,)),
    ]).run(project=None)
    assert any("undonated" in f.anchor for f in found), found


def test_donation_flags_forbidden_and_passes_clean():
    import jax

    a = aval((8, 8), np.float32)

    def step(state, batch):
        return state - batch.sum(), batch.mean()

    # serve-side contract: an entry declaring donate=() whose lowered
    # module still aliases inputs (someone added donate_argnums) fails
    sneaky = jax.jit(step, donate_argnums=(0,))
    found = DonationDiscipline(entries=[
        _entry("serve_like", sneaky, (a, a), donate=()),
    ]).run(project=None)
    assert any("forbidden" in f.anchor for f in found), found

    clean = jax.jit(step, donate_argnums=(0,))
    found = DonationDiscipline(entries=[
        _entry("clean_step", clean, (a, a), donate=(0,), must_donate=(0,)),
    ]).run(project=None)
    assert found == []


def test_donation_reads_sharded_lowerings():
    """A correctly-donated SHARDED step must come out clean: lowerings
    under shardings stamp `jax.buffer_donor` (not tf.aliasing_output) and
    embed nested braces in mhlo.sharding attr strings — both of which the
    chunk-based alias parser must survive (review regression)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), axis_names=("dp",))
    sh = NamedSharding(mesh, P("dp"))

    def step(state, batch):
        return state - batch.sum(), batch.mean()

    sharded = jax.jit(step, donate_argnums=(0,), in_shardings=(sh, sh),
                      out_shardings=None)
    a = aval((8, 8), np.float32)
    found = DonationDiscipline(entries=[
        _entry("sharded_step", sharded, (a, a),
               donate=(0,), must_donate=(0,)),
    ]).run(project=None)
    assert found == [], found


def _ast_project(tmp_path: Path, body: str) -> Project:
    p = tmp_path / "pkg" / "mod.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return Project(tmp_path, [p])


def test_donation_ast_donated_then_read(tmp_path):
    proj = _ast_project(tmp_path, """\
        import jax

        step = jax.jit(lambda s, b: (s + b, b.sum()), donate_argnums=(0,))

        def bad(state, batch):
            out = step(state, batch)
            return out, state.sum()   # state's buffer is gone by here

        def good(state, batch):
            state, loss = step(state, batch)
            return state, loss

        def loop_good(state, batches):
            for b in batches:
                state, loss = step(state, b)
            return state.sum()

        def multiline_good(state, batch):
            out = step(state,
                       batch + state.mean())  # same stmt: pre-donation
            return out

        def branch_good(state, batch, cond):
            if cond:
                out = step(state, batch)
            else:
                out = state.sum()   # other arm: can't follow the donate
            return out
        """)
    found = DonationDiscipline(entries=[], ast_scope=("pkg/",)).run(proj)
    assert len(found) == 1
    assert found[0].anchor == "bad:use-after-donate:state"


def test_donation_ast_scope_discipline(tmp_path):
    """A name bound to a donating factory inside ONE function must not
    taint a same-named plain callable in an unrelated function, while
    closure bindings stay visible to nested defs (review regression)."""
    proj = _ast_project(tmp_path, """\
        import jax

        def trainer(state, batches):
            step = jax.jit(lambda s, b: (s + b, b), donate_argnums=(0,))
            for b in batches:
                state, loss = step(state, b)
            return state

        def scorer(state, batch):
            step = jax.jit(lambda s, b: s * b)   # no donation here
            out = step(state, batch)
            return out, state.sum()              # perfectly legal read

        def factory(state0):
            step = jax.jit(lambda s: (s * 2, s.sum()),
                           donate_argnums=(0,))

            def inner(state):
                out = step(state)
                return out, state.mean()         # closure: still flagged
            return inner
        """)
    found = DonationDiscipline(entries=[], ast_scope=("pkg/",)).run(proj)
    assert len(found) == 1, found
    assert found[0].anchor.endswith("inner:use-after-donate:state")


def test_donation_ast_double_donation(tmp_path):
    proj = _ast_project(tmp_path, """\
        import jax

        def f(a, b, x):
            return a + x, b - x

        step2 = jax.jit(f, donate_argnums=(0, 1))

        def bad(state, x):
            return step2(state, state, x)
        """)
    found = DonationDiscipline(entries=[], ast_scope=("pkg/",)).run(proj)
    assert len(found) == 1
    assert found[0].anchor == "bad:double:state"


def test_donation_coarse_fallback_catches_forbidden(monkeypatch):
    """When the leaf mapping degrades (lowered arg count != pytree leaf
    count), an entry declaring donate=() whose module still aliases
    inputs must fail — the serve shared-params hazard (review
    regression: the coarse path previously checked only wasted)."""
    import jax

    import nerrf_tpu.analysis.programs.donation as dn

    a = aval((8, 8), np.float32)

    def step(state, batch):
        return state - batch.sum(), batch.mean()

    sneaky = jax.jit(step, donate_argnums=(0,))
    # force the coarse path: pretend the pytree has an extra leaf
    monkeypatch.setattr(dn, "leaf_paths",
                        lambda tree: ["<leaf>", "<phantom>"])
    found = DonationDiscipline(entries=[
        _entry("serve_like_coarse", sneaky, (a, a), donate=()),
    ]).run(project=None)
    assert len(found) == 1
    assert found[0].anchor.endswith("coarse-forbidden")


# -- collective-consistency ---------------------------------------------------


def _two_device_mesh():
    import jax

    if len(jax.devices()) < 2:  # pragma: no cover — conftest forces 8
        pytest.skip("needs the virtual multi-device mesh")
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                axis_names=("dp", "sp"))


def _shard_map_entry(name, body, mesh_axes, axis_sizes):
    def build():
        import jax

        try:
            from jax import shard_map as shard_map_fn
        except ImportError:
            from jax.experimental.shard_map import shard_map as shard_map_fn
        from jax.sharding import PartitionSpec as P

        mesh = _two_device_mesh()
        # no `check_rep=False`: jax 0.9 renamed it, and the TypeError made
        # every fixture a trace failure before the rule saw a collective
        fn = shard_map_fn(body, mesh=mesh, in_specs=(P("dp", "sp"),),
                          out_specs=P("dp", "sp"))
        return fn, (aval((2, 4), np.float32),)

    return CollectiveEntry(name=name, path="tests/fixture.py", build=build,
                           mesh_axes=mesh_axes, axis_sizes=axis_sizes)


def test_collectives_flags_bad_axis_and_trace_failure():
    import jax

    # a collective naming an axis outside the declared mesh spec
    entry = _shard_map_entry(
        "undeclared_axis", lambda x: jax.lax.psum(x, "sp"),
        mesh_axes=("dp",), axis_sizes={"dp": 1})
    found = CollectiveConsistency(entries=[entry], contracts=[]).run(None)
    assert any("psum" in f.anchor and "sp" in f.anchor for f in found), found

    # an axis that does not exist at all: the trace itself fails, and the
    # crash becomes a finding instead of a chip-time partitioning error
    entry = _shard_map_entry(
        "phantom_axis", lambda x: jax.lax.psum(x, "zz"),
        mesh_axes=("dp", "sp"), axis_sizes={"sp": 2})
    found = CollectiveConsistency(entries=[entry], contracts=[]).run(None)
    assert any("trace" in f.anchor for f in found), found


def test_collectives_clean_ring_and_real_contracts(project):
    found = CollectiveConsistency().run(project)
    assert found == []


def test_collectives_flags_sharding_rank_and_axis():
    from jax.sharding import PartitionSpec as P

    contracts = [
        ("prog", "batch", P("dp", "sp"), 1, ("dp", "sp")),   # rank overflow
        ("prog", "feat", P("zz"), 3, ("dp", "sp")),          # unknown axis
        ("prog", "ok", P("dp"), 2, ("dp", "sp")),            # fine
    ]
    found = CollectiveConsistency(entries=[], contracts=contracts).run(None)
    anchors = {f.anchor for f in found}
    assert "sharding:prog:batch:rank" in anchors
    assert "sharding:prog:feat:axes" in anchors
    assert len(found) == 2


# -- cache-key-coverage -------------------------------------------------------


def test_cachekey_flags_closure_capture():
    import jax.numpy as jnp

    big = np.arange(8192, dtype=np.float32)  # 32 KiB baked-in constant

    def build():
        return (lambda x: x + jnp.asarray(big)), \
            (aval((8192,), np.float32),)

    entry = CacheKeyEntry(name="captured", path="tests/fixture.py",
                          variants=[("base", build, {"k": "v"})])
    found = CacheKeyCoverage(entries=[entry]).run(None)
    assert len(found) == 1
    assert "closure-captured" in found[0].message
    assert found[0].anchor.startswith("cachekey:captured:const:")

    # a capture present only under a NON-base variant is the same hazard
    # (review regression: the scan runs for every variant)
    def clean_build():
        return (lambda x: x * 2.0), (aval((8192,), np.float32),)

    entry = CacheKeyEntry(name="late_capture", path="tests/fixture.py",
                          variants=[("base", clean_build, {"k": "a"}),
                                    ("cfgB", build, {"k": "b"})])
    found = CacheKeyCoverage(entries=[entry]).run(None)
    assert any("closure-captured" in f.message for f in found), found


def test_cachekey_flags_uncovered_axis_and_passes_covered():
    def mk(gain):
        def build():
            return (lambda x: x * gain), (aval((4,), np.float32),)

        return build

    # same extra on both sides of a program-changing axis → stale hazard
    entry = CacheKeyEntry(
        name="gain_prog", path="tests/fixture.py",
        variants=[("base", mk(2.0), {"cfg": "same"}),
                  ("gain", mk(3.0), {"cfg": "same"})])
    found = CacheKeyCoverage(entries=[entry]).run(None)
    assert len(found) == 1 and found[0].anchor.endswith("gain:uncovered")

    # keyed extra → covered → quiet
    entry = CacheKeyEntry(
        name="gain_prog", path="tests/fixture.py",
        variants=[("base", mk(2.0), {"cfg": "gain=2"}),
                  ("gain", mk(3.0), {"cfg": "gain=3"})])
    assert CacheKeyCoverage(entries=[entry]).run(None) == []


def test_cachekey_sees_small_const_value_drift():
    """Variants differing only in the VALUES of a sub-threshold captured
    array lower identical jaxpr text (constvar names, not values) — the
    program identity must still distinguish them (review regression)."""
    import jax.numpy as jnp

    def mk(values):
        arr = np.asarray(values, np.float32)  # well under min_const_bytes

        def build():
            return (lambda x: x * jnp.asarray(arr)), \
                (aval((4,), np.float32),)

        return build

    entry = CacheKeyEntry(
        name="weights_prog", path="tests/fixture.py",
        variants=[("base", mk([1, 2, 3, 4]), {"cfg": "same"}),
                  ("reweighted", mk([4, 3, 2, 1]), {"cfg": "same"})])
    found = CacheKeyCoverage(entries=[entry]).run(None)
    assert len(found) == 1
    assert found[0].anchor.endswith("reweighted:uncovered")


def test_cachekey_real_entries_are_covered(project):
    """The shipped key material (step_key_extra / serve_program_key)
    covers the aval-invariant axes the entries perturb — the stale-cache
    hazard class PR 7's poisoned-payload bug belongs to stays closed."""
    found = CacheKeyCoverage().run(project)
    assert found == []
