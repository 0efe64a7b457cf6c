"""Fully on-device MCTS: the whole PUCT search as ONE jitted XLA program.

The host planner (`mcts.py`) keeps the tree on host and dispatches leaf
batches to the device, so every frontier batch pays a host↔device round
trip.  This planner is the TPU-idiomatic alternative: tree arrays live in
device memory, and select → expand → evaluate → backup run inside
`lax.fori_loop`/`while_loop` (compiler-friendly control flow, no
data-dependent Python).  One `plan()` call is one device program: the host
boundary is crossed twice (args in, arrays out) regardless of the
simulation budget.

Compilation is amortized across incidents, not per incident: problem
shapes are padded to buckets (`FILE_BUCKET_FLOOR`/`PROC_BUCKET_FLOOR`) and
every per-incident quantity — detector scores, loss estimates, PUCT
priors, value-net weights — enters the program as a runtime argument
(`_Ctx`), never as an embedded constant.  Two incidents in the same bucket
therefore hit the same XLA executable (module-level `_programs` cache), so
a resident daemon compiles once at boot (`warmup_for`) and each real
incident plans against a warm program.  The m1 recovery artifact showed
why this matters: 21.9 s of a 22.9 s MTTR was plan time, most of it
trace+compile.

Same decision domain (`UndoDomain`, re-expressed branchlessly in jnp),
same PUCT scoring and reward bookkeeping as the host planner, and the same
plan extraction (`mcts.extract_plan`) over the returned arrays — the two
planners are interchangeable and cross-checked by tests.

Realizes the reference's planner spec (`architecture.mdx:62-72`: 500–1000
simulations, ≤5 min budget, ranked undo plan) — see `domain.py` for the
reward model's provenance.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from nerrf_tpu.planner.domain import (
    DOWNTIME_WEIGHT,
    FP_REVERT_FLOOR_MB,
    FP_REVERT_SCALE,
    KILL_DOWNTIME_SEC,
    ONGOING_LOSS_MB_PER_SEC,
    REVERT_SECONDS_PER_MB,
    UndoDomain,
    UndoPlan,
)
from nerrf_tpu.planner.mcts import MCTSConfig, extract_plan
from nerrf_tpu.utils import sync_result
from nerrf_tpu.planner.value_net import heuristic_value


class _Tree(NamedTuple):
    """Loop-carried search state (all fixed-shape, device-resident)."""

    visits: jnp.ndarray       # [M] int32
    value_sum: jnp.ndarray    # [M] f32
    parent: jnp.ndarray       # [M] int32
    parent_action: jnp.ndarray  # [M] int32
    children: jnp.ndarray     # [M, A] int32 (-1 = unvisited)
    child_reward: jnp.ndarray  # [M, A] f32
    expanded: jnp.ndarray     # [M] bool
    terminal: jnp.ndarray     # [M] bool
    state: jnp.ndarray        # [M, D] f32
    n_nodes: jnp.ndarray      # scalar int32


class _Ctx(NamedTuple):
    """Per-incident inputs — runtime ARGUMENTS of the compiled search, so a
    new incident (new scores, new value-net weights) reuses the executable
    compiled for its shape bucket instead of recompiling."""

    file_scores: jnp.ndarray   # [F] padded detector P(file compromised)
    file_loss: jnp.ndarray     # [F] padded data at stake (MB)
    proc_scores: jnp.ndarray   # [P] padded P(process malicious)
    prior: jnp.ndarray         # [A] padded PUCT priors
    real: jnp.ndarray          # [2] f32 (real F, real P) for normalization
    value_params: Any          # value-net pytree, or () for the heuristic


@functools.lru_cache(maxsize=32)
def _programs(F: int, P: int, M: int, max_steps: float, c_puct: float,
              value_apply):
    """(init_tree, search_chunk) compiled for one (shape-bucket, value-fn)
    signature.  ``value_apply`` is a pure ``(params, features) → values``
    callable (or None for the closed-form heuristic); its *identity* keys
    the cache, so callers must pass a stable function object
    (`value_net._mlp_apply` is shared per hidden size for exactly this)."""
    A, D = F + P + 1, F + P + 3

    # --- branchless jnp re-expression of UndoDomain ------------------------
    # state layout: [done_f (F), killed_p (P), downtime, steps, stopped]

    def legal(s: jnp.ndarray) -> jnp.ndarray:
        ok = jnp.concatenate(
            [s[:F] < 0.5, s[F:F + P] < 0.5, jnp.ones((1,), bool)])
        open_ = (s[F + P + 2] < 0.5) & (s[F + P + 1] < max_steps)
        return ok & open_

    def terminal(s: jnp.ndarray) -> jnp.ndarray:
        return (s[F + P + 2] > 0.5) | (s[F + P + 1] >= max_steps)

    def step(ctx: _Ctx, s: jnp.ndarray, a: jnp.ndarray):
        """(s, action index) → (s', incremental reward); mask-composed, no
        branches — mirrors UndoDomain.step_batch exactly."""
        is_file = a < F
        is_kill = (a >= F) & (a < F + P)
        is_stop = a == F + P

        fi = jnp.clip(a, 0, F - 1)
        pi = jnp.clip(a - F, 0, P - 1)
        killed_p = s[F:F + P]
        live_threat = jnp.sum(ctx.proc_scores * (killed_p < 0.5))
        steps = s[F + P + 1]
        remaining = jnp.clip(max_steps - steps, 0.0)
        cap = jnp.minimum(remaining, 30.0)

        sc_f = ctx.file_scores[fi]
        loss = ctx.file_loss[fi]
        t_op = REVERT_SECONDS_PER_MB * loss
        fp_cost = FP_REVERT_SCALE * loss + FP_REVERT_FLOOR_MB
        r_file = sc_f * loss - (1 - sc_f) * fp_cost - DOWNTIME_WEIGHT * t_op

        sc_p = ctx.proc_scores[pi]
        r_kill = (sc_p * ONGOING_LOSS_MB_PER_SEC * cap
                  - DOWNTIME_WEIGHT * KILL_DOWNTIME_SEC * sc_p
                  - (1 - sc_p) * DOWNTIME_WEIGHT * KILL_DOWNTIME_SEC * 2.0)

        r_stop = -live_threat * ONGOING_LOSS_MB_PER_SEC * cap

        reward = jnp.where(is_file, r_file,
                           jnp.where(is_kill, r_kill,
                                     jnp.where(is_stop, r_stop, 0.0)))

        done_f = s[:F] + jnp.where(
            is_file, (jnp.arange(F) == fi).astype(s.dtype), 0.0)
        killed = killed_p + jnp.where(
            is_kill, (jnp.arange(P) == pi).astype(s.dtype), 0.0)
        downtime = s[F + P] + jnp.where(is_file, t_op, 0.0)
        stopped = jnp.maximum(s[F + P + 2], is_stop.astype(s.dtype))
        s2 = jnp.concatenate([
            jnp.clip(done_f, 0.0, 1.0), jnp.clip(killed, 0.0, 1.0),
            downtime[None], (steps + 1.0)[None], stopped[None]])
        return s2, reward

    def features(ctx: _Ctx, s: jnp.ndarray) -> jnp.ndarray:
        rF, rP = ctx.real[0], ctx.real[1]
        done_f, killed_p = s[:F], s[F:F + P]
        # pad slots are born done/killed with zero score/loss, so the
        # remaining-mass sums get no pad contribution; only the done/killed
        # *fractions* must be re-normalized to the real counts so the value
        # net sees the feature distribution it was trained on
        rem_gain = jnp.sum((1 - done_f) * ctx.file_scores * ctx.file_loss)
        rem_fp = jnp.sum((1 - done_f) * (1 - ctx.file_scores))
        live = jnp.sum(ctx.proc_scores * (killed_p < 0.5))
        return jnp.stack([
            rem_gain, rem_fp, live,
            (jnp.sum(done_f) - (F - rF)) / jnp.maximum(rF, 1.0),
            (jnp.sum(killed_p) - (P - rP)) / jnp.maximum(rP, 1.0),
            s[F + P] / 60.0, s[F + P + 1] / max_steps,
            s[F + P + 2],
        ])

    def vfn(ctx: _Ctx, feats: jnp.ndarray) -> jnp.ndarray:
        if value_apply is None:
            return heuristic_value(feats)
        return value_apply(ctx.value_params, feats)

    # --- the search program ------------------------------------------------

    def ucb(ctx: _Ctx, t: _Tree, i: jnp.ndarray) -> jnp.ndarray:
        kids = t.children[i]
        has = kids >= 0
        safe = jnp.maximum(kids, 0)
        nv = jnp.where(has, t.visits[safe], 0)
        vs = jnp.where(has, t.value_sum[safe], 0.0)
        q = jnp.where(nv > 0, vs / jnp.maximum(nv, 1), 0.0) / 50.0
        total = jnp.maximum(t.visits[i], 1)
        u = (c_puct * ctx.prior
             * jnp.sqrt(total.astype(jnp.float32)) / (1.0 + nv))
        score = q + u + t.child_reward[i] / 50.0
        return jnp.where(legal(t.state[i]), score, -jnp.inf)

    def init_tree(root_state: jnp.ndarray) -> _Tree:
        return _Tree(
            visits=jnp.zeros(M, jnp.int32),
            value_sum=jnp.zeros(M, jnp.float32),
            parent=jnp.full(M, -1, jnp.int32),
            parent_action=jnp.full(M, -1, jnp.int32),
            children=jnp.full((M, A), -1, jnp.int32),
            child_reward=jnp.zeros((M, A), jnp.float32),
            expanded=jnp.zeros(M, bool).at[0].set(True),
            terminal=jnp.zeros(M, bool).at[0].set(terminal(root_state)),
            state=jnp.zeros((M, D), jnp.float32).at[0].set(root_state),
            n_nodes=jnp.asarray(1, jnp.int32),
        )

    def search_chunk(t: _Tree, num_sims: jnp.ndarray, ctx: _Ctx) -> _Tree:
        """Run ``num_sims`` more simulations on an existing tree (resumable:
        plan() calls this in slices so the wall-clock budget stays
        enforceable between compiled chunks)."""

        def simulate(_, t: _Tree) -> _Tree:
            # SELECT: descend by UCB until an unvisited child slot or a
            # frontier (unexpanded/terminal) node
            def sel_cond(c):
                cur, act, need_new = c
                return (~need_new) & t.expanded[cur] & (~t.terminal[cur])

            def sel_body(c):
                cur, act, _ = c
                a = jnp.argmax(ucb(ctx, t, cur)).astype(jnp.int32)
                child = t.children[cur, a]
                need_new = child < 0
                nxt = jnp.where(need_new, cur, child)
                return nxt, a, need_new

            cur, act, need_new = jax.lax.while_loop(
                sel_cond, sel_body,
                (jnp.asarray(0, jnp.int32), jnp.asarray(-1, jnp.int32),
                 jnp.asarray(False)))

            # EXPAND: materialize the chosen child (no-op when the walk
            # ended on a terminal/unexpanded node instead)
            grow = need_new & (~t.terminal[cur])
            new = t.n_nodes
            s2, r = step(ctx, t.state[cur], act)
            idx = jnp.where(grow, new, M - 1)  # scratch slot when not growing
            t = t._replace(
                state=t.state.at[idx].set(
                    jnp.where(grow, s2, t.state[idx])),
                parent=t.parent.at[idx].set(
                    jnp.where(grow, cur, t.parent[idx])),
                parent_action=t.parent_action.at[idx].set(
                    jnp.where(grow, act, t.parent_action[idx])),
                terminal=t.terminal.at[idx].set(
                    jnp.where(grow, terminal(s2), t.terminal[idx])),
                expanded=t.expanded.at[idx].set(
                    jnp.where(grow, True, t.expanded[idx])),
                children=t.children.at[cur, act].set(
                    jnp.where(grow, new, t.children[cur, act])),
                child_reward=t.child_reward.at[cur, act].set(
                    jnp.where(grow, r, t.child_reward[cur, act])),
                n_nodes=t.n_nodes + grow.astype(jnp.int32),
            )
            leaf = jnp.where(grow, new, cur)

            # EVALUATE
            v = vfn(ctx, features(ctx, t.state[leaf])[None])[0]
            v = jnp.where(t.terminal[leaf], 0.0, v)

            # BACKUP: climb the parent chain accumulating edge rewards
            def up_cond(c):
                i, _, t_ = c
                return i >= 0

            def up_body(c):
                i, v_, t_ = c
                t_ = t_._replace(
                    visits=t_.visits.at[i].add(1),
                    value_sum=t_.value_sum.at[i].add(v_),
                )
                pa = t_.parent_action[i]
                pr = t_.parent[i]
                v_ = v_ + jnp.where(
                    pa >= 0, t_.child_reward[jnp.maximum(pr, 0), pa], 0.0)
                return pr, v_, t_

            _, _, t = jax.lax.while_loop(up_cond, up_body, (leaf, v, t))
            return t

        return jax.lax.fori_loop(0, num_sims, simulate, t)

    return _Programs(jax.jit(init_tree), jax.jit(search_chunk),
                     step, legal, terminal, features)


class _Programs(NamedTuple):
    """One shape-bucket's compiled entry points plus the raw (unjitted)
    domain ops, kept visible so tests can cross-check the branchless
    re-expression against the numpy UndoDomain transition."""

    init_tree: Any
    search_chunk: Any
    step: Any
    legal: Any
    terminal: Any
    features: Any


@dataclasses.dataclass
class DeviceMCTS:
    """Single-program MCTS over an :class:`UndoDomain`.

    Preferred value-net form is the pure pair ``value_apply`` (a stable
    ``(params, features) → values`` callable) + ``value_params`` — weights
    ride the `_Ctx` runtime arguments and the compiled search is shared
    across incidents.  ``value_fn`` (a params-closed callable) is kept for
    compatibility but forfeits cross-incident program reuse.
    """

    domain: UndoDomain
    cfg: MCTSConfig = dataclasses.field(default_factory=MCTSConfig)
    value_fn: Optional[callable] = None
    value_apply: Optional[callable] = None
    value_params: Any = None

    # Compiled-program shape buckets.  F and P are padded up to these floors
    # (then next power of two), so every incident below the floor compiles to
    # the SAME XLA executable.
    FILE_BUCKET_FLOOR = 256
    PROC_BUCKET_FLOOR = 16

    @staticmethod
    def _bucket(n: int, floor: int) -> int:
        n = max(int(n), 1)
        return max(floor, 1 << int(np.ceil(np.log2(n))))

    def __post_init__(self) -> None:
        d = self.domain
        F, P = d.F, d.P
        Fp = self._bucket(F, self.FILE_BUCKET_FLOOR)
        Pp = self._bucket(P, self.PROC_BUCKET_FLOOR)
        self._real = (F, P)
        self._dims = dict(F=Fp, P=Pp, A=Fp + Pp + 1, D=Fp + Pp + 3)

        def pad(a: np.ndarray, n: int) -> np.ndarray:
            out = np.zeros(n, np.float32)
            out[: len(a)] = a
            return out

        pr = d.priors()
        prior = np.zeros(Fp + Pp + 1, np.float32)
        prior[:F] = pr[:F]
        prior[Fp:Fp + P] = pr[F:F + P]
        prior[-1] = pr[-1]

        apply = self.value_apply
        params = self.value_params if apply is not None else ()
        if apply is None and self.value_fn is not None:
            # legacy closure: adapt to the (params, features) signature; the
            # unique lambda identity means this instance compiles privately
            fn = self.value_fn
            apply = lambda _p, feats: fn(feats)  # noqa: E731
        self._ctx = _Ctx(
            file_scores=jnp.asarray(pad(d.file_scores, Fp)),
            file_loss=jnp.asarray(pad(d.file_loss_mb, Fp)),
            proc_scores=jnp.asarray(pad(d.proc_scores, Pp)),
            prior=jnp.asarray(prior),
            real=jnp.asarray([F, P], jnp.float32),
            value_params=params if params is not None else (),
        )
        self._progs = _programs(
            Fp, Pp, self.cfg.num_simulations + 1, float(d.max_steps),
            float(self.cfg.c_puct), apply)
        self._init_tree = self._progs.init_tree
        self._search_chunk = self._progs.search_chunk

    def _pad_state(self, s: np.ndarray) -> np.ndarray:
        """Domain-shaped state [F+P+3] → padded [Fp+Pp+3]; pad files are
        born done and pad procs born killed, so they are never legal."""
        (F, P), (Fp, Pp) = self._real, (self._dims["F"], self._dims["P"])
        out = np.ones(self._dims["D"], np.float32)
        out[:F] = s[:F]
        out[Fp:Fp + P] = s[F:F + P]
        out[Fp + Pp:] = s[F + P:]
        return out

    def _action_map(self) -> np.ndarray:
        """Domain action index → padded action index (files | procs | stop)."""
        (F, P), (Fp, Pp) = self._real, (self._dims["F"], self._dims["P"])
        return np.concatenate(
            [np.arange(F), Fp + np.arange(P), [Fp + Pp]]).astype(np.int64)

    def _unpad_state(self, p: np.ndarray) -> np.ndarray:
        (F, P), (Fp, Pp) = self._real, (self._dims["F"], self._dims["P"])
        return np.concatenate([p[:F], p[Fp:Fp + P], p[Fp + Pp:]])

    # --- domain-coordinate views of the compiled ops (tests cross-check
    # these against the numpy UndoDomain transition) ------------------------

    def _step(self, s, a):
        amap = self._action_map()
        s2, r = self._progs.step(
            self._ctx, jnp.asarray(self._pad_state(np.asarray(s))),
            jnp.asarray(amap[int(a)]))
        return jnp.asarray(self._unpad_state(np.asarray(s2))), r

    def _legal(self, s):
        full = self._progs.legal(jnp.asarray(self._pad_state(np.asarray(s))))
        return jnp.asarray(np.asarray(full)[self._action_map()])

    def _terminal(self, s):
        return self._progs.terminal(
            jnp.asarray(self._pad_state(np.asarray(s))))

    def _features(self, s):
        return self._progs.features(
            self._ctx, jnp.asarray(self._pad_state(np.asarray(s))))

    def warmup(self) -> float:
        """Trace+compile the search program (one 1-sim chunk); returns
        seconds spent.  Idempotent and cheap once the executable is cached."""
        t0 = time.perf_counter()
        tree = self._init_tree(
            jnp.asarray(self._pad_state(self.domain.initial_state())))
        out = self._search_chunk(tree, jnp.asarray(1, jnp.int32), self._ctx)
        # the warmup is make_planner's compile-AND-execute gate — an
        # execute-time failure must raise HERE so 'auto' can fall back to
        # the host search
        sync_result(out)
        return time.perf_counter() - t0

    @classmethod
    def warmup_for(cls, num_files: int, num_procs: int,
                   cfg: Optional[MCTSConfig] = None,
                   value_apply=None, value_params=None,
                   max_steps: int = 64) -> "DeviceMCTS":
        """Compile the search executable for the shape bucket covering
        (num_files, num_procs) — what a resident daemon does at boot, before
        any incident exists.  Any later incident in the same bucket reuses
        the compiled program, keeping compile time out of MTTR."""
        n_f, n_p = max(int(num_files), 1), max(int(num_procs), 1)
        dummy = UndoDomain(
            file_paths=[f"/warm/{i}" for i in range(n_f)],
            file_scores=np.full(n_f, 0.5, np.float32),
            file_loss_mb=np.ones(n_f, np.float32),
            proc_names=[f"warm-{i}" for i in range(n_p)],
            proc_scores=np.full(n_p, 0.5, np.float32),
            max_steps=max_steps,
        )
        planner = cls(dummy, cfg or MCTSConfig(),
                      value_apply=value_apply, value_params=value_params)
        planner.warmup()
        return planner

    # kept for tests/debugging: one full search from a root state
    # (domain-shaped; padded internally)
    def _search(self, root_state: jnp.ndarray) -> _Tree:
        tree = self._init_tree(
            jnp.asarray(self._pad_state(np.asarray(root_state))))
        return self._search_chunk(
            tree, jnp.asarray(self.cfg.num_simulations, jnp.int32), self._ctx)

    def plan(self) -> UndoPlan:
        """Search within the spec budget (``timeout_seconds``) and extract.

        The search runs as compiled chunks of ≤128 simulations with a
        wall-clock check between them — a compiled loop cannot be
        interrupted, so chunking is what keeps the ≤5 min planning budget
        a real contract (host parity) at the cost of a handful of extra
        device syncs."""
        cfg = self.cfg
        t0 = time.perf_counter()
        tree = self._init_tree(
            jnp.asarray(self._pad_state(self.domain.initial_state())))
        done = 0
        chunk = min(128, cfg.num_simulations)
        while done < cfg.num_simulations:
            n = min(chunk, cfg.num_simulations - done)
            tree = self._search_chunk(tree, jnp.asarray(n, jnp.int32),
                                      self._ctx)
            done += n
            if time.perf_counter() - t0 > cfg.timeout_seconds:
                break
        tree = jax.device_get(tree)
        elapsed = time.perf_counter() - t0
        sims = int(tree.visits[0])
        # project the padded action axis back onto the domain's action space
        # (pad slots are never legal, so dropping them loses nothing)
        return extract_plan(
            self.domain, self.cfg,
            children=tree.children[:, self._action_map()],
            visits=tree.visits, value_sum=tree.value_sum,
            is_terminal=tree.terminal, expanded=tree.expanded,
            sims=sims, elapsed=elapsed, root=0,
        )
