"""The real entry points the deep pass traces, as rule-consumable specs.

One registry so the contracts and the production code can only drift in
one place: the serve ladder resolves through `serve.config.ServeConfig` +
`serve.service.warmup_batches` (exactly what `start()` compiles), the
train boundary through `train.loop.make_flat_train_step` (exactly what the
compile cache serializes), the shard_map shim through
`parallel.ring.ring_self_attention`, and the sharding layout through
`parallel.train.sharding_contract`.  Everything is built at *micro* model
scale — the contracts quantify over program structure (jit boundaries,
donation specs, collective axes, key material), which is config-size
independent, and micro tensors keep each abstract trace ~1 s.
"""

from __future__ import annotations

import functools
from typing import List

from nerrf_tpu.analysis.programs.abstract import (
    CacheKeyEntry,
    CollectiveEntry,
    DonationEntry,
    aval,
    avals_of_spec,
    micro_train_config,
    param_avals,
)

TRAIN_LOOP = "nerrf_tpu/train/loop.py"
SERVE_SERVICE = "nerrf_tpu/serve/service.py"
RING = "nerrf_tpu/parallel/ring.py"
PARALLEL_TRAIN = "nerrf_tpu/parallel/train.py"
RESPOND_PLANNER = "nerrf_tpu/respond/planner.py"
TRAIN_STREAM = "nerrf_tpu/train/stream.py"


def _micro_ds_cfg():
    from nerrf_tpu.graph import GraphConfig
    from nerrf_tpu.train.data import DatasetConfig

    return DatasetConfig(graph=GraphConfig(max_nodes=64, max_edges=128),
                         seq_len=16, max_seqs=8)


def _micro_batch_avals(batch: int = 2) -> dict:
    from nerrf_tpu.train.data import sample_spec

    return avals_of_spec(sample_spec(_micro_ds_cfg()), batch=batch)


def _abstract_model_args(model_cfg, batch_size: int):
    """(params avals, batch avals) for a micro-bucket eval/train program
    — THE one derivation all entry builders share, so the donation and
    cache-key contracts can never trace differently-constructed args."""
    from nerrf_tpu.models import NerrfNet

    batch = _micro_batch_avals(batch_size)
    sample = {k: aval(v.shape[1:], v.dtype) for k, v in batch.items()}
    return param_avals(NerrfNet(model_cfg), sample), batch


def _eval_entry(model_cfg):
    """(eval jit fn, (params, batch)) — the serve-eval program at micro
    scale, shared by the donation and cache-key entries."""
    from nerrf_tpu.models import NerrfNet
    from nerrf_tpu.train.loop import make_eval_fn

    params, batch = _abstract_model_args(model_cfg, batch_size=2)
    return make_eval_fn(NerrfNet(model_cfg)), (params, batch)


def _flat_step_args(cfg):
    """Abstract (params, opt_state, step, batch, rng) for the flat train
    boundary — the exact aval tuple the compile cache fingerprints."""
    import jax
    import numpy as np

    from nerrf_tpu.train.loop import make_tx

    params, batch = _abstract_model_args(cfg.model, cfg.batch_size)
    opt_state = jax.eval_shape(make_tx(cfg).init, params)
    return (params, opt_state, aval((), np.int32), batch,
            aval((2,), np.uint32))


@functools.lru_cache(maxsize=None)
def _stream_step_entry(window: int = 4):
    """(flat jit fn, avals, key extra) of the stream pretrainer's scheduled
    resident step at micro scale: one windowed attention layer, two packed
    sequences of 16 tokens resident — the function `cache_train_step`
    serializes (`train.stream.make_stream_step`).  One layer, not the six
    kinds: the contracts are about the step's boundary (donation, key
    material), and tracing a scan layer three times cost the deep pass 6 s
    of its 30 s budget (26.6 s against 20.3 s, the parent's 20.0)."""
    import jax
    import numpy as np

    from nerrf_tpu.models.stream import StreamConfig, StreamNet
    from nerrf_tpu.train.loop import (TrainConfig, make_train_step_scheduled,
                                      make_tx, step_key_extra)
    from nerrf_tpu.train.stream import make_stream_loss_fn, stream_key_extra

    scfg = StreamConfig(dim=8, num_heads=2, num_kv_heads=2, head_dim=4,
                        mlp_dim=16, window=window, d_state=2, dt_rank=2,
                        num_layers=1, kinds=("swa",), vocab_size=32,
                        dropout=0.0, remat=False)
    cfg = TrainConfig(batch_size=1, num_steps=4, warmup_steps=1)
    model = StreamNet(scfg)
    data = {"tokens": np.zeros((2, 16), np.int32),
            "segments": np.ones((2, 16), np.int32)}
    step = make_train_step_scheduled(
        model, cfg, data, np.zeros((4, 1), np.int32),
        loss_fn=make_stream_loss_fn(model))
    tok = aval((1, 16), np.int32)
    params = jax.eval_shape(
        lambda r, t, g: model.init(r, t, g)["params"],
        aval((2,), np.uint32), tok, tok)
    args = (params, jax.eval_shape(make_tx(cfg).init, params),
            aval((), np.int32), aval((2,), np.uint32),
            {k: aval(v.shape, v.dtype) for k, v in data.items()},
            aval((4, 1), np.int32))
    extra = {**step_key_extra(cfg, "stream_step_scheduled"),
             **stream_key_extra(scfg)}
    return step.flat_jit_fn, args, extra


def donation_entries() -> List[DonationEntry]:
    cfg = micro_train_config()

    def build_flat_entry():
        from nerrf_tpu.models import NerrfNet
        from nerrf_tpu.train.loop import make_flat_train_step

        return (make_flat_train_step(NerrfNet(cfg.model), cfg),
                _flat_step_args(cfg))

    def build_eval():
        return _eval_entry(cfg.model)

    return [
        # the compile-cache boundary: (params, opt_state) donated, both
        # mandatory (an un-donated flagship state doubles peak HBM)
        DonationEntry(name="train_step_flat", path=TRAIN_LOOP,
                      build=build_flat_entry, donate=(0, 1),
                      must_donate=(0, 1)),
        # the serve scorer: params are SHARED across every batch and
        # stream — donation here would free the live weights mid-serve,
        # so the contract is exactly zero aliased inputs
        DonationEntry(name="serve_eval", path=TRAIN_LOOP,
                      build=build_eval, donate=(), must_donate=()),
        # the stream pretrainer's step: 11 GB of state at the published
        # widths, donated or the step does not fit one chip
        DonationEntry(name="stream_step_scheduled", path=TRAIN_STREAM,
                      build=lambda: _stream_step_entry()[:2],
                      donate=(0, 1), must_donate=(0, 1)),
    ]


def collective_entries() -> List[CollectiveEntry]:
    def build_ring():
        import jax
        import numpy as np
        from jax.sharding import Mesh

        from nerrf_tpu.parallel.ring import ring_self_attention

        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                    axis_names=("dp", "sp"))
        q = aval((2, 16, 2, 8), np.float32)
        return (lambda qq, kk, vv: ring_self_attention(qq, kk, vv, mesh),
                (q, q, q))

    return [
        CollectiveEntry(name="ring_self_attention", path=RING,
                        build=build_ring, mesh_axes=("dp", "sp"),
                        axis_sizes={"dp": 1, "sp": 2}),
    ]


def sharding_contracts() -> list:
    """(program, array, spec, ndim, mesh_axes) rows from the declared pjit
    layouts — checked without any tracing."""
    import jax

    from nerrf_tpu.parallel.mesh import MeshConfig, make_mesh
    from nerrf_tpu.parallel.train import sharding_contract

    mesh = make_mesh(MeshConfig(dp=1, tp=1, sp=1),
                     devices=jax.devices()[:1])
    return [(prog, arr, spec, ndim, tuple(mesh.axis_names))
            for prog, arr, spec, ndim in sharding_contract(mesh)]


def cache_key_entries() -> List[CacheKeyEntry]:
    import dataclasses

    cfg = micro_train_config()

    def train_variant(c, flavor="train_step"):
        from nerrf_tpu.models import NerrfNet
        from nerrf_tpu.train.loop import make_flat_train_step, step_key_extra

        def build():
            return make_flat_train_step(NerrfNet(c.model), c), \
                _flat_step_args(c)

        return build, step_key_extra(c, flavor)

    def serve_variant(model_cfg):
        from nerrf_tpu.compilecache import serve_program_key

        return (lambda: _eval_entry(model_cfg),
                serve_program_key(model_cfg, "64n/128e/8s"))

    # perturbations chosen to change the HLO while keeping the argument
    # avals IDENTICAL — precisely the drift only the `extra` key material
    # can catch (aval-changing axes are covered by the avals themselves)
    cfg_pw = dataclasses.replace(cfg, pos_weight=cfg.pos_weight + 1.0)
    # in-step telemetry: identical argument avals, different lowered
    # program AND output treedef — the axis PR 14's trainwatch added; a
    # fingerprint hole here would let a telemetry-off executable (whose
    # stored out-treedef lacks the telemetry leaves) serve a telemetry-on
    # run
    cfg_tel = dataclasses.replace(cfg, telemetry=True)
    base_model = cfg.model
    agg_model = dataclasses.replace(
        base_model,
        gnn=dataclasses.replace(base_model.gnn, aggregation="dense_adj"))

    def respond_variant(mcts_cfg, max_steps):
        """(build, extra) for the respond tier's batched search at one
        point of its config axis — build resolves the EXACT vmapped
        closure the router warms (`respond._batched_programs`), so the
        audit traces the production program, not a stand-in."""
        from nerrf_tpu.planner.device_mcts import DeviceMCTS
        from nerrf_tpu.respond.planner import (_batched_programs,
                                               _stack_ctx,
                                               respond_program_key)

        B = 2

        def build():
            import jax.numpy as jnp

            dm = DeviceMCTS.warmup_for(4, 2, mcts_cfg,
                                       max_steps=max_steps)
            dims = dm._dims
            init_b, search_b = _batched_programs(
                dims["F"], dims["P"], mcts_cfg.num_simulations + 1,
                float(dm.domain.max_steps), float(mcts_cfg.c_puct),
                None, B)
            roots = jnp.stack(
                [jnp.asarray(dm._pad_state(dm.domain.initial_state()))] * B)
            tree = init_b(roots)
            ctx = _stack_ctx([dm._ctx] * B)
            return search_b, (tree, jnp.asarray(1, jnp.int32), ctx)

        # bucket floors make micro dims land in the 256f/16p bucket
        return build, respond_program_key(256, 16, B, mcts_cfg,
                                          float(max_steps))

    def _micro_mcts(**over):
        from nerrf_tpu.planner.mcts import MCTSConfig

        return MCTSConfig(num_simulations=over.pop("num_simulations", 4),
                          **over)

    t_base, t_base_extra = train_variant(cfg)
    t_pw, t_pw_extra = train_variant(cfg_pw)
    t_tel, t_tel_extra = train_variant(cfg_tel)
    s_base, s_base_extra = serve_variant(base_model)
    s_agg, s_agg_extra = serve_variant(agg_model)
    # perturbations that change the search program while keeping the tree/
    # ctx avals identical: the PUCT constant and the step horizon are both
    # folded into the lowered HLO as literals
    r_base, r_base_extra = respond_variant(_micro_mcts(), 64)
    r_puct, r_puct_extra = respond_variant(_micro_mcts(c_puct=2.5), 64)
    r_horizon, r_horizon_extra = respond_variant(_micro_mcts(), 32)
    # the attention window folds into the masks as a literal: same avals,
    # another program — only `stream_key_extra` tells them apart
    st_base, st_window = _stream_step_entry(4), _stream_step_entry(8)
    return [
        CacheKeyEntry(
            name="stream_step_scheduled", path=TRAIN_STREAM,
            variants=[("base", lambda: st_base[:2], st_base[2]),
                      ("window", lambda: st_window[:2], st_window[2])]),
        CacheKeyEntry(
            name="train_step_flat", path=TRAIN_LOOP,
            variants=[("base", t_base, t_base_extra),
                      ("pos_weight", t_pw, t_pw_extra),
                      ("telemetry", t_tel, t_tel_extra)]),
        CacheKeyEntry(
            name="serve_eval", path=SERVE_SERVICE,
            variants=[("base", s_base, s_base_extra),
                      ("aggregation", s_agg, s_agg_extra)]),
        CacheKeyEntry(
            name="respond_search", path=RESPOND_PLANNER,
            variants=[("base", r_base, r_base_extra),
                      ("c_puct", r_puct, r_puct_extra),
                      ("max_steps", r_horizon, r_horizon_extra)]),
    ]
