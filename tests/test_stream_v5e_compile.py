"""The stream encoder's sequence kernels compiled at the published
widths for a described TPU v5e (no chip attached: nothing runs, the chip's
compiler accepts or refuses, and its memory plan is read).  One file, one
process: the fixture loads the TPU's library (on-chip-measurement guide,
section 2)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

T, D_INNER, D_STATE = 8192, 5120, 16


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def shape(one_chip, dims, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)


def test_scan_backward_never_holds_every_state(one_chip):
    """Forward + backward of `selective_scan` at T = 8192, d_inner 5120,
    d_state 16: all states would be 2.7 GB in float32; the chunked scan's
    plan stays under a third of that."""
    from nerrf_tpu.ops.ssm import scan_route, selective_scan

    # the backend here is the CPU's: the XLA route
    assert scan_route(T, D_INNER, D_STATE) == "xla_chunked"

    def loss(x, dt, a, b, c, d, first):
        return jnp.sum(selective_scan(x, dt, a, b, c, d, first) ** 2)

    td, tn = shape(one_chip, (T, D_INNER)), shape(one_chip, (T, D_STATE))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        td, td, shape(one_chip, (D_INNER, D_STATE)), tn, tn,
        shape(one_chip, (D_INNER,)), shape(one_chip, (T,), jnp.bool_)
    ).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < T * D_INNER * D_STATE * 4 / 3, temp


def test_scan_fused_route_compiles_at_the_published_widths(one_chip,
                                                          monkeypatch):
    """The same scan on the route a TPU traces (`ssm.scan_route` ->
    ``pallas``), under `vmap` as `_Mamba` calls it, forward + backward: the
    chip's compiler accepts both kernels at the module's chunk and channel
    block (the backward kernel's recomputed states and decays, 8.4 MB, in
    its fast memory, within the default scoped limit), the program holds
    one custom call a pass, and the plan stays under the chunked scan's
    bound."""
    from nerrf_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.scan_route(T, D_INNER, D_STATE) == "pallas"

    def loss(x, dt, a, b, c, d, first):
        y = jax.vmap(ssm.selective_scan, in_axes=(0, 0, None, 0, 0, None, 0))(
            x, dt, a, b, c, d, first)
        return jnp.sum(y ** 2)

    td, tn = shape(one_chip, (1, T, D_INNER)), shape(one_chip, (1, T, D_STATE))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        shape(one_chip, (1, T, D_INNER), jnp.bfloat16), td,
        shape(one_chip, (D_INNER, D_STATE)), tn, tn,
        shape(one_chip, (D_INNER,)), shape(one_chip, (1, T), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < T * D_INNER * D_STATE * 4 / 3, temp


def test_local_attention_compiles_with_window_and_segments(one_chip,
                                                           window=512):
    """The blockwise path at 40 heads of 64 with the value pair (128 wide),
    a window and packed documents, forward + backward, for one chip."""
    from nerrf_tpu.parallel.ring import _attention_local

    def loss(q, k, v, seg):
        return jnp.sum(_attention_local(q, k, v, True, window=window,
                                        q_seg=seg, k_seg=seg)
                       .astype(jnp.float32) ** 2)

    qk = shape(one_chip, (1, T, 40, 64), jnp.bfloat16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qk, qk, shape(one_chip, (1, T, 40, 128), jnp.bfloat16),
        shape(one_chip, (1, T), jnp.int32)).compile()
    # never the [heads, T, T] scores (10.7 GB in float32); 2.2 GB today
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_sparse_attention_runs_its_blocks_one_after_another(one_chip):
    """`ops/dsa.py::sparse_attention` at 32 query / 4 key-value heads of 128,
    16 index heads of 64 and 2048 keys a query, forward + backward: a block
    of 256 queries holds a few [32, 256, 8192] float32 arrays, and the four
    key lengths' copies of the block each get scratch of their own; side by
    side (a Python loop over the blocks) the plan was 98 GB."""
    from nerrf_tpu.ops import dsa

    def loss(q, k, v, qi, ki, wi, seg):
        o, kl, _ = dsa.sparse_attention(q, k, v, qi, ki, wi, seg, topk=2048)
        return jnp.sum(o.astype(jnp.float32) ** 2) + kl

    kv = shape(one_chip, (T, 4, 128), jnp.bfloat16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        shape(one_chip, (T, 32, 128), jnp.bfloat16), kv, kv,
        shape(one_chip, (T, 16, 64)), shape(one_chip, (T, 64)),
        shape(one_chip, (T, 16)), shape(one_chip, (T,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 5 << 30


def test_sparse_attention_fused_route_compiles_at_the_published_widths(
        one_chip, monkeypatch):
    """The same op on the route a TPU traces (`dsa.attention_route` ->
    ``pallas_flash``), under `vmap` as the layer calls it, forward +
    backward: the chip's compiler accepts the forward kernel at each of the
    four key lengths (every head of a block of 256 queries resident, the
    block's position a prefetched scalar that `vmap` leaves alone) and the
    one backward kernel (a key-value head's ``dk``, ``dv`` [8192, 128]
    float32 in its fast memory), and no [heads, 256, L] score reaches HBM:
    the plan of a batch of two is the indexer's blocks (0.78 GB; 1.74 GB on
    the XLA route), not the attention's."""
    from nerrf_tpu.ops import dsa

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dsa.attention_route(T, 32, 4, 128) == "pallas_flash"

    def loss(q, k, v, qi, ki, wi, seg):
        o, kl, _ = jax.vmap(lambda *a: dsa.sparse_attention(*a, topk=2048))(
            q, k, v, qi, ki, wi, seg)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(kl)

    kv = shape(one_chip, (2, T, 4, 128), jnp.bfloat16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        shape(one_chip, (2, T, 32, 128), jnp.bfloat16), kv, kv,
        shape(one_chip, (2, T, 16, 64)), shape(one_chip, (2, T, 64)),
        shape(one_chip, (2, T, 16)),
        shape(one_chip, (2, T), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert "dsa_flash_fwd" in text and "dsa_flash_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_latent_attention_runs_its_blocks_one_after_another(one_chip):
    """`ops/mla.py::attention` at 20 heads with 256-wide assembled keys and
    256-wide values, the one rotary key broadcast by `assemble`, forward +
    backward: a block of 256 queries holds a few [20, 256, 8192] float32
    arrays (168 MB each), never the [20, T, T] scores (5.4 GB)."""
    from nerrf_tpu.ops import dsa, mla

    def loss(q, k_r, kv, seg):
        o = mla.attention(*mla.assemble(q, k_r, kv, dsa.doc_positions(seg),
                                        nope=192, theta=1e6), seg)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(one_chip, (T, 20, 256), jnp.bfloat16),
        shape(one_chip, (T, 64), jnp.bfloat16),
        shape(one_chip, (T, 20, 448), jnp.bfloat16),
        shape(one_chip, (T,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 30


def test_latent_attention_fused_route_compiles_at_the_published_widths(
        one_chip, monkeypatch):
    """The same core on the route a TPU traces (`mla.attention_route` ->
    ``pallas_flash``), forward + backward: the chip's compiler accepts both
    kernels at the module's tile sizes (a [512, 512] float32 tile and a
    head's whole ``dq`` [8192, 256] in its fast memory), the program holds
    one custom call a pass, and no score ever reaches HBM: the plan of a
    batch of two is their float32 ``dq`` and the rows' sums, not the XLA
    route's gigabytes."""
    from nerrf_tpu.ops import dsa, mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mla.attention_route(T, 256, 256) == "pallas_flash"

    def loss(q, k_r, kv, seg):
        # under `vmap` as the layer calls it (`models/stream.py`): the
        # kernels' batching rule adds the batch to their grids
        o = jax.vmap(lambda q, k_r, kv, seg: mla.attention(*mla.assemble(
            q, k_r, kv, dsa.doc_positions(seg), nope=192, theta=1e6), seg))(
                q, k_r, kv, seg)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(one_chip, (2, T, 20, 256), jnp.bfloat16),
        shape(one_chip, (2, T, 64), jnp.bfloat16),
        shape(one_chip, (2, T, 20, 448), jnp.bfloat16),
        shape(one_chip, (2, T), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "mla_flash_fwd" in text and "mla_flash_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_expert_walk_never_copies_the_weights_per_tile(one_chip):
    """`ops/moe.py::moe_share` at 8192 tokens, 16 held experts of 2048 x
    768, 8 of 128 a token, forward + backward: the worst routing has 272
    tiles, and a copy of the three matrices for each (what reverse mode
    through a scan of conds kept) would be 38 GB; the hand-written walk
    holds the float32 gradients (0.45 GB), the buffer and its row maps."""
    from nerrf_tpu.ops import moe

    def loss(x, logits, wg, wu, wd):
        y, _ = moe.moe_share(x, logits, wg, wu, wd, k=8, first=0)
        return jnp.sum(y ** 2)

    w = shape(one_chip, (16, 2048, 768))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4))).lower(
        shape(one_chip, (T, 2048), jnp.bfloat16), shape(one_chip, (T, 128)),
        w, w, shape(one_chip, (16, 768, 2048))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


@pytest.mark.parametrize("heads, window", [(72, 512), (48, None)])
def test_grouped_cores_fused_route_compiles_at_the_published_widths(
        one_chip, monkeypatch, heads, window):
    """`ops/mla.py::attention` as the grouped-query kinds call it: 72 query
    heads over 8 key-value heads of 128 inside a window of 512, and 48 over
    8 over the whole document, forward + backward under `vmap`, on the route
    a TPU traces: the chip's compiler accepts both kernels with the key-value
    head read at ``h // group`` and the window's short walk, and the plan of
    a batch of two holds the query heads' shares of ``dk``, ``dv`` (0.6 GB at
    72 heads), their sums and the operands turned heads-first (1.22 GB in
    all), never the scores (19 GB at 72 heads in float32)."""
    from nerrf_tpu.ops import mla

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert mla.attention_route(T, 128, 128) == "pallas_flash"

    def loss(q, k, v, seg):
        o = jax.vmap(lambda q, k, v, seg: mla.attention(
            q, k, v, seg, window=window, scope="gqa_attention"))(q, k, v, seg)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    kv = shape(one_chip, (2, T, 8, 128), jnp.bfloat16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(one_chip, (2, T, heads, 128), jnp.bfloat16), kv, kv,
        shape(one_chip, (2, T), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "mla_flash_fwd" in text and "mla_flash_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 29


def test_delta_rule_compiles_at_the_published_widths(one_chip):
    """`ops/kda.py::chunk_delta` as the ``kda_*`` kinds call it (under
    `vmap`, batch 1): 8192 tokens, 32 heads with 128 x 128 states, forward +
    backward.  The chip's compiler accepts the chunk walk; the plan (0.40
    GB) holds the chunk-start states (268 MB) and one chunk's work at a
    time, never every step's state (17 GB in float32) nor every chunk's
    pairwise decays (8.6 GB)."""
    from nerrf_tpu.ops import kda

    def loss(q, k, v, g, beta, seg):
        o = jax.vmap(kda.chunk_delta)(q, k, v, g, beta, seg)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    qk = shape(one_chip, (1, T, 32, 128), jnp.bfloat16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, qk, shape(one_chip, (1, T, 32, 128)),
        shape(one_chip, (1, T, 32)), shape(one_chip, (1, T), jnp.int32)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_delta_rule_fused_route_compiles_at_the_published_widths(
        one_chip, monkeypatch):
    """The same rule on the route a TPU traces (`kda.delta_route` ->
    ``pallas``), under `vmap` as the ``kda_*`` kinds call it, forward +
    backward: the chip's compiler accepts both kernels at the module's
    chunk and head block, the program holds one custom call a pass, and
    the plan (the kept chunk-start states, the kernels' outputs) stays
    under the XLA route's bound."""
    from nerrf_tpu.ops import kda

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda.delta_route(T, 128, 128) == "pallas"

    def loss(q, k, v, g, beta, seg):
        o = jax.vmap(kda.chunk_delta)(q, k, v, g, beta, seg)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    qk = shape(one_chip, (1, T, 32, 128), jnp.bfloat16)
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, qk, shape(one_chip, (1, T, 32, 128)),
        shape(one_chip, (1, T, 32)), shape(one_chip, (1, T), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "kda_fwd" in text and "kda_bwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
