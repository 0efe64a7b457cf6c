"""The stream encoder's two sequence kernels compiled at the published
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
    from nerrf_tpu.ops.ssm import selective_scan

    def loss(x, dt, a, b, c, d, first):
        return jnp.sum(selective_scan(x, dt, a, b, c, d, first) ** 2)

    td, tn = shape(one_chip, (T, D_INNER)), shape(one_chip, (T, D_STATE))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
        td, td, shape(one_chip, (D_INNER, D_STATE)), tn, tn,
        shape(one_chip, (D_INNER,)), shape(one_chip, (T,), jnp.bool_)
    ).compile()
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
