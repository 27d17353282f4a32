"""The forest kernel compiles for a v5e chip at the served size.

Ahead-of-time compiles for a described (not attached) ``v5e:2x2`` topology:
the Mosaic compiler refuses here what the chip would refuse -- unsupported
gathers, misaligned blocks, too much VMEM -- at no chip time.  Sizes are
the chip smoke's: a height-19 tree (2^20 - 1 keys) and 512-lane blocks.
Nothing runs; results are the interpret-mode tests' concern.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis import invariants
from repro.kernels import bst_search as bs
from repro.kernels import ref

HEIGHT = 19
N_NODES = (1 << (HEIGHT + 1)) - 1
BATCH = 8192
BLOCK_Q = 512
DELTA_CAPACITY = 4096
SPLIT = 3  # Hyb8: eight vertical subtrees
CAPACITY = invariants.buffer_capacity(BLOCK_Q, 1 << SPLIT, 2.0)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache, so keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _hrz_membership(k, v, q):
    return bs.bst_search_forest_pallas(k[None], v[None], q[None], HEIGHT, block_q=BLOCK_Q)


def _dup_ordered_delta(k, v, q, dk, dv, dt, dw):
    q8 = q.reshape(8, -1)
    out = bs.bst_ordered_forest_pallas(
        k[None], v[None], q8, HEIGHT, block_q=BLOCK_Q, shared_tree=True
    )
    return ref.merge_delta_resolution(out, *ref.bst_delta_resolve_ref(dk, dv, dt, dw, q8))


def _hyb8q_ordered(k, v, q):
    return bs.bst_hybrid_forest_pallas(
        k, v, q, HEIGHT, SPLIT, "queue", CAPACITY, block_q=BLOCK_Q
    )


def _hyb8_direct(k, v, q):
    return bs.bst_hybrid_forest_pallas(
        k, v, q, HEIGHT, SPLIT, "direct", CAPACITY, block_q=BLOCK_Q, ordered=False
    )


@pytest.mark.parametrize(
    "fn,with_delta",
    [
        (_hrz_membership, False),
        (_dup_ordered_delta, True),
        (_hyb8q_ordered, False),
        (_hyb8_direct, False),
    ],
    ids=["hrz-membership", "dup-ordered-delta", "hyb8q-ordered", "hyb8-direct"],
)
def test_forest_kernel_compiles_for_v5e(one_chip, fn, with_delta):
    args = [_spec((N_NODES,), one_chip)] * 2 + [_spec((BATCH,), one_chip)]
    if with_delta:
        args += [_spec((DELTA_CAPACITY,), one_chip)] * 4
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
