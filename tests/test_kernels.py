"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tree as T
from repro.data.keysets import make_tree_data
from repro.kernels import ops, ref


# ------------------------------------------------------------------ bst_search
@pytest.mark.parametrize("n_keys", [1, 7, 100, 4095])
@pytest.mark.parametrize("n_queries", [1, 64, 700])
def test_bst_search_shape_sweep(n_keys, n_queries):
    keys, values = make_tree_data(n_keys, seed=n_keys)
    tree = T.build_tree(keys, values)
    rng = np.random.default_rng(n_queries)
    q = rng.choice(np.concatenate([keys, keys + 1]), size=n_queries).astype(np.int32)
    v1, f1 = ops.bst_search(tree.keys, tree.values, jnp.asarray(q), height=tree.height)
    v2, f2 = ref.bst_search_ref(tree.keys, tree.values, jnp.asarray(q), tree.height)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


@pytest.mark.parametrize("active_share", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("block_q", [128, 512])
def test_bst_search_config_sweep(active_share, block_q, medium_tree):
    tree, keys, _ = medium_tree
    rng = np.random.default_rng(0)
    q = rng.choice(np.concatenate([keys, keys + 1]), size=333).astype(np.int32)
    act = jnp.asarray(rng.random(333) < active_share)
    v1, f1 = ops.bst_search(
        tree.keys, tree.values, jnp.asarray(q), height=tree.height,
        active=act, block_q=block_q,
    )
    v2, f2 = ref.bst_search_ref(tree.keys, tree.values, jnp.asarray(q), tree.height, act)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


@given(st.integers(1, 300), st.integers(0, 10**6))
@settings(max_examples=15, deadline=None)
def test_bst_search_property(n_keys, seed):
    keys, values = make_tree_data(n_keys, seed=seed)
    tree = T.build_tree(keys, values)
    rng = np.random.default_rng(seed)
    q = rng.choice(np.concatenate([keys, keys + 1]), size=97).astype(np.int32)
    v1, f1 = ops.bst_search(tree.keys, tree.values, jnp.asarray(q), height=tree.height)
    v2, f2 = ref.bst_search_ref(tree.keys, tree.values, jnp.asarray(q), tree.height)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


# -------------------------------------------------------------- queue_dispatch
@pytest.mark.parametrize("n_dest,capacity,size", [
    (2, 2, 16), (8, 16, 128), (16, 8, 64), (4, 1, 33),
])
def test_queue_dispatch_sweep(n_dest, capacity, size):
    rng = np.random.default_rng(size)
    dest = jnp.asarray(rng.integers(-1, n_dest, size=size).astype(np.int32))
    b1, c1, o1 = ops.queue_dispatch(dest, n_dest=n_dest, capacity=capacity)
    b2, c2, o2 = ref.queue_dispatch_ref(dest, n_dest, capacity)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def _numpy_queue_model(dest, n_dest, capacity):
    """Independent NumPy model of the paper's queue mapping (Fig. 6): label
    each key with the count of earlier same-destination keys, keep it iff
    the label fits the buffer, preserve FIFO order."""
    buffers = np.full((n_dest, capacity), -1, np.int64)
    counts = np.zeros(n_dest, np.int64)
    overflow = np.zeros(len(dest), bool)
    for i, d in enumerate(dest):
        if d < 0:
            continue
        if counts[d] < capacity:
            buffers[d, counts[d]] = i
            counts[d] += 1
        else:
            overflow[i] = True
    return buffers, counts, overflow


@pytest.mark.parametrize("skew", ["all_one_dest", "two_hot", "mixed_inactive"])
def test_queue_dispatch_overflow_lanes(skew):
    """Force buffer overflow and pin the overflow_ref path of the Pallas
    kernel (and the jnp oracle) against the NumPy model: overflowed lanes
    must be flagged, NEVER placed in any buffer slot, and never counted."""
    n_dest, capacity, size = 4, 3, 40
    rng = np.random.default_rng(17)
    if skew == "all_one_dest":
        dest = np.zeros(size, np.int32)  # every lane overflows past slot 2
    elif skew == "two_hot":
        dest = rng.choice(np.array([1, 2], np.int32), size)
    else:  # inactive lanes interleaved with a hot destination
        dest = rng.choice(np.array([-1, 0, 0, 0, 3], np.int32), size)
    b_np, c_np, o_np = _numpy_queue_model(dest, n_dest, capacity)
    assert o_np.any(), "scenario must actually overflow"

    for use_ref in (False, True):
        b, c, o = ops.queue_dispatch(
            jnp.asarray(dest), n_dest=n_dest, capacity=capacity, use_ref=use_ref
        )
        tag = f"use_ref={use_ref}"
        np.testing.assert_array_equal(np.asarray(b), b_np, err_msg=tag)
        np.testing.assert_array_equal(np.asarray(c), c_np, err_msg=tag)
        np.testing.assert_array_equal(np.asarray(o), o_np, err_msg=tag)
        placed = np.asarray(b).reshape(-1)
        placed = set(placed[placed >= 0].tolist())
        # disjointness: a lane is either buffered or overflowed, never both
        assert placed.isdisjoint(np.flatnonzero(o_np).tolist()), tag
        kept = ~o_np & (dest >= 0)
        assert placed == set(np.flatnonzero(kept).tolist()), tag
        assert int(np.asarray(c).sum()) == int(kept.sum()), tag


# ------------------------------------------------------------- flash_attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("BH,BHkv,Sq,Skv,d,causal,window", [
    (4, 2, 256, 256, 64, True, None),   # GQA causal
    (4, 4, 128, 256, 32, True, None),   # decode-style offset
    (2, 1, 256, 256, 64, True, 128),    # sliding window
    (8, 2, 128, 128, 128, False, None), # bidirectional (encoder)
    (2, 2, 384, 384, 64, True, 256),    # window > block
])
def test_flash_attention_sweep(dtype, BH, BHkv, Sq, Skv, d, causal, window):
    kq = jax.random.normal(jax.random.key(0), (BH, Sq, d), jnp.float32).astype(dtype)
    kk = jax.random.normal(jax.random.key(1), (BHkv, Skv, d), jnp.float32).astype(dtype)
    kv = jax.random.normal(jax.random.key(2), (BHkv, Skv, d), jnp.float32).astype(dtype)
    o1 = ops.flash_attention(kq, kk, kv, causal=causal, window=window)
    o2 = ops.flash_attention(kq, kk, kv, causal=causal, window=window, use_ref=True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32), atol=tol, rtol=tol
    )


def test_flash_matches_blockwise_impl():
    """The jnp blockwise path (used in dry-runs) == the Pallas kernel."""
    from repro.models.attention import _blockwise_attn

    B, Sq, H, KV, hd = 2, 256, 4, 2, 64
    q = jax.random.normal(jax.random.key(0), (B, Sq, H, hd))
    k = jax.random.normal(jax.random.key(1), (B, Sq, KV, hd))
    v = jax.random.normal(jax.random.key(2), (B, Sq, KV, hd))
    blockwise = _blockwise_attn(q, k, v, True, None, 64, hd**-0.5)
    qf = q.swapaxes(1, 2).reshape(B * H, Sq, hd)
    kf = k.swapaxes(1, 2).reshape(B * KV, Sq, hd)
    vf = v.swapaxes(1, 2).reshape(B * KV, Sq, hd)
    flash = ops.flash_attention(qf, kf, vf, causal=True)
    flash = flash.reshape(B, H, Sq, hd).swapaxes(1, 2)
    np.testing.assert_allclose(
        np.asarray(blockwise), np.asarray(flash), atol=1e-5, rtol=1e-5
    )
