"""Acceptance tests for the forest-batched flat kernel (DESIGN.md §2).

Every strategy's descent must lower to exactly ONE ``pallas_call`` over one
flat level-major tree operand, and the results must be bit-identical to
``search_reference`` -- including at heights the old per-level-operand
kernel was never exercised at (> 12).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import plans, tree as T
from repro.core.engine import BSTEngine, PAPER_CONFIGS, EngineConfig
from repro.data.keysets import make_tree_data
from repro.kernels import ops


def _queries(keys, size, seed=0):
    rng = np.random.default_rng(seed)
    return rng.choice(np.concatenate([keys, keys + 1]), size=size).astype(np.int32)


def _nested_jaxprs(value):
    from jax._src import core as jcore

    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _nested_jaxprs(v)


def _count_pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in _nested_jaxprs(v):
                n += _count_pallas_calls(sub)
    return n


# ----------------------------------------------------------------- bit-ident.
@pytest.mark.parametrize("height", [4, 9, 13, 16])
def test_forest_kernel_matches_reference_deep_trees(height):
    """Heights up to 16 -- the per-level-operand kernel stopped at ~12."""
    n_keys = (1 << (height + 1)) - 1  # perfect tree, no sentinel padding
    keys, values = make_tree_data(n_keys, seed=height)
    tree = T.build_tree(keys, values)
    assert tree.height == height
    q = _queries(keys, 512, seed=height)
    ref_v, ref_f = T.search_reference(tree, jnp.asarray(q))
    v, f = ops.bst_search_forest(
        tree.keys[None], tree.values[None], jnp.asarray(q)[None], height=height
    )
    np.testing.assert_array_equal(np.asarray(v[0]), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(f[0]), np.asarray(ref_f))


def test_forest_kernel_shared_tree_rows():
    """dup layout: one operand row serves every query row bit-identically."""
    keys, values = make_tree_data(4095, seed=1)
    tree = T.build_tree(keys, values)
    q = _queries(keys, 1024, seed=2).reshape(4, 256)
    v, f = ops.bst_search_forest(
        tree.keys[None],
        tree.values[None],
        jnp.asarray(q),
        height=tree.height,
        shared_tree=True,
    )
    for row in range(4):
        ref_v, ref_f = T.search_reference(tree, jnp.asarray(q[row]))
        np.testing.assert_array_equal(np.asarray(v[row]), np.asarray(ref_v))
        np.testing.assert_array_equal(np.asarray(f[row]), np.asarray(ref_f))


# ----------------------------------------------------- one pallas_call per plan
@pytest.mark.parametrize("strategy,n_trees,mapping", [
    ("hrz", 1, "queue"),
    ("dup", 4, "queue"),
    ("hyb", 4, "queue"),
    ("hyb", 4, "direct"),
])
def test_single_pallas_call_per_strategy(strategy, n_trees, mapping):
    """hrz, dup and hyb all descend through exactly one pallas_call."""
    keys, values = make_tree_data(2047, seed=5)
    tree = T.build_tree(keys, values)
    plan = plans.make_plan(
        tree, strategy=strategy, n_trees=n_trees, mapping=mapping
    )
    q = _queries(keys, 256, seed=6)

    def run(queries):
        return plans.execute_plan(plan, queries, use_kernel=True)

    jaxpr = jax.make_jaxpr(run)(jnp.asarray(q))
    assert _count_pallas_calls(jaxpr.jaxpr) == 1, (strategy, mapping)

    ref_v, ref_f = T.search_reference(tree, jnp.asarray(q))
    v, f = run(jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(ref_v))
    np.testing.assert_array_equal(np.asarray(f), np.asarray(ref_f))


def test_kernel_engine_height13_all_strategies():
    """Every paper preset through the kernel path on a height-13 tree."""
    keys, values = make_tree_data((1 << 14) - 1, seed=9)
    tree = T.build_tree(keys, values)
    assert tree.height == 13
    q = _queries(keys, 256, seed=10)
    ref_v, ref_f = T.search_reference(tree, jnp.asarray(q))
    for name, cfg in PAPER_CONFIGS.items():
        eng = BSTEngine(keys, values, dataclasses.replace(cfg, use_kernel=True))
        v, f = eng.lookup(q)
        np.testing.assert_array_equal(np.asarray(v), np.asarray(ref_v), err_msg=name)
        np.testing.assert_array_equal(np.asarray(f), np.asarray(ref_f), err_msg=name)


@pytest.mark.parametrize("op", ["predecessor", "range_count", "range_scan"])
def test_single_pallas_call_per_ordered_op(op):
    """Every ordered op lowers through exactly one pallas_call too --
    range ops descend the concatenated lo||hi batch (DESIGN.md §6)."""
    keys, values = make_tree_data(2047, seed=5)
    tree = T.build_tree(keys, values)
    plan = plans.make_plan(tree, strategy="hyb", n_trees=4)
    q = _queries(keys, 256, seed=6)
    args = (jnp.asarray(q),)
    if op in plans.RANGE_OPS:
        args = (jnp.asarray(q), jnp.asarray(q + 64))

    def run(*a):
        return plans.ordered_query(plan, op, *a, use_kernel=True)

    jaxpr = jax.make_jaxpr(run)(*args)
    assert _count_pallas_calls(jaxpr.jaxpr) == 1, op


@pytest.mark.parametrize("height", [4, 13])
def test_ordered_kernel_matches_ordered_reference(height):
    """The kernel's ordered outputs (pred/succ ancestors, rank) are
    bit-identical to the jnp oracle at shallow and deep heights."""
    n_keys = (1 << (height + 1)) - 1
    keys, values = make_tree_data(n_keys, seed=height)
    tree = T.build_tree(keys, values)
    q = _queries(keys, 512, seed=height)
    ref = T.search_reference_ordered(tree, jnp.asarray(q))
    out = ops.bst_ordered_forest(
        tree.keys[None], tree.values[None], jnp.asarray(q)[None], height=height
    )
    for name, want, got in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(
            np.asarray(got[0]), np.asarray(want), err_msg=name
        )


def test_ordered_kernel_inactive_lanes_identity():
    """Inactive lanes report the tracking identities (merge-safe fills)."""
    keys, values = make_tree_data(511, seed=3)
    tree = T.build_tree(keys, values)
    q = _queries(keys, 128, seed=4)
    act = np.zeros(128, bool)
    out = ops.bst_ordered_forest(
        tree.keys[None],
        tree.values[None],
        jnp.asarray(q)[None],
        height=tree.height,
        active=jnp.asarray(act)[None],
    )
    val, found, pk, pv, sk, sv, rank = (np.asarray(o[0]) for o in out)
    assert not found.any()
    assert (pk == T.NO_PRED_KEY).all() and (sk == T.NO_SUCC_KEY).all()
    assert (val == T.SENTINEL_VALUE).all() and (rank == 0).all()


def test_forest_kernel_active_mask():
    """Inactive lanes can neither hit nor leak values."""
    keys, values = make_tree_data(511, seed=3)
    tree = T.build_tree(keys, values)
    q = _queries(keys, 128, seed=4)
    rng = np.random.default_rng(7)
    act = rng.integers(0, 2, size=128).astype(bool)
    v, f = ops.bst_search_forest(
        tree.keys[None],
        tree.values[None],
        jnp.asarray(q)[None],
        height=tree.height,
        active=jnp.asarray(act)[None],
    )
    ref_v, ref_f = T.search_reference(tree, jnp.asarray(q))
    np.testing.assert_array_equal(np.asarray(f[0]), np.asarray(ref_f) & act)
    np.testing.assert_array_equal(
        np.asarray(v[0])[act], np.asarray(ref_v)[act]
    )
    assert np.all(np.asarray(v[0])[~act] == T.SENTINEL_VALUE)
