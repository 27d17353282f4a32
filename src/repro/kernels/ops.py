"""Jitted public wrappers around the Pallas kernels (the ``ops.py`` contract).

The BST ops run their Pallas kernel compiled with Mosaic on a TPU and in
the Pallas interpreter on any other backend (``interpret_mode``), or the
jnp oracle in ``ref.py`` with ``use_ref=True``.  Nothing falls back
silently: a kernel that does not compile for the TPU raises.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bst_search import (
    bst_hybrid_forest_pallas,
    bst_ordered_forest_pallas,
    bst_search_forest_pallas,
    bst_search_pallas,
)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.queue_dispatch import queue_dispatch_pallas


def interpret_mode() -> bool:
    """Whether the BST kernels run in the Pallas interpreter: on every
    backend but the TPU, whose compiled Mosaic kernel is the served path."""
    return jax.default_backend() != "tpu"


def _merge_delta(out, delta, queries, active):
    """Fold the write buffer (DESIGN.md §7) into either path's descent:
    ``delta-hit > tombstone > tree-hit`` on value/found, merged rank."""
    if delta is None:
        return out
    hit, dead, d_val, wb = ref.bst_delta_resolve_ref(*delta, queries, active)
    return ref.merge_delta_resolution(out, hit, dead, d_val, wb)


def _forest_ref(oracle, forest_keys, forest_values, queries, height, active, shared_tree):
    """vmap a single-tree oracle over the forest rows (dup shares row 0)."""
    T = queries.shape[0]
    if shared_tree:
        forest_keys = jnp.broadcast_to(forest_keys, (T,) + forest_keys.shape[1:])
        forest_values = jnp.broadcast_to(forest_values, (T,) + forest_values.shape[1:])
    if active is None:
        active = jnp.ones(queries.shape, bool)
    return jax.vmap(lambda k, v, q, a: oracle(k, v, q, height, a))(
        forest_keys, forest_values, queries, active
    )


@functools.partial(
    jax.jit, static_argnames=("height", "block_q", "shared_tree", "use_ref")
)
def bst_search_forest(
    forest_keys: jax.Array,
    forest_values: jax.Array,
    queries: jax.Array,
    height: int,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    shared_tree: bool = False,
    use_ref: bool = False,
    delta: Optional[Tuple[jax.Array, ...]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Forest-batched search: (n_trees, B) queries over (n_rows, n) flat trees.

    The single entry point behind every engine strategy (DESIGN.md §2): hrz
    is a forest of one, dup shares one tree row across grid rows, hyb gives
    each vertical subtree its own row.  One ``pallas_call`` for all three.
    ``delta`` optionally folds the write buffer's four flat operands
    (DESIGN.md §7) into either path; value/found come back merged.
    """
    if use_ref:
        out = _forest_ref(
            ref.bst_search_ref, forest_keys, forest_values, queries, height,
            active, shared_tree,
        )
    else:
        out = bst_search_forest_pallas(
            forest_keys,
            forest_values,
            queries,
            height,
            active=active,
            block_q=block_q,
            interpret=interpret_mode(),
            shared_tree=shared_tree,
        )
    return _merge_delta(out, delta, queries, active)


@functools.partial(
    jax.jit, static_argnames=("height", "block_q", "shared_tree", "use_ref")
)
def bst_ordered_forest(
    forest_keys: jax.Array,
    forest_values: jax.Array,
    queries: jax.Array,
    height: int,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    shared_tree: bool = False,
    use_ref: bool = False,
    delta: Optional[Tuple[jax.Array, ...]] = None,
) -> Tuple[jax.Array, ...]:
    """Forest-batched ORDERED search (DESIGN.md §6): one pass per query
    yields ``(values, found, pred_keys, pred_values, succ_keys,
    succ_values, rank)``, each (n_trees, B).

    The single descent behind every ordered query op (predecessor,
    successor, range_count, range_scan) for every strategy -- same
    forest-batching contract as ``bst_search_forest``, same one
    ``pallas_call`` lowering.  ``delta`` folds the write buffer (DESIGN.md
    §7): value/found/rank come back merged against the pending
    upserts/tombstones; pred/succ stay tree-local (``core/delta.py``).
    """
    if use_ref:
        out = _forest_ref(
            ref.bst_ordered_ref, forest_keys, forest_values, queries, height,
            active, shared_tree,
        )
    else:
        out = bst_ordered_forest_pallas(
            forest_keys,
            forest_values,
            queries,
            height,
            active=active,
            block_q=block_q,
            interpret=interpret_mode(),
            shared_tree=shared_tree,
        )
    return _merge_delta(out, delta, queries, active)


@functools.partial(
    jax.jit,
    static_argnames=(
        "height",
        "split_level",
        "mapping",
        "capacity",
        "block_q",
        "ordered",
        "use_ref",
    ),
)
def bst_hybrid_forest(
    tree_keys: jax.Array,
    tree_values: jax.Array,
    queries: jax.Array,
    height: int,
    split_level: int,
    mapping: str = "queue",
    capacity: int = 1,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    ordered: bool = True,
    use_ref: bool = False,
    delta: Optional[Tuple[jax.Array, ...]] = None,
) -> Tuple[jax.Array, ...]:
    """The hybrid strategy's single entry point (DESIGN.md §8): register
    route, queue/direct dispatch, vertical-subtree descent and stall-round
    replay, all in ONE ``pallas_call`` -- or the structurally matching jnp
    oracle with ``use_ref=True``.  Operands are the (n,) flat FULL tree and
    a (B,) query batch; outputs are (B,) in the §6 ordered contract
    (``(values, found)`` with ``ordered=False``).

    ``capacity`` is the per-subtree dispatch-buffer depth per chunk: the
    kernel dispatches each ``block_q`` chunk independently (the FPGA
    streams chunks), the oracle treats the whole batch as one chunk (the
    retired driver's granularity) -- results are identical either way,
    which is exactly the stall round's contract.  ``delta`` folds the
    write buffer on both paths; value/found/rank come back merged.
    """
    if use_ref:
        out = ref.bst_hybrid_ref(
            tree_keys,
            tree_values,
            queries,
            height,
            split_level,
            mapping,
            capacity,
            active=active,
            ordered=ordered,
        )
    else:
        out = bst_hybrid_forest_pallas(
            tree_keys,
            tree_values,
            queries,
            height,
            split_level,
            mapping=mapping,
            capacity=capacity,
            active=active,
            block_q=block_q,
            interpret=interpret_mode(),
            ordered=ordered,
        )
    return _merge_delta(out, delta, queries, active)


@functools.partial(jax.jit, static_argnames=("height", "block_q", "use_ref"))
def bst_search(
    tree_keys: jax.Array,
    tree_values: jax.Array,
    queries: jax.Array,
    height: int,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    use_ref: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    if use_ref:
        return ref.bst_search_ref(tree_keys, tree_values, queries, height, active)
    return bst_search_pallas(
        tree_keys,
        tree_values,
        queries,
        height,
        active=active,
        block_q=block_q,
        interpret=interpret_mode(),
    )


@jax.jit
def bst_delta_resolve(
    delta_keys: jax.Array,
    delta_values: jax.Array,
    delta_tombstone: jax.Array,
    delta_weight: jax.Array,
    queries: jax.Array,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Delta-buffer resolution over the four flat operands (DESIGN.md §7).

    Per-query ``(hit, dead, value, weight_below)`` against the sorted write
    buffer -- the same math the forest kernels apply in-``pallas_call``
    when the buffer rides as an operand.  Public so drivers whose descent
    the kernel cannot absorb (the sharded shard_map programs, DESIGN.md
    §9) fold the REPLICATED buffer on-device through the one contract
    entry point instead of reaching into ``kernels/ref``.  ``active``
    masks lanes whose resolution must not contribute (padding, unplaced
    stall lanes): their hit drops and their rank correction zeroes.
    """
    hit, dead, value, wbelow = ref.bst_delta_resolve_ref(
        delta_keys, delta_values, delta_tombstone, delta_weight, queries
    )
    if active is not None:
        hit = hit & active
        wbelow = jnp.where(active, wbelow, 0)
    return hit, dead, value, wbelow


@functools.partial(
    jax.jit, static_argnames=("n_dest", "capacity", "interpret", "use_ref")
)
def queue_dispatch(
    dest: jax.Array,
    n_dest: int,
    capacity: int,
    interpret: bool = True,
    use_ref: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    if use_ref:
        return ref.queue_dispatch_ref(dest, n_dest, capacity)
    return queue_dispatch_pallas(dest, n_dest, capacity, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "scale",
        "block_q",
        "block_k",
        "interpret",
        "use_ref",
    ),
)
def flash_attention(
    q: jax.Array,  # (BH, Sq, d)
    k: jax.Array,  # (BHkv, Skv, d)
    v: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
    use_ref: bool = False,
) -> jax.Array:
    if use_ref:
        group = q.shape[0] // k.shape[0]
        kk = jnp.repeat(k, group, axis=0)
        vv = jnp.repeat(v, group, axis=0)
        return jax.vmap(
            lambda qq, kx, vx: ref.mha_attention_ref(
                qq, kx, vx, causal=causal, window=window, scale=scale
            )
        )(q, kk, vv)
    return flash_attention_pallas(
        q,
        k,
        v,
        causal=causal,
        window=window,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
