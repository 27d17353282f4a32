"""Complete-BST construction and level-major (Eytzinger/BFS) layout.

The paper stores 32-bit key / 32-bit value pairs of a *complete* binary tree
level-by-level in separate BRAM partitions.  The software analogue of "one
BRAM partition per level" is the BFS (a.k.a. Eytzinger) layout: node ``i``'s
children are ``2i+1`` / ``2i+2`` and level ``l`` occupies the contiguous
slice ``[2^l - 1, 2^{l+1} - 1)``.  Each descent step then touches exactly one
contiguous region -- the property the FPGA design builds its level pipeline
on, and the property that lets the forest-batched Pallas kernel keep each
whole tree in ONE flat level-major VMEM operand (kernels/bst_search.py).

We work with *perfect* trees (n = 2^{H+1} - 1 nodes); arbitrary sorted inputs
are padded with a +inf sentinel, matching the paper's complete-tree setting
("the throughput will not change when the type of tree changes during a
stream of infinite keys").
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel key for padding to a perfect tree.  int32 max keeps compare
# semantics intact for any real int32 key strictly below it.
SENTINEL_KEY = np.int32(np.iinfo(np.int32).max)
SENTINEL_VALUE = np.int32(-1)

# Ordered-query sentinels (DESIGN.md §6): the descent tracks the last
# right-turn ancestor (largest stored key < q) and last left-turn ancestor
# (smallest stored key > q).  "No such ancestor" self-encodes as the identity
# of the max/min tracking -- int32 min for predecessors, int32 max for
# successors (the latter coincides with SENTINEL_KEY: a sentinel successor
# IS "no real successor", since sentinels pad above every real key).
NO_PRED_KEY = np.int32(np.iinfo(np.int32).min)
NO_SUCC_KEY = SENTINEL_KEY


class OrderedResult(NamedTuple):
    """Per-query outputs of one ordered compare-descend pass (DESIGN.md §6).

    value/found: the exact-match payload (SENTINEL_VALUE when absent).
    pred_key/pred_value: deepest right-turn ancestor == largest stored key
        strictly below the query (NO_PRED_KEY/SENTINEL_VALUE when none).
    succ_key/succ_value: deepest left-turn ancestor == smallest stored key
        strictly above the query (NO_SUCC_KEY/SENTINEL_VALUE when none).
    rank: number of stored keys strictly below the query -- the rank
        boundary that range_count / range_scan arithmetic builds on.
    """

    value: jax.Array
    found: jax.Array
    pred_key: jax.Array
    pred_value: jax.Array
    succ_key: jax.Array
    succ_value: jax.Array
    rank: jax.Array


def level_offset(level: int) -> int:
    """First BFS index of ``level`` (the start of its "BRAM partition")."""
    return (1 << level) - 1


def level_size(level: int) -> int:
    return 1 << level


def height_for(n_keys: int) -> int:
    """Height H of the smallest perfect tree holding ``n_keys`` nodes."""
    h = 0
    while ((1 << (h + 1)) - 1) < n_keys:
        h += 1
    return h


@dataclasses.dataclass(frozen=True)
class TreeData:
    """A perfect BST in BFS layout.

    keys/values: (n,) arrays, n = 2^{height+1} - 1, BFS order.
    n_real: number of non-sentinel entries.
    """

    keys: jax.Array
    values: jax.Array
    height: int
    n_real: int

    @property
    def n_nodes(self) -> int:
        return int(self.keys.shape[0])

    def level(self, l: int) -> Tuple[jax.Array, jax.Array]:
        """The ``l``-th "BRAM partition": (keys, values) of one tree level."""
        o, s = level_offset(l), level_size(l)
        return self.keys[o : o + s], self.values[o : o + s]

    def register_layer(self, levels: int) -> Tuple[jax.Array, jax.Array]:
        """Top ``levels`` levels flattened -- the FPGA register layer."""
        n = level_offset(levels)
        return self.keys[:n], self.values[:n]

    def subtree(self, split_level: int, index: int) -> "TreeData":
        """Vertical partition: the ``index``-th subtree rooted at ``split_level``.

        In BFS layout, subtree ``s`` owns, at global level ``l >= split_level``,
        the offsets ``p`` with ``p >> (l - split_level) == s``; locally that is
        level ``l' = l - split_level`` offset ``p' = p - s * 2^{l'}``.
        """
        sub_h = self.height - split_level
        idx = subtree_gather_indices(self.height, split_level, index)
        return TreeData(
            keys=self.keys[idx],
            values=self.values[idx],
            height=sub_h,
            n_real=int((np.asarray(self.keys[idx]) != SENTINEL_KEY).sum()),
        )


def subtree_gather_indices(height: int, split_level: int, index: int) -> np.ndarray:
    """Global BFS indices of subtree ``index`` rooted at ``split_level``."""
    out = []
    for l_local in range(height - split_level + 1):
        l = split_level + l_local
        p = index * (1 << l_local) + np.arange(1 << l_local)
        out.append(level_offset(l) + p)
    return np.concatenate(out)


def all_subtree_gather_indices(height: int, split_level: int) -> np.ndarray:
    """(n_subtrees, subtree_nodes) gather map for every vertical partition."""
    n_sub = 1 << split_level
    return np.stack(
        [subtree_gather_indices(height, split_level, s) for s in range(n_sub)]
    )


def eytzinger_from_sorted(
    sorted_keys: np.ndarray, sorted_values: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Lay out sorted key/value pairs as a perfect BFS tree (vectorized).

    For a perfect tree of height H, the node at level ``l`` offset ``p`` has
    in-order rank ``(2p + 1) * 2^{H-l} - 1``; inverting that map assigns each
    sorted element its BFS slot without recursion.
    """
    sorted_keys = np.asarray(sorted_keys)
    sorted_values = np.asarray(sorted_values)
    if sorted_keys.ndim != 1 or sorted_keys.shape != sorted_values.shape:
        raise ValueError("keys/values must be equal-length 1-D arrays")
    if sorted_keys.size == 0:
        raise ValueError("empty tree")
    if not np.all(sorted_keys[:-1] < sorted_keys[1:]):
        raise ValueError("keys must be strictly increasing")

    n_real = sorted_keys.size
    H = height_for(n_real)
    n = (1 << (H + 1)) - 1

    padded_keys = np.full(n, SENTINEL_KEY, dtype=np.int32)
    padded_vals = np.full(n, SENTINEL_VALUE, dtype=np.int32)
    padded_keys[:n_real] = sorted_keys.astype(np.int32)
    padded_vals[:n_real] = sorted_values.astype(np.int32)
    # Sentinel keys must stay the largest: they land in the right-most
    # in-order ranks automatically because SENTINEL_KEY > every real key.

    bfs_keys = np.empty(n, dtype=np.int32)
    bfs_vals = np.empty(n, dtype=np.int32)
    for l in range(H + 1):
        p = np.arange(1 << l)
        rank = (2 * p + 1) * (1 << (H - l)) - 1
        o = level_offset(l)
        bfs_keys[o : o + (1 << l)] = padded_keys[rank]
        bfs_vals[o : o + (1 << l)] = padded_vals[rank]
    return bfs_keys, bfs_vals, H, n_real


def build_tree(keys: np.ndarray, values: np.ndarray, host: bool = False) -> TreeData:
    """Build a TreeData from (unsorted) unique keys + values.

    ``host=True`` leaves the arrays in host memory (NumPy): a sharded
    server places only its shards on the devices (DESIGN.md §9)."""
    keys = np.asarray(keys, dtype=np.int32)
    values = np.asarray(values, dtype=np.int32)
    order = np.argsort(keys, kind="stable")
    k, v, h, n_real = eytzinger_from_sorted(keys[order], values[order])
    if not host:
        k, v = jnp.asarray(k), jnp.asarray(v)
    return TreeData(keys=k, values=v, height=h, n_real=n_real)


def search_reference(tree: TreeData, queries: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Pure-jnp oracle: batched BST descent in BFS layout.

    Returns (values, found).  Not-found queries get SENTINEL_VALUE.
    """
    n = tree.n_nodes

    def step(carry, _):
        idx, val, found = carry
        node_key = tree.keys[idx]
        node_val = tree.values[idx]
        hit = (node_key == queries) & ~found
        val = jnp.where(hit, node_val, val)
        found = found | hit
        go_right = queries > node_key
        nxt = 2 * idx + 1 + go_right.astype(idx.dtype)
        idx = jnp.where(found, idx, jnp.minimum(nxt, n - 1))
        return (idx, val, found), None

    B = queries.shape[0]
    init = (
        jnp.zeros((B,), dtype=jnp.int32),
        jnp.full((B,), SENTINEL_VALUE, dtype=jnp.int32),
        jnp.zeros((B,), dtype=bool),
    )
    (idx, val, found), _ = jax.lax.scan(step, init, None, length=tree.height + 1)
    del idx
    return val, found


def left_subtree_sizes(height: int) -> np.ndarray:
    """Per-level left-subtree size ``2^{H-l} - 1`` of a height-``H`` tree.

    The ordered descent's rank arithmetic: taking the right branch at level
    ``l`` skips the node plus its entire left subtree -- ``2^{H-l}`` keys,
    all real whenever the node itself is real (sentinels pad only the top
    in-order ranks, so a real node's left subtree never contains one).
    """
    levels = np.arange(height + 1)
    return ((1 << (height - levels)) - 1).astype(np.int32)


@functools.lru_cache(maxsize=None)
def rank_to_bfs_indices(height: int) -> np.ndarray:
    """BFS index of every in-order rank (the sorted view of the layout).

    Inverts ``rank = (2p + 1) * 2^{H-l} - 1``: with ``t = rank + 1``, the
    number of trailing zero bits of ``t`` is ``H - l`` and the remaining odd
    factor is ``2p + 1``.  range_scan gathers consecutive ranks through this
    map instead of re-sorting (DESIGN.md §6).  Memoized per height (callers
    treat the array as read-only): compaction runs in the serving steady
    state and must not rebuild O(n) host maps per swap.
    """
    n = (1 << (height + 1)) - 1
    t = np.arange(1, n + 1, dtype=np.int64)
    z = np.log2(t & -t).astype(np.int64)  # trailing zeros, exact for 2^k
    level = height - z
    offset = ((t >> z) - 1) >> 1
    return (((1 << level) - 1) + offset).astype(np.int32)


@functools.lru_cache(maxsize=None)
def bfs_inorder_ranks(height: int) -> np.ndarray:
    """In-order rank of every BFS index (inverse of ``rank_to_bfs_indices``).

    The node at level ``l`` offset ``p`` has rank ``(2p + 1) * 2^{H-l} - 1``.
    Gathering a sorted array through this map IS the Eytzinger layout step --
    the device-side re-layout that ``layout_from_sorted_device`` (and the
    delta-compaction path, DESIGN.md §7) runs under ``jit``.  Memoized per
    height like its inverse (read-only contract).
    """
    n = (1 << (height + 1)) - 1
    out = np.empty(n, dtype=np.int32)
    for l in range(height + 1):
        p = np.arange(1 << l)
        o = level_offset(l)
        out[o : o + (1 << l)] = (2 * p + 1) * (1 << (height - l)) - 1
    return out


def layout_from_sorted_device(
    sorted_keys: jax.Array, sorted_values: jax.Array, n_real: int
) -> TreeData:
    """Build a TreeData from a DEVICE-resident sorted view (one gather).

    ``sorted_keys/values`` hold ``n_real`` real pairs in ascending key order
    followed by sentinel padding (any length >= n_real).  The perfect-tree
    height is derived from ``n_real`` (a host int -- the one scalar the
    delta write path syncs per compaction, DESIGN.md §7); the BFS image is a
    single gather through ``bfs_inorder_ranks``, so the arrays never leave
    the device.
    """
    if n_real < 1:
        raise ValueError("empty tree")
    h = height_for(n_real)
    n = (1 << (h + 1)) - 1
    pad = n - int(sorted_keys.shape[0])
    if pad > 0:
        sorted_keys = jnp.concatenate(
            [sorted_keys, jnp.full((pad,), SENTINEL_KEY, jnp.int32)]
        )
        sorted_values = jnp.concatenate(
            [sorted_values, jnp.full((pad,), SENTINEL_VALUE, jnp.int32)]
        )
    ranks = jnp.asarray(bfs_inorder_ranks(h))
    return TreeData(
        keys=sorted_keys[:n][ranks],
        values=sorted_values[:n][ranks],
        height=h,
        n_real=n_real,
    )


def _ordered_step(keys, values, queries, active, idx_clamp):
    """One ordered compare-descend scan step over BFS-layout operands.

    The single implementation behind both tree-level jnp descents (full
    reference, register-layer route); the independent twin lives in
    ``kernels/ref.bst_ordered_ref`` (deliberately separate ground truth for
    the kernel property tests).  ``idx_clamp`` bounds the child index for
    full-tree descents; the register route leaves it None because the final
    index must step past the register block to name the subtree.
    """

    def step(carry, left):
        idx, r = carry
        nk = keys[idx]
        nv = values[idx]
        live = ~r.found if active is None else active & ~r.found
        hit = (nk == queries) & live
        go_right = live & ~hit & (queries > nk)
        go_left = live & ~hit & (queries < nk)
        r = OrderedResult(
            value=jnp.where(hit, nv, r.value),
            found=r.found | hit,
            pred_key=jnp.where(go_right, nk, r.pred_key),
            pred_value=jnp.where(go_right, nv, r.pred_value),
            succ_key=jnp.where(go_left, nk, r.succ_key),
            succ_value=jnp.where(go_left, nv, r.succ_value),
            rank=r.rank
            + jnp.where(go_right, left + 1, 0)
            + jnp.where(hit, left, 0),
        )
        nxt = 2 * idx + 1 + go_right.astype(idx.dtype)
        if idx_clamp is not None:
            nxt = jnp.minimum(nxt, idx_clamp)
        frozen = r.found if active is None else r.found | ~active
        idx = jnp.where(frozen, idx, nxt)
        return (idx, r), None

    return step


def search_reference_ordered(
    tree: TreeData, queries: jax.Array, active: jax.Array | None = None
) -> OrderedResult:
    """Pure-jnp oracle for the ordered descent (DESIGN.md §6).

    One root-to-leaf pass per query yields the exact-match payload PLUS the
    strict predecessor/successor ancestors and the query's rank boundary.
    Bit-identical to the forest kernel's ordered outputs (property-tested).
    Queries must be real keys, i.e. strictly inside
    (NO_PRED_KEY, SENTINEL_KEY).
    """
    B = queries.shape[0]
    if active is None:
        active = jnp.ones((B,), dtype=bool)
    left_sizes = jnp.asarray(left_subtree_sizes(tree.height))
    step = _ordered_step(tree.keys, tree.values, queries, active, tree.n_nodes - 1)
    init = (jnp.zeros((B,), jnp.int32), init_ordered(B))
    (_, res), _ = jax.lax.scan(step, init, left_sizes)
    return res


def init_ordered(B: int) -> OrderedResult:
    """The ordered descent's identity state (also the inactive-lane output)."""
    return OrderedResult(
        value=jnp.full((B,), SENTINEL_VALUE, jnp.int32),
        found=jnp.zeros((B,), bool),
        pred_key=jnp.full((B,), NO_PRED_KEY, jnp.int32),
        pred_value=jnp.full((B,), SENTINEL_VALUE, jnp.int32),
        succ_key=jnp.full((B,), NO_SUCC_KEY, jnp.int32),
        succ_value=jnp.full((B,), SENTINEL_VALUE, jnp.int32),
        rank=jnp.zeros((B,), jnp.int32),
    )


def register_layer_route(
    tree: TreeData, queries: jax.Array, register_levels: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Descend the register layer only; route survivors to subtrees.

    Returns (subtree_id, value, found):
      * found=True  -> key matched inside the register layer, value valid.
      * found=False -> subtree_id in [0, 2^register_levels) names the vertical
        partition in which the descent must continue (paper Fig. 3).
    """
    if register_levels < 1:
        raise ValueError("need at least one register level (the root)")

    def step(carry, _):
        idx, val, found = carry
        node_key = tree.keys[idx]
        node_val = tree.values[idx]
        hit = (node_key == queries) & ~found
        val = jnp.where(hit, node_val, val)
        found = found | hit
        go_right = queries > node_key
        nxt = 2 * idx + 1 + go_right.astype(idx.dtype)
        idx = jnp.where(found, idx, nxt)
        return (idx, val, found), None

    B = queries.shape[0]
    init = (
        jnp.zeros((B,), dtype=jnp.int32),
        jnp.full((B,), SENTINEL_VALUE, dtype=jnp.int32),
        jnp.zeros((B,), dtype=bool),
    )
    (idx, val, found), _ = jax.lax.scan(step, init, None, length=register_levels)
    # After `register_levels` steps, a live key's idx is a BFS index at level
    # `register_levels`; its offset there *is* the subtree id.
    subtree_id = jnp.clip(idx - level_offset(register_levels), 0, None)
    subtree_id = jnp.where(found, -1, subtree_id).astype(jnp.int32)
    return subtree_id, val, found


def register_layer_route_ordered(
    tree: TreeData, queries: jax.Array, register_levels: int, full_height: int
) -> Tuple[jax.Array, OrderedResult]:
    """Ordered variant of ``register_layer_route`` (DESIGN.md §6).

    Returns (subtree_id, partial OrderedResult): the register layer's
    contribution to predecessor/successor tracking and rank arithmetic.
    Rank contributions use ``full_height`` left-subtree sizes -- the register
    layer is a prefix of the FULL tree, so a right turn at global level ``l``
    skips ``2^{full_height - l}`` keys regardless of where the subtree split
    sits.  The subtree descent's local rank then simply adds on.
    """
    if register_levels < 1:
        raise ValueError("need at least one register level (the root)")
    B = queries.shape[0]
    left_sizes = jnp.asarray(left_subtree_sizes(full_height)[:register_levels])
    step = _ordered_step(tree.keys, tree.values, queries, None, None)
    init = (jnp.zeros((B,), jnp.int32), init_ordered(B))
    (idx, res), _ = jax.lax.scan(step, init, left_sizes)
    subtree_id = jnp.clip(idx - level_offset(register_levels), 0, None)
    subtree_id = jnp.where(res.found, -1, subtree_id).astype(jnp.int32)
    return subtree_id, res


def subtree_search(
    sub_keys: jax.Array,
    sub_values: jax.Array,
    sub_height: int,
    queries: jax.Array,
    active: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Descend one vertical partition (local BFS layout).

    ``active`` masks padded/irrelevant slots so they cannot fake a hit.
    """
    n = sub_keys.shape[0]

    def step(carry, _):
        idx, val, found = carry
        node_key = sub_keys[idx]
        node_val = sub_values[idx]
        hit = (node_key == queries) & ~found & active
        val = jnp.where(hit, node_val, val)
        found = found | hit
        go_right = queries > node_key
        nxt = 2 * idx + 1 + go_right.astype(idx.dtype)
        idx = jnp.where(found, idx, jnp.minimum(nxt, n - 1))
        return (idx, val, found), None

    B = queries.shape[0]
    init = (
        jnp.zeros((B,), dtype=jnp.int32),
        jnp.full((B,), SENTINEL_VALUE, dtype=jnp.int32),
        jnp.zeros((B,), dtype=bool),
    )
    (_, val, found), _ = jax.lax.scan(step, init, None, length=sub_height + 1)
    return val, found & active
