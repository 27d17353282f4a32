"""Device-side delta buffer: the live write path (DESIGN.md §7).

The paper defers Insert/Delete, and until now the repo's only rendition was
the host-side O(n + m) full rebuild between snapshot swaps -- fine for
read-mostly streams, fatal for continuous writes.  This module is the
LSM-style fix, shaped after the level-wise batch-maintenance idiom (see
PAPERS.md): pending upserts and tombstones accumulate in a small sorted
**delta buffer** that is searched in the same pass as the main tree, and a
bulk **compaction** merges the buffer into a fresh perfect snapshot when it
crosses a high-water mark.  The deeply pipelined search datapath of the
source paper stays untouched -- the buffer simply rides the forest
``pallas_call`` as one extra (tiny) operand, like the register layer does,
for EVERY single-chip strategy (hyb resolves it inside the same kernel
pass as its dispatch/replay pipeline, DESIGN.md §8).

Entry resolution per query: ``delta-hit > tombstone > tree-hit``.  Each
entry records, at ingest time, whether its key exists in the backing
snapshot (``in_tree``) and the key's tree rank -- both fall out of one
ordered descent over the immutable snapshot, so writes ride the same
datapath reads do.  From those two bits every entry gets a signed **rank
weight**

    w = +1  upsert of a new key        (grows the key set)
    w =  0  upsert of an existing key  (value override only)
    w = -1  tombstone of a stored key  (shrinks the key set)
    w =  0  tombstone of an absent key (no-op, kept only to shadow
                                        earlier buffered upserts)

and the merged rank of any query is ``tree_rank(q) + sum of weights of
entries with key < q`` -- exact, associative, and computable per lane with
one broadcast compare against the sorted buffer.  Ordered epilogues
(predecessor / successor / range_scan) then *select by merged rank*
(``select_merged``): the element at merged rank ``j`` is either a live
delta entry whose own merged rank is ``j``, or a tree key inside one of the
C + 1 gaps between consecutive delta keys, at tree rank ``j`` minus that
gap's weight prefix.  Tombstoned tree keys coincide with buffer keys, i.e.
gap *boundaries*, so the strict-gap test excludes them for free.

Everything here is pure jnp with static shapes (buffer capacity and batch
sizes are compile-time constants), so ingest, search and compaction all run
under ``jit`` -- updates never leave the device.  The single host sync in
the whole write path is the new key count read at compaction time, needed
to pick the next snapshot's (static) perfect-tree height.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import runtime as analysis_runtime
from repro.core import tree as tree_lib
from repro.core.tree import OrderedResult, TreeData
from repro.kernels import ops as kops


class DeltaBuffer(NamedTuple):
    """Fixed-capacity sorted buffer of pending upserts and tombstones.

    keys:      (C,) int32, ascending; SENTINEL_KEY marks empty slots (they
               self-sort to the tail, exactly like tree padding).
    values:    (C,) int32 upsert payloads (ignored for tombstones).
    tombstone: (C,) bool -- entry deletes its key instead of upserting it.
    in_tree:   (C,) bool -- key exists in the backing snapshot (frozen at
               ingest; the snapshot is immutable until compaction).
    tree_rank: (C,) int32 -- snapshot rank of the key at ingest time.
    count:     () int32 -- live entries (device scalar; the engine tracks a
               host-side upper bound so the hot path never syncs it).
    """

    keys: jax.Array
    values: jax.Array
    tombstone: jax.Array
    in_tree: jax.Array
    tree_rank: jax.Array
    count: jax.Array

    @property
    def capacity(self) -> int:
        return int(self.keys.shape[0])


def empty(capacity: int) -> DeltaBuffer:
    """A fresh all-sentinel buffer of ``capacity`` slots."""
    if capacity < 1:
        raise ValueError("delta capacity must be >= 1")
    return DeltaBuffer(
        keys=jnp.full((capacity,), tree_lib.SENTINEL_KEY, jnp.int32),
        values=jnp.full((capacity,), tree_lib.SENTINEL_VALUE, jnp.int32),
        tombstone=jnp.zeros((capacity,), bool),
        in_tree=jnp.zeros((capacity,), bool),
        tree_rank=jnp.zeros((capacity,), jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


def weights(delta: DeltaBuffer) -> jax.Array:
    """Per-entry signed rank weight (see module doc); 0 for empty slots."""
    live = delta.keys != tree_lib.SENTINEL_KEY
    w = jnp.where(
        delta.in_tree,
        jnp.where(delta.tombstone, -1, 0),
        jnp.where(delta.tombstone, 0, 1),
    )
    return jnp.where(live, w, 0).astype(jnp.int32)


def net_keys(delta: DeltaBuffer) -> jax.Array:
    """Net change to the stored-key count once the buffer lands (() int32)."""
    return jnp.sum(weights(delta))


def operands(delta: DeltaBuffer) -> Tuple[jax.Array, ...]:
    """The four flat int32 arrays the kernel rides as extra operands:
    (keys, values, tombstone, weight)."""
    return (
        delta.keys,
        delta.values,
        delta.tombstone.astype(jnp.int32),
        weights(delta),
    )


# ------------------------------------------------------------------- ingest
def ingest(
    delta: DeltaBuffer,
    new_keys: jax.Array,
    new_values: jax.Array,
    new_deletes: jax.Array,
    new_valid: jax.Array,
    new_in_tree: jax.Array,
    new_tree_rank: jax.Array,
) -> DeltaBuffer:
    """Merge a batch of write ops (submission order, last-wins) into the
    buffer.  Pure jnp, static shapes, jit-safe.

    The batch arrives in submission order; a stable sort of
    ``old-entries || batch`` keyed on the key puts, for every duplicated
    key, the buffer's old entry first and batch occurrences in submission
    order -- so keeping the LAST occurrence per key is exactly the
    last-write-wins contract.  ``new_valid`` masks padding lanes (the
    server pads write chunks to a fixed jit shape).  The caller guarantees
    the merged live count fits the capacity (the engine compacts first
    otherwise); entries are never silently dropped.
    """
    C = delta.keys.shape[0]
    m = new_keys.shape[0]
    nk = jnp.where(new_valid, new_keys, tree_lib.SENTINEL_KEY).astype(jnp.int32)
    keys_cat = jnp.concatenate([delta.keys, nk])
    vals_cat = jnp.concatenate([delta.values, new_values.astype(jnp.int32)])
    tomb_cat = jnp.concatenate([delta.tombstone, new_deletes.astype(bool)])
    intree_cat = jnp.concatenate([delta.in_tree, new_in_tree.astype(bool)])
    rank_cat = jnp.concatenate([delta.tree_rank, new_tree_rank.astype(jnp.int32)])

    order = jnp.argsort(keys_cat, stable=True)
    k = keys_cat[order]
    # last occurrence per key wins; sentinels (padding / empty slots) drop
    keep = (k != tree_lib.SENTINEL_KEY) & jnp.concatenate(
        [k[:-1] != k[1:], jnp.ones((1,), bool)]
    )
    pos = jnp.cumsum(keep) - keep  # target slot among kept entries
    sink = C + m
    pos = jnp.where(keep, pos, sink).astype(jnp.int32)

    def place(src, fill, dtype):
        out = jnp.full((sink + 1,), fill, dtype)
        return out.at[pos].set(src[order].astype(dtype), mode="drop")[:C]

    return DeltaBuffer(
        keys=place(keys_cat, tree_lib.SENTINEL_KEY, jnp.int32),
        values=place(vals_cat, tree_lib.SENTINEL_VALUE, jnp.int32),
        tombstone=place(tomb_cat, False, bool),
        in_tree=place(intree_cat, False, bool),
        tree_rank=place(rank_cat, 0, jnp.int32),
        count=jnp.minimum(jnp.sum(keep), C).astype(jnp.int32),
    )


# ------------------------------------------------------------------ resolve
def resolve_operands(
    delta_ops: Tuple[jax.Array, ...],
    queries: jax.Array,
    active: jax.Array | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``resolve`` over the four flat kernel operands (see ``operands``).

    This is the shard_map-friendly spelling: inside a sharded program the
    replicated buffer exists only as plain arrays (DESIGN.md §9 folds it
    on-device, per chip, against the local query slice), so the resolution
    cannot take the NamedTuple.  Same math as the in-``pallas_call``
    resolution, property-tested bit-identical.
    """
    return kops.bst_delta_resolve(*delta_ops, queries, active)


def resolve(
    delta: DeltaBuffer, queries: jax.Array, active: jax.Array | None = None
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-query buffer search: (hit, dead, value, weight_below).

    The jnp rendition of what the forest kernel computes in-``pallas_call``
    when the buffer rides as an operand (same math, property-tested
    bit-identical).  Every single-chip strategy -- hyb included since
    DESIGN.md §8 -- resolves in-kernel; the sharded drivers resolve the
    replicated operands inside their shard_map programs
    (``resolve_operands``), so no driver-level twin remains anywhere.
    """
    return resolve_operands(operands(delta), queries, active)


def merge_lookup(
    value: jax.Array,
    found: jax.Array,
    hit: jax.Array,
    dead: jax.Array,
    delta_value: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """delta-hit > tombstone > tree-hit, membership configuration."""
    return (
        jnp.where(hit, jnp.where(dead, tree_lib.SENTINEL_VALUE, delta_value), value),
        jnp.where(hit, ~dead, found),
    )


def merge_ordered(
    res: OrderedResult,
    hit: jax.Array,
    dead: jax.Array,
    delta_value: jax.Array,
    weight_below: jax.Array,
) -> OrderedResult:
    """Fold a buffer resolution into a tree ``OrderedResult``.

    value/found resolve ``delta-hit > tombstone > tree-hit``; the rank
    gains the signed weight of buffer entries below the query (the merged
    rank is then exact).  pred/succ fields stay tree-local -- the exact
    merged floor/ceiling comes from rank selection (``point_epilogue``),
    because a tombstone can kill the tree's tracked ancestor.
    """
    value, found = merge_lookup(res.value, res.found, hit, dead, delta_value)
    return res._replace(value=value, found=found, rank=res.rank + weight_below)


# ---------------------------------------------------------------- selection
def select_merged(
    sorted_keys: jax.Array,
    sorted_values: jax.Array,
    n_real: int,
    delta: DeltaBuffer,
    j: jax.Array,
    valid: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The live key/value at merged in-order rank ``j`` (exact).

    Two disjoint cases (see module doc): the element is a live buffer
    upsert whose merged rank ``tree_rank + exclusive-weight-prefix`` equals
    ``j``, or a tree key strictly inside one of the C + 1 gaps between
    consecutive buffer keys, at tree rank ``j - W_gap``.  Tombstoned and
    overwritten tree keys sit ON gap boundaries, so the strict inequality
    excludes them; overwrites are found through their buffer entry instead.
    The tree keys of gap ``g`` hold consecutive merged ranks from
    ``m_g = (tree keys <= its lower boundary) + W_gap``; ``m`` never
    decreases, so only the last gap with ``m_g <= j`` can hold rank ``j``
    and each lane reads one tree key, not one per gap.
    ``j``/``valid`` broadcast over any batch shape; returns (keys, values,
    ok) where ``ok`` is False only for masked or out-of-range lanes.
    """
    w = weights(delta)
    live = delta.keys != tree_lib.SENTINEL_KEY
    present = live & ~delta.tombstone
    w_inc = jnp.cumsum(w)
    entry_rank = delta.tree_rank + (w_inc - w)  # exclusive prefix

    jj = j[..., None]
    vv = valid[..., None]
    hit_e = present & (entry_rank == jj) & vv
    from_delta = jnp.any(hit_e, axis=-1)
    d_key = jnp.sum(jnp.where(hit_e, delta.keys, 0), axis=-1)
    d_val = jnp.sum(jnp.where(hit_e, delta.values, 0), axis=-1)

    zero = jnp.zeros((1,), jnp.int32)
    w_gap = jnp.concatenate([zero, w_inc])  # (C+1,) weight prefix per gap
    lo_b = jnp.concatenate([jnp.full((1,), tree_lib.NO_PRED_KEY), delta.keys])
    hi_b = jnp.concatenate([delta.keys, jnp.full((1,), tree_lib.SENTINEL_KEY)])
    first_rank = jnp.minimum(jnp.searchsorted(sorted_keys, lo_b, side="right"), n_real)
    gap_start = first_rank + w_gap  # (C+1,) merged rank of each gap's first tree key
    gap = jnp.clip(jnp.sum((gap_start <= jj).astype(jnp.int32), axis=-1) - 1, 0, w_gap.shape[0] - 1)
    s = j - w_gap[gap]  # candidate tree rank
    s_ok = (s >= 0) & (s < n_real) & valid
    safe = jnp.clip(s, 0, sorted_keys.shape[0] - 1)
    t_key = sorted_keys[safe]
    from_tree = s_ok & (t_key > lo_b[gap]) & (t_key < hi_b[gap])
    t_k = jnp.where(from_tree, t_key, 0)
    t_v = jnp.where(from_tree, sorted_values[safe], 0)

    ok = from_delta | from_tree
    key = jnp.where(from_delta, d_key, t_k)
    val = jnp.where(from_delta, d_val, t_v)
    return key, val, ok


def point_epilogue(
    op: str,
    queries: jax.Array,
    res: OrderedResult,
    sorted_keys: jax.Array,
    sorted_values: jax.Array,
    n_real: int,
    delta: DeltaBuffer,
):
    """Delta-aware twin of ``plans.point_epilogue`` (same op contract).

    ``res`` carries MERGED found/value/rank (``merge_ordered`` ran, in the
    kernel or the driver); floor/ceiling resolve by rank selection, which
    is exact even when tombstones kill the tree's tracked ancestors.  With
    an empty buffer every branch degenerates to the classic answers.
    """
    if op == "lookup":
        return res.value, res.found
    if op == "predecessor":
        need = ~res.found & (res.rank > 0)
        k, v, sel_ok = select_merged(
            sorted_keys, sorted_values, n_real, delta, res.rank - 1, need
        )
        got = need & sel_ok
        keys = jnp.where(res.found, queries, jnp.where(got, k, tree_lib.NO_PRED_KEY))
        values = jnp.where(
            res.found, res.value, jnp.where(got, v, tree_lib.SENTINEL_VALUE)
        )
        return keys, values, res.found | got
    # successor: ceiling(q) = the element at the query's own merged rank.
    total = n_real + net_keys(delta)
    need = ~res.found & (res.rank < total)
    k, v, sel_ok = select_merged(
        sorted_keys, sorted_values, n_real, delta, res.rank, need
    )
    got = need & sel_ok
    keys = jnp.where(res.found, queries, jnp.where(got, k, tree_lib.NO_SUCC_KEY))
    values = jnp.where(
        res.found, res.value, jnp.where(got, v, tree_lib.SENTINEL_VALUE)
    )
    return keys, values, res.found | got


def range_epilogue(
    op: str,
    sorted_keys: jax.Array,
    sorted_values: jax.Array,
    n_real: int,
    delta: DeltaBuffer,
    r_lo: OrderedResult,
    r_hi: OrderedResult,
    *,
    k: int = 8,
):
    """Delta-aware twin of ``plans.range_epilogue``.

    The count formula is unchanged -- ``rank_le(hi) - rank_lt(lo)`` over
    MERGED ranks -- and range_scan gathers consecutive merged ranks through
    ``select_merged`` instead of the static rank -> BFS map (the sorted
    view of tree + buffer exists only logically until compaction).
    """
    counts = jnp.maximum(r_hi.rank + r_hi.found.astype(jnp.int32) - r_lo.rank, 0)
    if op == "range_count":
        return counts
    take = jnp.minimum(counts, k)
    ranks = r_lo.rank[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < take[:, None]
    keys, values, _ = select_merged(
        sorted_keys, sorted_values, n_real, delta, ranks, valid
    )
    keys = jnp.where(valid, keys, tree_lib.SENTINEL_KEY)
    values = jnp.where(valid, values, tree_lib.SENTINEL_VALUE)
    return keys, values, take


# --------------------------------------------------------------- compaction
@functools.partial(jax.jit, static_argnames=("n_real", "out_size"))
def compact_sorted(
    tree_keys: jax.Array,
    tree_values: jax.Array,
    rank_to_bfs: jax.Array,
    n_real: int,
    delta: DeltaBuffer,
    out_size: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Merge snapshot + buffer into one sorted device view (pure jnp, jit).

    Returns ``(sorted_keys (out_size,), sorted_values, count)`` with
    sentinel padding past ``count``.  The merge is searchsorted/prefix-sum
    rank arithmetic -- the device twin of ``bulk_insert``'s two-pointer
    merge: surviving old keys shift down by the tombstones below them and
    up by the new upserts below them; live buffer entries land at their
    (ingest-time) tree rank adjusted the same way.  ``out_size`` must be
    >= n_real + capacity (the static worst case).
    """
    sk = tree_keys[rank_to_bfs]
    sv = tree_values[rank_to_bfs]
    n = sk.shape[0]

    live = delta.keys != tree_lib.SENTINEL_KEY
    pres = live & ~delta.tombstone
    # old ranks shadowed by a buffer entry (tombstoned OR overwritten)
    shadow_idx = jnp.where(live & delta.in_tree, delta.tree_rank, n)
    shadowed = (
        jnp.zeros((n + 1,), bool).at[shadow_idx].set(True, mode="drop")[:n]
    )
    real_old = jnp.arange(n) < n_real
    keep_old = real_old & ~shadowed

    pres_i = pres.astype(jnp.int32)
    pres_cum = jnp.cumsum(pres_i)
    pres_prefix = jnp.concatenate([jnp.zeros((1,), jnp.int32), pres_cum])
    # live upserts strictly below each old key (old keys never equal a
    # SURVIVING buffer key: equal keys are shadowed)
    pres_below_old = pres_prefix[jnp.searchsorted(delta.keys, sk, side="left")]
    pos_old = (jnp.cumsum(keep_old) - keep_old) + pres_below_old

    shadow_prefix = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(shadowed.astype(jnp.int32))]
    )
    kept_below_entry = delta.tree_rank - shadow_prefix[delta.tree_rank]
    pos_new = kept_below_entry + (pres_cum - pres_i)

    def scatter(values_old, values_new, fill):
        out = jnp.full((out_size + 1,), fill, jnp.int32)
        po = jnp.where(keep_old, pos_old, out_size).astype(jnp.int32)
        out = out.at[po].set(values_old, mode="drop")
        pn = jnp.where(pres, pos_new, out_size).astype(jnp.int32)
        return out.at[pn].set(values_new, mode="drop")[:out_size]

    out_k = scatter(sk, delta.keys, tree_lib.SENTINEL_KEY)
    out_v = scatter(sv, delta.values, tree_lib.SENTINEL_VALUE)
    count = (jnp.sum(keep_old) + jnp.sum(pres)).astype(jnp.int32)
    return out_k, out_v, count


def compact(tree: TreeData, delta: DeltaBuffer) -> TreeData:
    """Absorb the buffer into a fresh perfect snapshot (DESIGN.md §7).

    Device work end to end -- sorted merge + Eytzinger re-layout are both
    jitted gathers -- except the single scalar sync that reads the new key
    count (it fixes the new snapshot's static height).
    """
    rank_to_bfs = jnp.asarray(tree_lib.rank_to_bfs_indices(tree.height))
    out_size = tree.n_real + delta.capacity
    sk, sv, count = compact_sorted(
        tree.keys, tree.values, rank_to_bfs, tree.n_real, delta, out_size
    )
    # The write path's ONE sanctioned host sync, per compaction: counted by
    # the runtime gate, allowlisted under lint rule ANA006 (DESIGN.md §10).
    n_real = int(analysis_runtime.device_fetch(count))
    if n_real == 0:
        raise ValueError("compaction would empty the tree")
    return tree_lib.layout_from_sorted_device(sk, sv, n_real)
