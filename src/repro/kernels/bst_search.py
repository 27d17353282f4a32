"""Pallas TPU kernel: forest-batched ordered BST descent, one flat operand.

FPGA -> TPU mapping (DESIGN.md §2):

* the BFS (Eytzinger) array *is* the level-major BRAM image: level ``l``
  occupies the contiguous slice ``[2^l - 1, 2^{l+1} - 1)``.  The kernel
  sees it as a ``(rows, 128)`` lane-dense VMEM block (the flat array
  zero-padded to whole ``(8, 128)`` tiles), so node ``i`` sits at row
  ``i >> 7``, lane ``i & 127``;
* the register layer (the top 7 levels, nodes ``0..126``) is row 0 of that
  block: every query lane reads it with an in-register lane gather;
* deeper levels are BRAM port reads: each lane's row index goes to SMEM,
  the scalar core copies the ``block_q`` addressed rows into a VMEM
  scratch, and a transpose plus a one-hot lane select picks each lane's
  node out of its row (Mosaic gathers only within one vreg, so a per-lane
  read of a large block is a row copy and a select, never a gather);
* parallel subtrees / replicas  ->  a leading *forest* dimension.  The 2-D
  grid ``(n_trees, query_chunks)`` lowers horizontal (``n_trees == 1``),
  duplicated (``shared_tree=True``: every grid row reads tree row 0) and
  hybrid (one row per vertical subtree) partitioning to the SAME kernel --
  one ``pallas_call``, no ``vmap``-of-``pallas_call``;
* dual-port keys/cycle  ->  a whole query *chunk* (``block_q`` lanes) does a
  compare-descend step per level, i.e. the level pipeline is unrolled across
  the vector unit instead of across clock cycles;
* the query-chunk grid dimension streams chunks exactly like the FPGA
  streams key chunks -- while chunk ``i`` is being compared, the DMA engine
  prefetches chunk ``i+1`` (Pallas double-buffers input blocks).

The datapath is ORDERED (DESIGN.md §6): besides the exact-match payload,
each compare-descend step tracks the last right-turn ancestor (the strict
predecessor), the last left-turn ancestor (the strict successor) and the
query's rank boundary -- all inside the same pipelined descent, which is
what turns the membership accelerator into a range-query engine.  The
paper's hit/miss search is the SAME kernel body unrolled in its 2-output
configuration (``ordered=False``), so lookups pay none of the tracking.

``interpret=False`` (the default) compiles the kernel with Mosaic for the
TPU; ``kernels/ops.py`` switches to the Pallas interpreter on any other
backend.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import invariants

# Plain ints: jnp scalars would be captured as consts inside the kernel.
SENTINEL_VALUE = -1
NO_PRED_KEY = -(2**31)  # int32 min: identity of the max-tracked predecessor
NO_SUCC_KEY = 2**31 - 1  # int32 max: identity of the min-tracked successor

LANES = 128  # vreg lane width: one tree row, one query lane group
TILE_NODES = 8 * LANES  # one (8, 128) int32 tile
REGISTER_LEVELS = 7  # levels whose nodes (0..126) all sit in tree row 0
VMEM_HEADROOM = 8 << 20  # scratch, pipelined query/output blocks, temporaries


def tree_rows(n_nodes: int) -> int:
    """Rows of the lane-dense tree block for an ``n_nodes`` flat tree."""
    return -(-n_nodes // TILE_NODES) * (TILE_NODES // LANES)


def _read_nodes(idx, n_nodes, level, tk_ref, tv_ref, scratch):
    """(keys, values) at BFS index ``idx`` for every lane of the chunk.

    Query lane ``j`` needs lane ``idx & 127`` of tree row ``idx >> 7``.
    Levels inside tree row 0 all read that row.  Deeper levels copy each
    lane's row into ``rows_k``/``rows_v`` (row indices reach the scalar
    core through an SMEM copy).  Transposed, row ``j`` becomes column
    ``j``, and a one-hot compare against the lane offsets sums each column
    down to the lane's node.
    """
    B = idx.shape[1]
    safe = jnp.clip(idx, 0, n_nodes - 1)
    if level < REGISTER_LEVELS:
        # Every lane's node is in row 0: its transpose serves every group.
        col_k = jnp.broadcast_to(tk_ref[0:1, :], (LANES, LANES)).T
        col_v = jnp.broadcast_to(tv_ref[0:1, :], (LANES, LANES)).T
        columns = lambda g: (col_k, col_v)  # noqa: E731
    else:
        row_vmem, row_smem, rows_k, rows_v, sem = scratch
        row_vmem[...] = safe >> 7
        copy = pltpu.make_async_copy(row_vmem, row_smem, sem)
        copy.start()
        copy.wait()

        def fetch(step, carry):
            for u in range(8):  # eight row copies per trip keep the scalar core busy
                j = step * 8 + u
                r = row_smem[0, j]
                rows_k[pl.ds(j, 1), :] = tk_ref[pl.ds(r, 1), :]
                rows_v[pl.ds(j, 1), :] = tv_ref[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, B // 8, fetch, 0)
        columns = lambda g: (rows_k[g : g + LANES, :].T, rows_v[g : g + LANES, :].T)  # noqa: E731
    lane_of_row = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    ks, vs = [], []
    for g in range(0, B, LANES):
        pick = lane_of_row == (safe[:, g : g + LANES] & (LANES - 1))
        col_k, col_v = columns(g)
        ks.append(jnp.sum(jnp.where(pick, col_k, 0), axis=0, keepdims=True))
        vs.append(jnp.sum(jnp.where(pick, col_v, 0), axis=0, keepdims=True))
    return jnp.concatenate(ks, axis=1), jnp.concatenate(vs, axis=1)


def _descend_one_level(q, state, active, nk, nv, left_size, ordered):
    """One compare-descend step against the lanes' current nodes.

    With ``ordered`` (a Python flag: the level loop is unrolled, so the
    membership configuration emits none of the tracking ops) the step also
    updates the ordered state: ``left_size`` is the left-subtree size
    ``2^{H-l} - 1`` at this level -- a right turn skips the node plus that
    whole subtree, an exact hit skips just the subtree, which is the rank
    arithmetic range queries build on (DESIGN.md §6).
    """
    idx, val, found, pk, pv, sk, sv, rank = state
    live = active & (found == 0)
    hit = (nk == q) & live
    go_right = live & ~hit & (q > nk)
    val = jnp.where(hit, nv, val)
    found = jnp.where(hit, 1, found)
    if ordered:
        go_left = live & ~hit & (q < nk)
        pk = jnp.where(go_right, nk, pk)  # right-turn keys increase: last == max
        pv = jnp.where(go_right, nv, pv)
        sk = jnp.where(go_left, nk, sk)  # left-turn keys decrease: last == min
        sv = jnp.where(go_left, nv, sv)
        rank = rank + jnp.where(go_right, left_size + 1, 0)
        rank = rank + jnp.where(hit, left_size, 0)
    idx = jnp.where(live & ~hit, 2 * idx + 1 + go_right.astype(idx.dtype), idx)
    return (idx, val, found, pk, pv, sk, sv, rank)


def _dispatch_lanes(dest, live, mapping: str, n_sub: int, capacity: int):
    """In-kernel buffer placement (paper §II.C.3): which lanes land in their
    subtree's dispatch buffer this chunk, and which overflow to the stall
    round.  Each lane counts the earlier live lanes bound for its subtree:
    all of them for ``'queue'`` (the paper's labeling network, placed while
    the label fits the buffer), only those sharing its slot ``i % capacity``
    for ``'direct'`` (placed when the slot is still free).  The count is a
    0/1 matmul against a strictly-triangular lane mask, exact in f32 on the
    MXU.  Pure lane arithmetic -- the buffers are never materialized
    because the lanes never move: a placed lane simply continues its
    descent inside its subtree's BRAM slice.
    """
    if mapping not in ("queue", "direct"):
        raise ValueError(f"unknown mapping {mapping!r} (want 'direct' or 'queue')")
    B = dest.shape[1]
    rows = -(-n_sub // 8) * 8
    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, B), 0)
    member = ((sub == dest) & live).astype(jnp.float32)  # (rows, B)
    earlier = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    later = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    before = earlier < later
    if mapping == "direct":
        before = before & (earlier % capacity == later % capacity)
    counts = jnp.dot(member, before.astype(jnp.float32), preferred_element_type=jnp.float32)
    mine = jnp.sum(counts * member, axis=0, keepdims=True).astype(jnp.int32)
    placed = live & (mine < (capacity if mapping == "queue" else 1))
    return placed, live & ~placed


def _forest_search_kernel(
    q_ref,
    act_ref,
    tk_ref,
    tv_ref,
    *refs,
    height: int,
    n_nodes: int,
    ordered: bool,
    dispatch: Optional[Tuple[int, str, int]] = None,
):
    """ONE kernel body for every configuration of the datapath: membership
    (2 output refs), ordered (7 output refs, DESIGN.md §6) and -- with
    ``dispatch`` (a static ``(split_level, mapping, capacity)`` triple,
    DESIGN.md §8) -- the full hybrid pipeline: register-layer route,
    queue/direct dispatch into per-subtree lanes, vertical-subtree descent
    and the overflow-lane stall-round replay, all in this body.  The last
    five refs are the row-fetch scratch of ``_read_nodes``."""
    n_out = 7 if ordered else 2
    out_refs, scratch = refs[:n_out], refs[n_out:]
    q = q_ref[...]
    active = act_ref[...] != 0
    shape = q.shape
    state = (
        jnp.zeros(shape, jnp.int32),  # idx
        jnp.full(shape, SENTINEL_VALUE, dtype=jnp.int32),  # val
        jnp.zeros(shape, jnp.int32),  # found (int32: Mosaic carries no i1 through cond)
        jnp.full(shape, NO_PRED_KEY, dtype=jnp.int32),  # pred key
        jnp.full(shape, SENTINEL_VALUE, dtype=jnp.int32),  # pred value
        jnp.full(shape, NO_SUCC_KEY, dtype=jnp.int32),  # succ key
        jnp.full(shape, SENTINEL_VALUE, dtype=jnp.int32),  # succ value
        jnp.zeros(shape, jnp.int32),  # rank
    )

    def descend(st, levels, gate):
        for l in levels:
            nk, nv = _read_nodes(st[0], n_nodes, l, tk_ref, tv_ref, scratch)
            st = _descend_one_level(q, st, gate, nk, nv, (1 << (height - l)) - 1, ordered)
        return st

    if dispatch is None:
        state = descend(state, range(height + 1), active)
    else:
        # --- hybrid pipeline (DESIGN.md §8).  The route is the descent over
        # levels [0, split).  A live lane's BFS index then sits at the split
        # level; its offset there names its vertical subtree.  Dispatch
        # decides which lanes the per-subtree buffers admit this chunk;
        # placed lanes descend their subtree's BRAM slice, overflow lanes
        # sit out the subtree pass and REPLAY the same levels afterwards --
        # the in-kernel stall round (the buffers have drained by then, so
        # the replay admits everything).  Both passes start from the same
        # register-layer state: it is a valid prefix of every lane's
        # root-to-leaf path, which is what makes the replay exact.
        split, mapping, capacity = dispatch
        state = descend(state, range(split), active)
        n_sub = 1 << split
        live = active & (state[2] == 0)
        dest = jnp.clip(state[0] - (n_sub - 1), 0, n_sub - 1)
        _, overflow = _dispatch_lanes(dest, live, mapping, n_sub, capacity)
        deep = range(split, height + 1)
        sub_state = descend(state, deep, active & ~overflow)
        # The stall round re-runs the subtree levels for the deferred lanes
        # only -- the hardware's "frontend stalls while buffers drain", paid
        # only when a buffer actually overflowed (the cond is the cycle cost
        # of a stall, in kernel form).
        stalled = jnp.max(overflow.astype(jnp.int32)) > 0
        rep_state = jax.lax.cond(
            stalled, lambda st: descend(st, deep, overflow), lambda st: st, state
        )
        state = tuple(jnp.where(overflow, r, s) for r, s in zip(rep_state, sub_state))

    _, val, found, pk, pv, sk, sv, rank = state
    outs = (val, found)
    if ordered:
        outs = outs + (pk, pv, sk, sv, rank)
    for ref, arr in zip(out_refs, outs):
        ref[...] = arr


def bst_ordered_forest_pallas(
    forest_keys: jax.Array,
    forest_values: jax.Array,
    queries: jax.Array,
    height: int,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    interpret: bool = False,
    shared_tree: bool = False,
    ordered: bool = True,
    dispatch: Optional[Tuple[int, str, int]] = None,
) -> Tuple[jax.Array, ...]:
    """Ordered search over a forest of BFS-layout trees in ONE ``pallas_call``.

    forest_keys/forest_values: (n_rows, n) flat level-major trees, where
    ``n = 2^{height+1} - 1``.  queries/active: (n_trees, B).  With
    ``shared_tree=True`` the operand has one row that every grid row reads
    (duplicated partitioning -- replication without materialisation).
    ``block_q`` (a multiple of 128) is the query chunk per grid step.

    ``dispatch`` selects the hybrid configuration (DESIGN.md §8): a static
    ``(split_level, mapping, capacity)`` triple that makes levels
    ``[0, split_level)`` the route, places the surviving lanes into
    per-subtree dispatch buffers (queue/direct, paper §II.C.3) and replays
    overflow lanes through the deep levels after the subtree pass -- the
    in-kernel stall round.

    Returns per-lane (n_trees, B) arrays
    ``(values, found, pred_keys, pred_values, succ_keys, succ_values, rank)``
    -- the ordered contract of DESIGN.md §6: strict predecessor/successor
    ancestors (NO_PRED_KEY / NO_SUCC_KEY when absent) and the count of
    stored keys strictly below each query.
    """
    if forest_keys.ndim != 2 or queries.ndim != 2:
        raise ValueError("forest operands and queries must be 2-D")
    T, B = queries.shape
    n_rows, n = forest_keys.shape
    # Shared with repro.analysis.contracts (DESIGN.md §10).
    invariants.check_forest_nodes(n, height)
    if not shared_tree and n_rows != T:
        raise ValueError("need one tree row per query row (or shared_tree=True)")
    if block_q % LANES:
        raise ValueError(f"block_q={block_q} must be a multiple of {LANES}")
    if dispatch is not None and not 0 <= dispatch[0] <= height:
        raise ValueError("hybrid split level must lie in [0, height]")
    if active is None:
        active = jnp.ones((T, B), bool)
    pad = (-B) % block_q
    qp = jnp.pad(queries, ((0, 0), (0, pad)))[:, None, :]
    ap = jnp.pad(active.astype(jnp.int32), ((0, 0), (0, pad)))[:, None, :]
    nq = qp.shape[2] // block_q

    rows = tree_rows(n)
    pad_nodes = rows * LANES - n
    tk = jnp.pad(forest_keys, ((0, 0), (0, pad_nodes))).reshape(n_rows, rows, LANES)
    tv = jnp.pad(forest_values, ((0, 0), (0, pad_nodes))).reshape(n_rows, rows, LANES)

    if shared_tree:
        tree_map = lambda t, i: (0, 0, 0)  # noqa: E731 -- every grid row reads row 0
    else:
        tree_map = lambda t, i: (t, 0, 0)  # noqa: E731
    chunk_map = lambda t, i: (t, 0, i)  # noqa: E731
    # The tree block changes only with the grid row, so one buffer suffices.
    tree_spec = pl.BlockSpec((None, rows, LANES), tree_map, pipeline_mode=pl.Buffered(1))
    chunk_spec = pl.BlockSpec((None, 1, block_q), chunk_map)

    kernel = functools.partial(
        _forest_search_kernel,
        height=height,
        n_nodes=n,
        ordered=ordered,
        dispatch=dispatch,
    )
    n_out = 7 if ordered else 2
    tree_bytes = 2 * rows * LANES * 4
    outs = pl.pallas_call(
        kernel,
        grid=(T, nq),
        in_specs=[chunk_spec, chunk_spec, tree_spec, tree_spec],
        out_specs=[chunk_spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct(qp.shape, jnp.int32)] * n_out,
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.int32),
            pltpu.SMEM((1, block_q), jnp.int32),
            pltpu.VMEM((block_q, LANES), jnp.int32),
            pltpu.VMEM((block_q, LANES), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tree_bytes + VMEM_HEADROOM + 16 * block_q * block_q
        ),
        interpret=interpret,
        name="bst_forest_search",
    )(qp, ap, tk, tv)
    outs = tuple(o[:, 0, :B] for o in outs)
    return (outs[0], outs[1] != 0) + outs[2:]


def bst_search_forest_pallas(
    forest_keys: jax.Array,
    forest_values: jax.Array,
    queries: jax.Array,
    height: int,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    interpret: bool = False,
    shared_tree: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Membership search: the same kernel body in its 2-output configuration.

    Returns (values, found), each (n_trees, B).  One ``pallas_call``; the
    unroll skips the ordered tracking entirely (``ordered=False`` is a
    Python flag), so lookups pay nothing for the §6 datapath.
    """
    out = bst_ordered_forest_pallas(
        forest_keys,
        forest_values,
        queries,
        height,
        active=active,
        block_q=block_q,
        interpret=interpret,
        shared_tree=shared_tree,
        ordered=False,
    )
    return out[0], out[1]


def bst_hybrid_forest_pallas(
    tree_keys: jax.Array,
    tree_values: jax.Array,
    queries: jax.Array,
    height: int,
    split_level: int,
    mapping: str = "queue",
    capacity: int = 1,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    interpret: bool = False,
    ordered: bool = True,
) -> Tuple[jax.Array, ...]:
    """The WHOLE hybrid pipeline in ONE ``pallas_call`` (DESIGN.md §8).

    tree_keys/tree_values: the (n,) flat level-major FULL tree -- the top
    ``split_level`` levels are the route and each vertical subtree is a
    BRAM slice of the same operand.  Per ``block_q`` chunk the kernel
    routes, places survivors into per-subtree dispatch buffers (``mapping``
    x ``capacity``, paper §II.C.3), descends placed lanes through their
    subtree and replays overflow lanes through the same levels (the stall
    round) -- no driver-level composition left.  Returns (B,) arrays: the
    7-field ordered contract, or (values, found) with ``ordered=False``.
    """
    if queries.ndim != 1 or tree_keys.ndim != 1:
        raise ValueError("hybrid operands are single-tree: 1-D arrays")
    out = bst_ordered_forest_pallas(
        tree_keys[None, :],
        tree_values[None, :],
        queries[None, :],
        height,
        active=None if active is None else active[None, :],
        block_q=block_q,
        interpret=interpret,
        ordered=ordered,
        dispatch=(split_level, mapping, capacity),
    )
    return tuple(o[0] for o in out)


def bst_search_pallas(
    tree_keys: jax.Array,
    tree_values: jax.Array,
    queries: jax.Array,
    height: int,
    active: Optional[jax.Array] = None,
    block_q: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Single-tree convenience wrapper: a forest of one (n_trees == 1)."""
    val, found = bst_search_forest_pallas(
        tree_keys[None, :],
        tree_values[None, :],
        queries[None, :],
        height,
        active=None if active is None else active[None, :],
        block_q=block_q,
        interpret=interpret,
    )
    return val[0], found[0]
