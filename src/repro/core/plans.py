"""SearchPlan: one strategy abstraction behind every search datapath.

The paper's claim is a single compare-descend datapath *reconfigured* by
partitioning strategy (horizontal / duplicated / hybrid).  This module is
that datapath in software (DESIGN.md §4): a ``SearchPlan`` captures the
strategy's static layout (flat forest operands, register layer, dispatch
mapping) and the pipeline phases

    route_phase    -- register-layer descent, survivors get a subtree id
    dispatch_phase -- direct-/queue-mapped buffer placement (paper §II.C.3)
    descend_phase  -- forest-batched subtree descent (Pallas kernel or oracle)
    combine_phase  -- scatter buffered results back into chunk order

are plain functions.  Since DESIGN.md §8 the single-chip driver no longer
composes them: every strategy -- hyb included -- lowers straight through
the one forest call (``_hybrid_descend`` selects the kernel's dispatch
configuration, so route/dispatch/descent/stall-replay/delta all run inside
the ``pallas_call`` or its jnp twin).  The phase functions remain the
shared vocabulary of the drivers whose dispatch crosses a real boundary:
the multi-chip ``all_to_all`` engine in ``core/distributed.py`` (a pair of
collectives between dispatch and descent) and the roofline lowering in
``launch/dryrun_bst.py``.

The datapath is ORDERED (DESIGN.md §6): every phase has an ``_ordered``
variant carrying the full ``OrderedResult`` (exact match + strict
predecessor/successor ancestors + rank boundary), and ``ordered_query``
is the per-op contract every engine lowers through -- lookup, predecessor,
successor, range_count and range_scan all ride the SAME single
forest-batched ``pallas_call`` (range ops descend ``lo || hi`` in one
concatenated pass and finish with rank arithmetic over the sorted view).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import invariants
from repro.core import buffers as buf
from repro.core import delta as delta_lib
from repro.core import tree as tree_lib
from repro.core.tree import OrderedResult, TreeData
from repro.kernels import ops as kops

# The per-op query contract (DESIGN.md §6).  Every op lowers through one
# ordered forest descent; they differ only in operand count and epilogue.
QUERY_OPS = ("lookup", "predecessor", "successor", "range_count", "range_scan")
RANGE_OPS = ("range_count", "range_scan")

# How each engine strategy lays out over a serving mesh (DESIGN.md §9):
#   hrz -- the one tree vertically partitioned into per-device subtrees;
#          request chunks route through the stall-free all_to_all network;
#   dup -- the tree replicated on every device, the chunk split over the
#          axis (data parallelism, no routing traffic at all);
#   hyb -- subtree-sharded forest + replicated register layer, with the
#          paper's queue-capped dispatch buffers as the collective-bytes
#          lever (finite capacity + stall rounds).
# ``mesh_axis_for_strategy`` is the single place that mapping lives, so the
# server, the benchmarks and the examples cannot disagree on which mesh
# axis a strategy shards over.
SHARDED_STRATEGIES = ("hrz", "dup", "hyb")


def mesh_axis_for_strategy(strategy: str) -> str:
    """The mesh axis a sharded plan uses: dup shards the *batch* over the
    data axis; hrz/hyb shard the *tree* over the model axis."""
    if strategy not in SHARDED_STRATEGIES:
        raise ValueError(
            f"unknown sharded strategy {strategy!r} (want {SHARDED_STRATEGIES})"
        )
    return "data" if strategy == "dup" else "model"


def validate_op(op: str, has_hi: bool) -> None:
    """One place for the op-name / operand-arity contract checks -- shared
    by every query entry point (engine, distributed, plans)."""
    if op not in QUERY_OPS:
        raise ValueError(f"unknown op {op!r} (want one of {QUERY_OPS})")
    if has_hi != (op in RANGE_OPS):
        raise ValueError(f"op {op!r}: range ops take (lo, hi), others one batch")


@dataclasses.dataclass(frozen=True)
class SearchPlan:
    """Static per-engine search configuration (built once, looked up often).

    forest_keys/forest_values: (n_rows, m) flat level-major trees -- one
    row for every single-chip strategy: hrz and hyb carry the full tree
    (for hyb, levels [0, split_level) double as the register layer and
    each vertical subtree is a BRAM slice of the same flat image --
    DESIGN.md §8), dup shares its one row across replicas.
    ``shared_tree`` marks dup's replication-without-copy: every kernel
    grid row reads operand row 0.  ``split_level``/``mapping``/
    ``buffer_slack`` parameterize hyb's in-kernel dispatch (paper
    §II.C.3).  ``full_tree`` (every strategy) backs the ordered ops'
    sorted-view gathers; ``rank_to_bfs`` maps in-order rank -> BFS index
    so range_scan reads consecutive ranks straight out of the flat layout
    (the delta epilogues' sorted view is the same gather, traced on demand
    inside ``ordered_query`` so read-only plans never materialize it).
    ``reg_keys``/``reg_values`` remain only for multi-chip drivers that
    replicate the register layer explicitly (``core/distributed.py``
    builds its own; single-chip hyb reads it out of the flat operand).
    """

    strategy: str  # hrz | dup | hyb
    forest_keys: jax.Array
    forest_values: jax.Array
    forest_height: int
    n_trees: int
    shared_tree: bool
    split_level: int = 0
    mapping: str = "queue"  # direct | queue (hyb only)
    buffer_slack: float = 2.0
    reg_keys: Optional[jax.Array] = None
    reg_values: Optional[jax.Array] = None
    full_tree: Optional[TreeData] = None
    rank_to_bfs: Optional[jax.Array] = None

    def sorted_view(self) -> Tuple[jax.Array, jax.Array]:
        """The snapshot's sorted key/value view (one gather; under ``jit``
        both inputs are constants, so XLA folds it at compile time)."""
        return (
            self.full_tree.keys[self.rank_to_bfs],
            self.full_tree.values[self.rank_to_bfs],
        )

    def memory_nodes(self) -> int:
        """Stored nodes (the paper's Fig. 8 memory metric)."""
        rows, m = self.forest_keys.shape
        if self.strategy == "dup":
            return int(m) * self.n_trees
        reg = 0 if self.reg_keys is None else int(self.reg_keys.shape[0])
        return rows * int(m) + reg


def resolved_register_levels(n_trees: int, register_levels: Optional[int]) -> int:
    if register_levels is not None:
        return register_levels
    return max(1, int(math.log2(max(n_trees, 2))))


def make_plan(
    tree: TreeData,
    *,
    strategy: str,
    n_trees: int = 1,
    mapping: str = "queue",
    register_levels: Optional[int] = None,
    buffer_slack: float = 2.0,
) -> SearchPlan:
    """Build the strategy's SearchPlan from one immutable tree snapshot."""
    rank_to_bfs = jnp.asarray(tree_lib.rank_to_bfs_indices(tree.height))
    if strategy == "hrz":
        return SearchPlan(
            strategy="hrz",
            forest_keys=tree.keys[None, :],
            forest_values=tree.values[None, :],
            forest_height=tree.height,
            n_trees=1,
            shared_tree=False,
            full_tree=tree,
            rank_to_bfs=rank_to_bfs,
        )
    if strategy == "dup":
        if n_trees < 1:
            raise ValueError("dup needs n_trees >= 1")
        return SearchPlan(
            strategy="dup",
            forest_keys=tree.keys[None, :],
            forest_values=tree.values[None, :],
            forest_height=tree.height,
            n_trees=n_trees,
            shared_tree=True,
            full_tree=tree,
            rank_to_bfs=rank_to_bfs,
        )
    if strategy != "hyb":
        raise ValueError(f"unknown strategy {strategy!r}")

    r = resolved_register_levels(n_trees, register_levels)
    if (1 << r) < n_trees:
        raise ValueError(
            f"register_levels={r} exposes {1 << r} subtrees < n_trees={n_trees}"
        )
    if r > tree.height:
        raise ValueError("register layer deeper than the tree")
    split_level = invariants.split_level_for(n_trees)
    # One flat operand carries the whole pipeline (DESIGN.md §8): levels
    # [0, split_level) double as the register layer and each vertical
    # subtree is a BRAM slice of the same level-major image, so the hybrid
    # kernel (and its jnp twin) needs no per-subtree gather at build time.
    return SearchPlan(
        strategy="hyb",
        forest_keys=tree.keys[None, :],
        forest_values=tree.values[None, :],
        forest_height=tree.height,
        n_trees=n_trees,
        shared_tree=False,
        split_level=split_level,
        mapping=mapping,
        buffer_slack=buffer_slack,
        full_tree=tree,
        rank_to_bfs=rank_to_bfs,
    )


# --------------------------------------------------------------------- phases
def route_phase(
    reg_keys: jax.Array,
    reg_values: jax.Array,
    queries: jax.Array,
    split_level: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Register-layer descent -> (dest, value, found).

    ``split_level == 0`` means no routing network: everything goes to
    subtree 0 unresolved (the single-partition degenerate case).
    """
    B = queries.shape[0]
    if split_level == 0:
        return (
            jnp.zeros((B,), jnp.int32),
            jnp.full((B,), tree_lib.SENTINEL_VALUE, jnp.int32),
            jnp.zeros((B,), bool),
        )
    reg_tree = TreeData(
        reg_keys, reg_values, max(split_level - 1, 0), int(reg_keys.shape[0])
    )
    return tree_lib.register_layer_route(reg_tree, queries, split_level)


def route_phase_ordered(
    reg_keys: jax.Array,
    reg_values: jax.Array,
    queries: jax.Array,
    split_level: int,
    full_height: int,
) -> Tuple[jax.Array, OrderedResult]:
    """Ordered register-layer descent -> (dest, partial OrderedResult).

    The partial result carries the register layer's predecessor/successor
    candidates and its rank contribution (left-subtree sizes of the FULL
    tree); the subtree descent below the split completes all three
    (``merge_ordered``).
    """
    B = queries.shape[0]
    if split_level == 0:
        return jnp.zeros((B,), jnp.int32), tree_lib.init_ordered(B)
    reg_tree = TreeData(
        reg_keys, reg_values, max(split_level - 1, 0), int(reg_keys.shape[0])
    )
    return tree_lib.register_layer_route_ordered(
        reg_tree, queries, split_level, full_height
    )


def dispatch_phase(
    mapping: str,
    dest: jax.Array,
    n_dest: int,
    capacity: int,
    active: Optional[jax.Array] = None,
) -> buf.DispatchPlan:
    """Buffer placement: the paper's direct/queue mapping networks."""
    return buf.dispatch(mapping, dest, n_dest, capacity, active=active)


def gather_phase(
    items: jax.Array, dplan: buf.DispatchPlan, fill_value=0
) -> Tuple[jax.Array, jax.Array]:
    """Materialize the buffered items: (B,) -> ((n_dest, cap), live mask)."""
    per_dest = buf.gather_from_buffers(items, dplan.buffers, fill_value=fill_value)
    return per_dest, dplan.buffers >= 0


def descend_phase(
    forest_keys: jax.Array,
    forest_values: jax.Array,
    height: int,
    queries: jax.Array,
    active: Optional[jax.Array] = None,
    *,
    shared_tree: bool = False,
    use_kernel: bool = False,
    delta: Optional[Tuple[jax.Array, ...]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Forest-batched compare-descend: (n_trees, B) queries in one shot.

    ``use_kernel=True`` lowers to the single forest ``pallas_call``;
    otherwise the vmapped jnp oracle runs (bit-identical by property test).
    Both paths live behind ``kernels.ops.bst_search_forest`` so the
    forest-batching shape handling exists exactly once.  ``delta`` rides
    the write buffer's flat operands on either path (DESIGN.md §7).
    """
    return kops.bst_search_forest(
        forest_keys,
        forest_values,
        queries,
        height=height,
        active=active,
        shared_tree=shared_tree,
        use_ref=not use_kernel,
        delta=delta,
    )


def descend_phase_ordered(
    forest_keys: jax.Array,
    forest_values: jax.Array,
    height: int,
    queries: jax.Array,
    active: Optional[jax.Array] = None,
    *,
    shared_tree: bool = False,
    use_kernel: bool = False,
    delta: Optional[Tuple[jax.Array, ...]] = None,
) -> OrderedResult:
    """Ordered forest-batched compare-descend (DESIGN.md §6).

    Same single-``pallas_call`` lowering as ``descend_phase``; the extra
    outputs (strict predecessor/successor ancestors, rank boundary) fall out
    of the same pipelined descent.  Fields are (n_trees, B).  With
    ``delta`` the write buffer rides the call and value/found/rank come
    back merged (DESIGN.md §7).
    """
    out = kops.bst_ordered_forest(
        forest_keys,
        forest_values,
        queries,
        height=height,
        active=active,
        shared_tree=shared_tree,
        use_ref=not use_kernel,
        delta=delta,
    )
    return OrderedResult(*out)


def combine_phase(
    sub_values: jax.Array,
    sub_found: jax.Array,
    dplan: buf.DispatchPlan,
    chunk_size: int,
    reg_values: Optional[jax.Array] = None,
    reg_found: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter per-buffer results back to chunk order; merge register hits."""
    got_v = buf.combine_to_chunk(
        sub_values, dplan.buffers, chunk_size, fill_value=tree_lib.SENTINEL_VALUE
    )
    got_f = buf.combine_to_chunk(sub_found, dplan.buffers, chunk_size, fill_value=False)
    if reg_found is None:
        return got_v, got_f
    return jnp.where(reg_found, reg_values, got_v), reg_found | got_f


def combine_phase_ordered(
    sub: OrderedResult, dplan: buf.DispatchPlan, chunk_size: int
) -> OrderedResult:
    """Scatter per-buffer ordered results back to chunk order.

    Unplaced lanes get each field's identity (no hit, no predecessor, no
    successor, rank 0), so a later ``merge_ordered`` / stall-round override
    composes cleanly.
    """
    fills = (
        tree_lib.SENTINEL_VALUE,  # value
        False,  # found
        tree_lib.NO_PRED_KEY,
        tree_lib.SENTINEL_VALUE,
        tree_lib.NO_SUCC_KEY,
        tree_lib.SENTINEL_VALUE,
        0,  # rank
    )
    return OrderedResult(
        *(
            buf.combine_to_chunk(field, dplan.buffers, chunk_size, fill_value=fill)
            for field, fill in zip(sub, fills)
        )
    )


def pack_ordered(res: OrderedResult) -> jax.Array:
    """Stack the 7 ordered fields into one ``(..., F)`` int32 image.

    The whole ordered payload then rides a routing collective as ONE
    ``all_to_all`` (or one device transfer) instead of a collective per
    field -- the packed-combine contract of DESIGN.md §9.  The lane width
    is pinned to ``invariants.ORDERED_PACK_WIDTH`` so a field added to
    ``OrderedResult`` cannot silently widen every collective.
    """
    assert len(res) == invariants.ORDERED_PACK_WIDTH, res._fields
    return jnp.stack([f.astype(jnp.int32) for f in res], axis=-1)


def unpack_ordered(packed: jax.Array) -> OrderedResult:
    # NamedTuple order on both sides keeps pack/unpack structurally tied.
    assert packed.shape[-1] == invariants.ORDERED_PACK_WIDTH, packed.shape
    fields = tuple(packed[..., i] for i in range(packed.shape[-1]))
    res = OrderedResult(*fields)
    return res._replace(found=res.found != 0)


def merge_ordered(reg: OrderedResult, sub: OrderedResult) -> OrderedResult:
    """Merge the register layer's partial result with the subtree descent.

    The two are disjoint halves of one root-to-leaf path, so: exact hits are
    exclusive; the predecessor is the deeper (larger) of the two right-turn
    candidates and the successor the deeper (smaller) left-turn candidate
    (absent candidates sit at the tracking identities, so plain max/min is
    exact); ranks add (register turns count FULL-tree left subtrees, subtree
    turns count local ones -- together the global rank, DESIGN.md §6).
    """
    take_sub_pred = sub.pred_key > reg.pred_key
    take_sub_succ = sub.succ_key < reg.succ_key
    return OrderedResult(
        value=jnp.where(reg.found, reg.value, sub.value),
        found=reg.found | sub.found,
        pred_key=jnp.maximum(reg.pred_key, sub.pred_key),
        pred_value=jnp.where(take_sub_pred, sub.pred_value, reg.pred_value),
        succ_key=jnp.minimum(reg.succ_key, sub.succ_key),
        succ_value=jnp.where(take_sub_succ, sub.succ_value, reg.succ_value),
        rank=reg.rank + sub.rank,
    )


def where_ordered(
    mask: jax.Array, a: OrderedResult, b: OrderedResult
) -> OrderedResult:
    """Per-lane select between two ordered results (stall-round override)."""
    return OrderedResult(*(jnp.where(mask, x, y) for x, y in zip(a, b)))


# -------------------------------------------------------------------- drivers
# The kernel dispatches each block_q chunk independently (the FPGA streams
# chunks); the jnp twin treats the whole batch as one chunk, the retired
# driver's granularity.  Results are identical either way -- the stall
# round's contract -- so the choice is purely a throughput model.
KERNEL_BLOCK_Q = 512


def hyb_capacity(plan: SearchPlan, chunk: int) -> int:
    """Per-subtree dispatch-buffer depth for a ``chunk``-lane frontend:
    the fair share ``chunk / n_trees`` scaled by the plan's slack."""
    return invariants.buffer_capacity(chunk, plan.n_trees, plan.buffer_slack)


def _hybrid_descend(
    plan: SearchPlan,
    queries: jax.Array,
    *,
    ordered: bool,
    use_kernel: bool,
    delta: Optional[Tuple[jax.Array, ...]],
) -> Tuple[jax.Array, ...]:
    """Single-chip hyb: the WHOLE pipeline in one call (DESIGN.md §8).

    Register route, queue/direct dispatch, subtree descent and stall-round
    replay all execute inside the forest ``pallas_call``
    (``use_kernel=True``) or its structurally matching jnp oracle, and
    ``kernels.ops`` folds the delta buffer in right after -- there is no
    driver-level composition (and no driver-level delta twin) left to
    drift.
    """
    chunk = KERNEL_BLOCK_Q if use_kernel else queries.shape[0]
    return kops.bst_hybrid_forest(
        plan.full_tree.keys,
        plan.full_tree.values,
        queries,
        height=plan.full_tree.height,
        split_level=plan.split_level,
        mapping=plan.mapping,
        capacity=hyb_capacity(plan, chunk),
        block_q=KERNEL_BLOCK_Q,
        ordered=ordered,
        use_ref=not use_kernel,
        delta=delta,
    )


def execute_plan_ordered(
    plan: SearchPlan,
    queries: jax.Array,
    *,
    use_kernel: bool = False,
    delta: Optional[delta_lib.DeltaBuffer] = None,
) -> OrderedResult:
    """The single-chip driver: one ordered pass through the plan's phases.

    Returns the full per-query ``OrderedResult`` -- the common substrate
    every query op's epilogue reads (``ordered_query``).  All strategies
    descend through the one forest-batched kernel / oracle; hyb's route /
    dispatch / descent / stall replay execute inside that same call
    (DESIGN.md §8).

    With ``delta`` (DESIGN.md §7) value/found/rank come back merged
    against the pending write buffer, folded by the descent's own
    ``kernels.ops`` entry point -- the driver never composes a jnp twin
    on top.
    """
    B = queries.shape[0]
    d_ops = None if delta is None else delta_lib.operands(delta)
    if plan.strategy == "hrz":
        res = descend_phase_ordered(
            plan.forest_keys,
            plan.forest_values,
            plan.forest_height,
            queries[None, :],
            use_kernel=use_kernel,
            delta=d_ops,
        )
        return OrderedResult(*(f[0] for f in res))

    if plan.strategy == "dup":
        # n_trees replicas each take a contiguous slice of the chunk.
        n = plan.n_trees
        pad = (-B) % n
        q = jnp.pad(queries, (0, pad)).reshape(n, -1)
        res = descend_phase_ordered(
            plan.forest_keys,
            plan.forest_values,
            plan.forest_height,
            q,
            shared_tree=True,
            use_kernel=use_kernel,
            delta=d_ops,
        )
        return OrderedResult(*(f.reshape(-1)[:B] for f in res))

    # hyb: route + dispatch + descent + stall replay + delta merge, all
    # inside the one forest call (DESIGN.md §8).
    return OrderedResult(
        *_hybrid_descend(
            plan,
            queries,
            ordered=True,
            use_kernel=use_kernel,
            delta=d_ops,
        )
    )


def execute_plan(
    plan: SearchPlan,
    queries: jax.Array,
    *,
    use_kernel: bool = False,
    delta: Optional[delta_lib.DeltaBuffer] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Membership lookup through the kernel's 2-output configuration.

    Same phase chain as ``execute_plan_ordered`` but none of the ordered
    tracking -- the hot lookup path pays nothing for the §6 datapath.
    ``delta`` folds into the descent call for every strategy (DESIGN.md §7).
    """
    B = queries.shape[0]
    d_ops = None if delta is None else delta_lib.operands(delta)
    if plan.strategy == "hrz":
        val, found = descend_phase(
            plan.forest_keys,
            plan.forest_values,
            plan.forest_height,
            queries[None, :],
            use_kernel=use_kernel,
            delta=d_ops,
        )
        return val[0], found[0]

    if plan.strategy == "dup":
        n = plan.n_trees
        pad = (-B) % n
        q = jnp.pad(queries, (0, pad)).reshape(n, -1)
        val, found = descend_phase(
            plan.forest_keys,
            plan.forest_values,
            plan.forest_height,
            q,
            shared_tree=True,
            use_kernel=use_kernel,
            delta=d_ops,
        )
        return val.reshape(-1)[:B], found.reshape(-1)[:B]

    # hyb: route + dispatch + descent + stall replay + delta merge, all
    # inside the one forest call's 2-output configuration (DESIGN.md §8).
    val, found = _hybrid_descend(
        plan,
        queries,
        ordered=False,
        use_kernel=use_kernel,
        delta=d_ops,
    )
    return val, found


def ordered_query(
    plan: SearchPlan,
    op: str,
    queries: jax.Array,
    queries_hi: Optional[jax.Array] = None,
    *,
    k: int = 8,
    use_kernel: bool = False,
    delta: Optional[delta_lib.DeltaBuffer] = None,
):
    """The per-op query contract (DESIGN.md §6) -- one descent, one epilogue.

    * ``lookup(q)``           -> (values, found)
    * ``predecessor(q)``      -> (keys, values, ok): largest stored key <= q
    * ``successor(q)``        -> (keys, values, ok): smallest stored key >= q
    * ``range_count(lo, hi)`` -> counts of stored keys in [lo, hi]
    * ``range_scan(lo, hi)``  -> (keys (B, k), values (B, k), counts): the
      first ``k`` in-order pairs of [lo, hi], sentinel-padded past the end;
      ``counts`` is clipped to ``k`` (the bounded-scan contract).

    Range ops descend the concatenated ``lo || hi`` batch, so every op costs
    exactly one forest ``pallas_call``; the epilogues are rank arithmetic
    plus (for range_scan) a gather through the rank -> BFS map.  Keys and
    bounds must be strictly inside (NO_PRED_KEY, SENTINEL_KEY); when ``ok``
    is False the key output is NO_PRED_KEY / NO_SUCC_KEY and the value
    SENTINEL_VALUE.

    With ``delta`` (the live write path, DESIGN.md §7) the same descent
    resolves the pending upserts/tombstones, and every epilogue switches to
    its delta-aware twin in ``core/delta.py`` -- rank selection over the
    merged key set instead of the static rank -> BFS map.  An empty buffer
    degenerates to the classic answers bit-for-bit, so one compiled
    function serves the engine before and after writes land.
    """
    validate_op(op, queries_hi is not None)

    if op == "lookup":
        # The hot membership path: same phases, 2-output kernel config.
        return execute_plan(
            plan, queries, use_kernel=use_kernel, delta=delta
        )

    if op in RANGE_OPS:
        lo, hi = queries, queries_hi
        B = lo.shape[0]
        res = execute_plan_ordered(
            plan,
            jnp.concatenate([lo, hi]),
            use_kernel=use_kernel,
            delta=delta,
        )
        r_lo = OrderedResult(*(f[:B] for f in res))
        r_hi = OrderedResult(*(f[B:] for f in res))
        if delta is not None:
            sorted_keys, sorted_values = plan.sorted_view()
            return delta_lib.range_epilogue(
                op,
                sorted_keys,
                sorted_values,
                plan.full_tree.n_real,
                delta,
                r_lo,
                r_hi,
                k=k,
            )
        return range_epilogue(
            op, plan.full_tree, plan.rank_to_bfs, r_lo, r_hi, k=k
        )

    res = execute_plan_ordered(
        plan, queries, use_kernel=use_kernel, delta=delta
    )
    if delta is not None:
        sorted_keys, sorted_values = plan.sorted_view()
        return delta_lib.point_epilogue(
            op,
            queries,
            res,
            sorted_keys,
            sorted_values,
            plan.full_tree.n_real,
            delta,
        )
    return point_epilogue(op, queries, res)


def point_epilogue(op: str, queries: jax.Array, res: OrderedResult):
    """Per-lane epilogue of the single-batch ops (shared with distributed)."""
    if op == "lookup":
        return res.value, res.found
    if op == "predecessor":
        # floor(q): q itself on an exact hit, else the strict predecessor.
        keys = jnp.where(res.found, queries, res.pred_key)
        values = jnp.where(res.found, res.value, res.pred_value)
        ok = res.found | (res.pred_key != tree_lib.NO_PRED_KEY)
        return keys, values, ok
    # successor: ceiling(q).
    keys = jnp.where(res.found, queries, res.succ_key)
    values = jnp.where(res.found, res.value, res.succ_value)
    ok = res.found | (res.succ_key != tree_lib.NO_SUCC_KEY)
    return keys, values, ok


def range_epilogue(
    op: str,
    full_tree: TreeData,
    rank_to_bfs: jax.Array,
    r_lo: OrderedResult,
    r_hi: OrderedResult,
    *,
    k: int = 8,
):
    """Rank arithmetic over the sorted view (shared with distributed).

    |[lo, hi]| = rank_le(hi) - rank_lt(lo); empty ranges (lo > hi) clamp to
    0.  range_scan gathers the first ``k`` ranks through the rank -> BFS
    map, so the "sorted view" is read straight out of the flat layout.
    """
    counts = jnp.maximum(r_hi.rank + r_hi.found.astype(jnp.int32) - r_lo.rank, 0)
    if op == "range_count":
        return counts
    take = jnp.minimum(counts, k)
    ranks = r_lo.rank[:, None] + jnp.arange(k, dtype=jnp.int32)[None, :]
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < take[:, None]
    bfs = rank_to_bfs[jnp.clip(ranks, 0, full_tree.n_nodes - 1)]
    keys = jnp.where(valid, full_tree.keys[bfs], tree_lib.SENTINEL_KEY)
    values = jnp.where(valid, full_tree.values[bfs], tree_lib.SENTINEL_VALUE)
    return keys, values, take
