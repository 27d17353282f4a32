"""Multi-chip hybrid partitioning: the paper's key router as a collective.

On the FPGA, vertical partitioning splits the tree into subtrees that live in
disjoint BRAM groups, and a routing network moves keys from the register
layer to the right subtree.  On a TPU pod the disjoint memories are *chips*:

  * the register layer (top ``log2(M)`` levels, a few KiB) is REPLICATED on
    every chip -- exactly the port-less register file;
  * subtree ``s`` lives in chip ``s``'s HBM (sharded over the ``model`` axis);
  * the routing network is an ``all_to_all``: after the local register-layer
    descent, each chip posts (dest -> key) buffers built by the paper's
    queue mapping, and the collective delivers each subtree its keys;
  * results ride the inverse all_to_all back to the requesting chip.

Tree *duplication* (DupN) is replication over the ``data``/``pod`` axes: each
replica group serves its own query stream -- plain data parallelism, included
here for completeness via ``dup_lookup``.

Buffer capacity is the collective-bytes lever (§Perf): capacity == local
batch is stall-free but sends B x M keys; smaller capacities send less and
handle overflow with an extra "stall round", faithfully mirroring the
paper's throughput/buffer-size trade-off.

Every pipeline phase here (route / dispatch / descend / combine) comes
from ``core/plans.py``, so this module only contributes the collectives
and the sharding (DESIGN.md §4).  Since §8 this is the ONE driver that
still composes the phases: the single-chip engine runs the whole hybrid
pipeline inside the forest kernel, but here dispatch IS a pair of
``all_to_all`` collectives, which no kernel body can absorb.

The entry point is ``make_distributed_query`` -- the same ``query(op, ...)``
contract as ``BSTEngine.query`` (DESIGN.md §6): the ordered descent runs
sharded (the full ``OrderedResult`` rides the return ``all_to_all`` as one
packed collective), so ONE compiled program serves every op -- lookups here
deliberately share the ordered datapath (+5 int32 lanes of return payload)
rather than compile a second membership-only program per mesh.  The per-op
epilogues are the plans-layer functions, and
range_scan's sorted-view gather reads the host snapshot (the bounded ``k``
columns are tiny next to the descent traffic).  ``make_distributed_lookup``
and ``make_dup_lookup`` remain as membership shorthands.

The live write path (DESIGN.md §7) extends the contract: ``run(op, ...,
delta=...)`` takes a ``core.delta.DeltaBuffer`` of pending
upserts/tombstones.  Like the register layer, the buffer is small and
REPLICATED on every chip; since DESIGN.md §9 its resolution runs INSIDE
the shard_map program -- each chip folds the replicated operands into its
local slice of the packed ``OrderedResult`` in the same compiled sharded
program as the collectives, so writes cost no extra collective and no
driver-level jnp twin remains.  The ordered epilogues then switch to rank
selection over the merged key set.  Compaction swaps the snapshot exactly
like a bulk rebuild.

``make_sharded_query`` is the serving-facing factory (DESIGN.md §9): one
strategy name -- hrz / dup / hyb, the same vocabulary as ``EngineConfig``
-- picks the mesh layout (``plans.mesh_axis_for_strategy``), the routing
pattern and the buffer capacities, and returns the same ``run(op, ...)``
contract, so ``BSTServer`` shards by flipping a constructor argument.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.compat import shard_map

from repro.analysis import invariants
from repro.analysis import runtime as analysis_runtime
from repro.core import delta as delta_lib
from repro.core import plans as plans_lib
from repro.core import tree as tree_lib
from repro.core.tree import TreeData

# The delta buffer rides every sharded program as four REPLICATED flat
# operands (DESIGN.md §9).  One constant serves both shard_map builders and
# the static contract checker, so the replication layout cannot drift.
DELTA_IN_SPECS = (P(),) * invariants.DELTA_OPERANDS

# Deriving the kernel operands from a DeltaBuffer compares against the host
# sentinel scalar; jitted so steady-state chunks replay a cached program
# instead of re-shipping the constant to device on every call.
_delta_operands = jax.jit(delta_lib.operands)


def stored_nodes_per_device(*arrays) -> int:
    """MEASURED stored key slots on the fullest device, from the arrays'
    actual shard layout (not a formula): the per-device memory figure the
    bench gate compares against single-chip (DESIGN.md §9).  A sharding
    regression that silently replicated a partitioned operand shows up
    here as an M-fold jump.
    """
    per: dict = {}
    for a in arrays:
        for s in a.addressable_shards:
            per[s.device] = per.get(s.device, 0) + int(np.prod(s.data.shape))
    return max(per.values()) if per else 0


def shard_subtrees(
    tree: TreeData, mesh: Mesh, axis: str
) -> Tuple[jax.Array, jax.Array, int, int]:
    """Vertical-partition the tree across ``axis``: (M, sub_n) arrays.

    The subtrees are gathered on the host and each device receives only
    its own row: no device ever holds the whole tree (DESIGN.md §9)."""
    M = mesh.shape[axis]
    split_level = invariants.check_power_of_two(M, f"mesh axis {axis} size")
    if split_level > tree.height:
        raise ValueError("tree shallower than the mesh axis")
    idx = tree_lib.all_subtree_gather_indices(tree.height, split_level)
    sharding = NamedSharding(mesh, P(axis, None))
    sub_keys = jax.device_put(np.asarray(tree.keys)[idx], sharding)
    sub_vals = jax.device_put(np.asarray(tree.values)[idx], sharding)
    return sub_keys, sub_vals, split_level, tree.height - split_level


def on_host(tree: TreeData) -> TreeData:
    """``tree`` with its arrays in host memory, where a sharded server keeps
    its snapshot (a host tree is returned as it is)."""
    if isinstance(tree.keys, np.ndarray):
        return tree
    keys, values = analysis_runtime.device_fetch((tree.keys, tree.values))
    return TreeData(keys, values, tree.height, tree.n_real)


def _make_query_runner(
    descend, tree: TreeData, mesh: Mesh, axis: str, lookup=None
):
    """Wrap a sharded ordered-descent into the ``run(op, ...)`` contract.

    One implementation of the op dispatch (operand validation, lo||hi
    concat/split, per-op epilogues from core/plans) shared by the
    all_to_all and data-parallel engines, so the contract cannot drift
    between them or from ``BSTEngine.query``.  ``delta`` (a replicated
    ``core.delta.DeltaBuffer``) rides the sharded program as four flat
    operands: ``descend(both, d_ops)`` folds the buffer ON-DEVICE inside
    the shard_map body (DESIGN.md §9), and this wrapper only switches the
    epilogues to their delta-aware twins (DESIGN.md §7).

    ``lookup`` is an optional membership fast path: a 2-output
    ``(queries, d_ops) -> (values, found)`` sharded program (the engine's
    own §6 rule -- the hot lookup path pays nothing for the ordered
    datapath).  Without it lookups ride the ordered descent and take its
    value/found lanes, which come back final: no epilogue runs.

    Nothing of the whole index's size is built for a lookup.  The rank ->
    BFS map and the sorted view (the ordered epilogues' gathers over the
    whole snapshot, jit constants) are built when the first op that reads
    them is served.  The runner also carries the write path on the mesh:
    ``ingest`` classifies a write chunk with the sharded descent and lands
    it in a REPLICATED buffer, and ``compact`` stages the merge through
    the mesh's first device and returns the merged snapshot to the host.
    """
    q_sharding = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    sorted_cache: list = []  # the whole sorted view, built on first use

    def _sorted_view():
        if not sorted_cache:
            r2b = tree_lib.rank_to_bfs_indices(tree.height)
            sorted_cache.append((np.asarray(tree.keys)[r2b], np.asarray(tree.values)[r2b]))
        return sorted_cache[0]

    # Per-op epilogues, jitted once per (op, k, delta?) so steady-state
    # chunks replay cached programs: run eagerly, every sentinel/arange/
    # n_real constant they mix with device results would ride host->device
    # again on EVERY chunk (the retrace/transfer gate fails exactly there).
    # The snapshot constants (sorted view, rank map) fold at compile time:
    # host arrays, made jnp constants inside the trace.
    epilogues: dict = {}

    def _epilogue(op: str, k: int, with_delta: bool):
        key = (op, k if op == "range_scan" else None, with_delta)
        if key not in epilogues:
            if with_delta:
                host_sorted = _sorted_view()
            if op in plans_lib.RANGE_OPS:
                def _split(res):
                    # lo||hi concatenated descent (DESIGN.md §6) splits
                    # back here, inside the jitted epilogue: an eager
                    # slice of the sharded result is a per-chunk transfer.
                    B = res.value.shape[0] // 2
                    return (
                        plans_lib.OrderedResult(*(f[:B] for f in res)),
                        plans_lib.OrderedResult(*(f[B:] for f in res)),
                    )

                if with_delta:
                    def fn(res, delta):
                        r_lo, r_hi = _split(res)
                        return delta_lib.range_epilogue(
                            op, *map(jnp.asarray, host_sorted), tree.n_real,
                            delta, r_lo, r_hi, k=k,
                        )
                else:
                    host_r2b = tree_lib.rank_to_bfs_indices(tree.height)

                    def fn(res):
                        r_lo, r_hi = _split(res)
                        full = TreeData(jnp.asarray(tree.keys), jnp.asarray(tree.values),
                                        tree.height, tree.n_real)
                        return plans_lib.range_epilogue(
                            op, full, jnp.asarray(host_r2b), r_lo, r_hi, k=k
                        )
            elif with_delta:
                def fn(q, res, delta):
                    return delta_lib.point_epilogue(
                        op, q, res, *map(jnp.asarray, host_sorted), tree.n_real,
                        delta,
                    )
            else:
                def fn(q, res):
                    return plans_lib.point_epilogue(op, q, res)
            epilogues[key] = jax.jit(fn)
        return epilogues[key]

    def put(queries) -> jax.Array:
        """A query batch placed straight from the host onto the mesh,
        sharded over ``axis`` (an array already placed so stays as it is)."""
        if not isinstance(queries, jax.Array):
            queries = np.asarray(queries, np.int32)
        elif queries.dtype != jnp.int32:
            queries = queries.astype(jnp.int32)
        return jax.device_put(queries, q_sharding)

    def run(op: str, queries, queries_hi=None, *, k: int = 8, delta=None):
        plans_lib.validate_op(op, queries_hi is not None)
        d_ops = None if delta is None else _delta_operands(delta)
        q = put(queries)
        if op == "lookup":
            # delta-hit > tombstone > tree-hit resolves in-program, so the
            # membership columns come back final either way.
            if lookup is not None:
                return lookup(q, d_ops)
            res = descend(q, d_ops)
            return res.value, res.found
        if op in plans_lib.RANGE_OPS:
            both = jnp.concatenate([q, put(queries_hi)])
            res = descend(both, d_ops)
            epi = _epilogue(op, k, delta is not None)
            return epi(res, delta) if delta is not None else epi(res)
        res = descend(q, d_ops)
        epi = _epilogue(op, k, delta is not None)
        return epi(q, res, delta) if delta is not None else epi(q, res)

    @functools.partial(jax.jit, out_shardings=replicated)
    def _land(delta, keys, values, deletes, valid, found, rank):
        B = keys.shape[0]
        return delta_lib.ingest(delta, keys, values, deletes, valid, found[:B], rank[:B])

    def ingest(delta, keys, values, deletes, valid):
        """One write chunk into the buffer: classified against the sharded
        snapshot, landed in a buffer replicated on every device."""
        pad = (-keys.shape[0]) % mesh.shape[axis]
        q = keys if not pad else jnp.concatenate([keys, jnp.zeros((pad,), jnp.int32)])
        res = descend(put(q))
        return _land(delta, keys, values, deletes, valid, res.found, res.rank)

    def compact(snapshot: TreeData, delta) -> TreeData:
        """Merge the buffer into a fresh snapshot on the mesh's first device
        (the whole snapshot passes through it for the merge) and return the
        merged snapshot to the host, whence its shards are placed."""
        dev = mesh.devices.flat[0]
        staged = TreeData(
            jax.device_put(snapshot.keys, dev), jax.device_put(snapshot.values, dev),
            snapshot.height, snapshot.n_real,
        )
        return on_host(delta_lib.compact(staged, jax.device_put(delta, dev)))

    run.put = put
    # a fresh buffer starts where every ingest leaves it: replicated
    run.empty_delta = lambda capacity: jax.device_put(delta_lib.empty(capacity), replicated)
    run.ingest = ingest
    run.compact = compact
    return run


def make_distributed_query(
    tree: TreeData,
    mesh: Mesh,
    axis: str = "model",
    capacity: Optional[int] = None,
    stall_rounds: int = 1,
    use_kernel: bool = False,
    capacity_frac: Optional[float] = None,
):
    """Build a jitted distributed ``query(op, ...)`` over ``axis``.

    Returns ``run(op, queries, queries_hi=None, *, k=8)`` with the same
    per-op contract as ``BSTEngine.query`` (DESIGN.md §6).  Query batches
    are (B_global,) sharded over ``axis``; results come back with the same
    sharding (range_scan's gathered columns are replicated host arrays).

    ``capacity`` is the per-(src,dst) buffer depth; None means stall-free
    (capacity = local batch).  ``capacity_frac`` instead sizes the depth
    per TRACE as the local batch's fair share scaled by the fraction
    (``ceil(B_local / M * frac)``), so the concatenated ``lo || hi``
    range traces (2x the lanes) keep the same relative slack as point
    traces instead of silently halving it.  ``stall_rounds`` extra rounds
    re-dispatch overflowed keys (paper: frontend stall while buffers
    drain); keys still pending afterwards ride one final stall-free drain
    round, so every result is exact -- capacity/stall_rounds trade
    collective bytes for rounds, never correctness.  ``use_kernel=True``
    routes each chip's local subtree descent through the forest-batched
    Pallas kernel.
    """
    if capacity is not None and capacity_frac is not None:
        raise ValueError("pass capacity OR capacity_frac, not both")
    M = mesh.shape[axis]
    sub_keys, sub_vals, split_level, sub_height = shard_subtrees(tree, mesh, axis)
    reg_n = (1 << max(split_level, 1)) - 1
    reg_keys = jax.device_put(tree.keys[:reg_n], NamedSharding(mesh, P()))
    reg_vals = jax.device_put(tree.values[:reg_n], NamedSharding(mesh, P()))

    def _one_round(queries, dest, active, sub_k, sub_v, cap):
        """dispatch -> all_to_all -> local ordered descent -> all_to_all back."""
        dplan = plans_lib.dispatch_phase("queue", dest, M, cap, active=active)
        send_q, send_live = plans_lib.gather_phase(queries, dplan)
        # (M, C): row d goes to chip d; receive row s = keys from chip s.
        recv_q = jax.lax.all_to_all(send_q, axis, 0, 0, tiled=False)
        recv_live = jax.lax.all_to_all(
            send_live.astype(jnp.int32), axis, 0, 0, tiled=False
        )
        sub = plans_lib.descend_phase_ordered(
            sub_k,
            sub_v,
            sub_height,
            recv_q.reshape(1, -1),
            (recv_live.reshape(-1) != 0)[None, :],
            use_kernel=use_kernel,
        )
        packed = plans_lib.pack_ordered(
            plans_lib.OrderedResult(*(f[0].reshape(M, cap) for f in sub))
        )
        back = jax.lax.all_to_all(packed, axis, 0, 0, tiled=False)
        got = plans_lib.combine_phase_ordered(
            plans_lib.unpack_ordered(back), dplan, queries.shape[0]
        )
        return got, dplan.overflow

    capped = capacity is not None or capacity_frac is not None

    def _query_local(queries, sub_k, sub_v, *d_ops):
        B = queries.shape[0]
        if capacity_frac is not None:
            # Sized per trace: the lo||hi range traces see 2x the lanes
            # and get 2x the depth, keeping the slack a real constant.
            cap = invariants.capacity_for_trace(B, M, capacity_frac)
        else:
            cap = capacity if capacity is not None else B
        dest, reg = plans_lib.route_phase_ordered(
            reg_keys, reg_vals, queries, split_level, tree.height
        )
        acc = tree_lib.init_ordered(B)
        pending = ~reg.found
        # Stall rounds: overflowed keys re-enter, buffers now empty.
        for _ in range(1 + (stall_rounds if capped else 0)):
            got, overflow = _one_round(queries, dest, pending, sub_k, sub_v, cap)
            acc = plans_lib.where_ordered(pending & ~overflow, got, acc)
            pending = overflow
        if capped:
            # Final drain at capacity == local batch: queue mapping cannot
            # overflow a depth-B buffer, so NO lane is left with a partial
            # ordered result (ranks/floors must be exact, not best-effort --
            # the FPGA frontend likewise stalls until every key is placed).
            # Guarded by a mesh-wide any() so the full-size round only runs
            # when some chip still has pending keys: that is what makes
            # capacity/stall_rounds a real bytes-vs-rounds trade, the small
            # rounds lowering the probability of ever paying this one.
            def drain(args):
                acc, pending = args
                got, _ = _one_round(queries, dest, pending, sub_k, sub_v, B)
                return plans_lib.where_ordered(pending, got, acc)

            any_pending = (
                jax.lax.pmax(pending.any().astype(jnp.int32), axis) > 0
            )
            acc = jax.lax.cond(any_pending, drain, lambda a: a[0], (acc, pending))
        res = plans_lib.merge_ordered(reg, acc)
        if d_ops:
            # On-device delta fold (DESIGN.md §9): the REPLICATED buffer
            # resolves against this chip's local query slice inside the
            # same compiled sharded program as the collectives -- after the
            # register merge, so register hits see overrides too.
            res = delta_lib.merge_ordered(
                res, *delta_lib.resolve_operands(d_ops, queries)
            )
        return tuple(res)

    # One compiled sharded program per write-path state: reads without a
    # buffer keep the 3-operand program; the delta variant threads the four
    # replicated buffer operands through the same shard_map body.
    programs = {}

    def _program(with_delta: bool):
        if with_delta not in programs:
            programs[with_delta] = jax.jit(
                shard_map(
                    _query_local,
                    mesh=mesh,
                    in_specs=(P(axis), P(axis, None), P(axis, None))
                    + (DELTA_IN_SPECS if with_delta else ()),
                    out_specs=tuple([P(axis)] * 7),
                    check=False,
                )
            )
        return programs[with_delta]

    def _descend(queries, d_ops=None) -> plans_lib.OrderedResult:
        extra = tuple(d_ops) if d_ops is not None else ()
        return plans_lib.OrderedResult(
            *_program(bool(extra))(queries, sub_keys, sub_vals, *extra)
        )

    def routed_slots(lanes: int) -> int:
        """Send-buffer slots the router's ``all_to_all`` carries in one
        call of ``lanes`` lanes: chips x chips x capacity, per round that
        every call runs (a capped router's conditional drain round is not
        counted)."""
        B = lanes // M
        if capacity_frac is not None:
            cap = invariants.capacity_for_trace(B, M, capacity_frac)
        else:
            cap = capacity if capacity is not None else B
        return M * M * cap * (1 + (stall_rounds if capped else 0))

    run = _make_query_runner(_descend, tree, mesh, axis)
    run.routed_slots = routed_slots
    run.mesh = mesh
    run.capacity = capacity
    run.split_level = split_level
    run.device_nodes = stored_nodes_per_device(sub_keys, reg_keys)
    return run


def make_distributed_lookup(
    tree: TreeData,
    mesh: Mesh,
    axis: str = "model",
    capacity: Optional[int] = None,
    stall_rounds: int = 1,
    use_kernel: bool = False,
):
    """Membership shorthand over ``make_distributed_query`` (kept API)."""
    query = make_distributed_query(
        tree,
        mesh,
        axis=axis,
        capacity=capacity,
        stall_rounds=stall_rounds,
        use_kernel=use_kernel,
    )

    def run(queries: jax.Array):
        return query("lookup", queries)

    run.mesh = query.mesh
    run.capacity = query.capacity
    run.split_level = query.split_level
    run.query = query
    return run


def make_dup_query(
    tree: TreeData,
    mesh: Mesh,
    axis: str = "data",
    use_kernel: bool = False,
):
    """DupN as data parallelism: replicate the tree, shard the query stream.

    Returns the same ``run(op, ...)`` contract as ``make_distributed_query``
    -- each replica group runs the full ordered descent on its slice, so
    every op is embarrassingly parallel here.  ``use_kernel=True`` lowers
    each replica's local descent through the forest-batched Pallas kernel;
    ``delta`` folds the replicated write buffer on-device per replica
    (DESIGN.md §9).  Lookups take a MEMBERSHIP fast-path program (the
    kernel's 2-output configuration, the engine's own §6 rule): with no
    collectives to share, the hot path pays nothing for the ordered
    datapath's extra tracking or return lanes.
    """
    keys = jax.device_put(tree.keys, NamedSharding(mesh, P()))
    vals = jax.device_put(tree.values, NamedSharding(mesh, P()))

    def _local(queries, k, v, *d_ops):
        res = plans_lib.descend_phase_ordered(
            k[None, :],
            v[None, :],
            tree.height,
            queries[None, :],
            use_kernel=use_kernel,
        )
        res = plans_lib.OrderedResult(*(f[0] for f in res))
        if d_ops:
            res = delta_lib.merge_ordered(
                res, *delta_lib.resolve_operands(d_ops, queries)
            )
        return tuple(res)

    def _local_lookup(queries, k, v, *d_ops):
        val, found = plans_lib.descend_phase(
            k[None, :],
            v[None, :],
            tree.height,
            queries[None, :],
            use_kernel=use_kernel,
        )
        val, found = val[0], found[0]
        if d_ops:
            hit, dead, d_val, _ = delta_lib.resolve_operands(d_ops, queries)
            val, found = delta_lib.merge_lookup(val, found, hit, dead, d_val)
        return val, found

    programs = {}

    def _program(body, n_out: int, with_delta: bool):
        key = (body.__name__, with_delta)
        if key not in programs:
            programs[key] = jax.jit(
                shard_map(
                    body,
                    mesh=mesh,
                    in_specs=(P(axis), P(), P())
                    + (DELTA_IN_SPECS if with_delta else ()),
                    out_specs=tuple([P(axis)] * n_out),
                    check=False,
                )
            )
        return programs[key]

    def _call(body, n_out, queries, d_ops):
        extra = tuple(d_ops) if d_ops is not None else ()
        return _program(body, n_out, bool(extra))(queries, keys, vals, *extra)

    def _descend(queries, d_ops=None) -> plans_lib.OrderedResult:
        return plans_lib.OrderedResult(*_call(_local, 7, queries, d_ops))

    def _lookup(queries, d_ops=None):
        return _call(_local_lookup, 2, queries, d_ops)

    run = _make_query_runner(_descend, tree, mesh, axis, lookup=_lookup)
    run.routed_slots = lambda lanes: 0  # no router: each replica serves its own slice
    run.mesh = mesh
    run.device_nodes = stored_nodes_per_device(keys)
    return run


def make_dup_lookup(tree: TreeData, mesh: Mesh, axis: str = "data"):
    """Membership shorthand over ``make_dup_query`` (kept API)."""
    query = make_dup_query(tree, mesh, axis=axis)

    def run(queries: jax.Array):
        return query("lookup", queries)

    run.mesh = query.mesh
    run.query = query
    return run


# ------------------------------------------------------------ sharded serving
def make_serving_mesh(strategy: str, devices=None) -> Mesh:
    """A 1-D mesh over ``devices`` named for the strategy's shard axis.

    The serving layer shards over ONE axis (DESIGN.md §9): the batch for
    dup, the tree for hrz/hyb.  ``plans.mesh_axis_for_strategy`` picks the
    name, so a mesh built here always satisfies ``make_sharded_query``.
    """
    axis = plans_lib.mesh_axis_for_strategy(strategy)
    devs = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.asarray(devs), (axis,))


def make_sharded_query(
    tree: TreeData,
    mesh: Mesh,
    strategy: str,
    *,
    buffer_slack: float = 2.0,
    stall_rounds: int = 1,
    use_kernel: bool = False,
):
    """The serving-facing sharded factory (DESIGN.md §9).

    One strategy name -- the same hrz / dup / hyb vocabulary as
    ``EngineConfig`` -- picks the whole mesh layout:

      * ``hrz``: the tree vertically partitioned into per-device subtrees,
        chunks routed by the STALL-FREE all_to_all (capacity == local
        batch -- one round, maximal collective bytes);
      * ``dup``: the tree replicated, the chunk split over the axis (pure
        data parallelism, no routing traffic);
      * ``hyb``: subtree-sharded forest + replicated register layer with
        the paper's queue-capped dispatch buffers: per-(src,dst) capacity
        sized PER TRACE as the local batch's fair share scaled by
        ``buffer_slack`` (so range ops' doubled lo||hi lanes keep the same
        relative slack) plus ``stall_rounds`` -- collective bytes traded
        for rounds, correctness guaranteed by the final drain round.

    Returns the ``run(op, queries, queries_hi=None, *, k=8, delta=None)``
    contract of ``make_distributed_query``; ``delta`` folds on-device
    inside the sharded program.  The caller must keep global batches
    divisible by the axis size (``BSTServer`` pads its fixed-shape chunks
    and enforces divisibility at construction).
    """
    axis = plans_lib.mesh_axis_for_strategy(strategy)
    if axis not in mesh.axis_names:
        raise ValueError(
            f"strategy {strategy!r} shards over mesh axis {axis!r}, but the "
            f"mesh has {mesh.axis_names} -- build one with make_serving_mesh"
        )
    if strategy == "dup":
        run = make_dup_query(
            tree, mesh, axis=axis, use_kernel=use_kernel
        )
        run.capacity_frac = None
    else:
        frac = buffer_slack if strategy == "hyb" else None
        run = make_distributed_query(
            tree,
            mesh,
            axis=axis,
            capacity_frac=frac,  # hrz: None -> stall-free routing
            stall_rounds=stall_rounds,
            use_kernel=use_kernel,
        )
        run.capacity_frac = frac
    run.strategy = strategy
    run.axis = axis
    run.n_shards = mesh.shape[axis]
    return run
