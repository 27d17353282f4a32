"""BSTServer: streaming request scheduler over immutable tree snapshots.

The paper's deployment story (DESIGN.md §5): search streams are served at
full throughput from an immutable snapshot while inserts/deletes accumulate;
a bulk update builds a fresh perfect tree and the server swaps snapshots
atomically between chunks.  This module is that loop, TPU-native:

  * **typed request kinds** -- every query op of DESIGN.md §6 is a request
    kind: ``lookup`` / ``predecessor`` / ``successor`` via ``submit``,
    ``range_count`` / ``range_scan`` via ``submit_range``.  The drain packs
    each kind into its own fixed-shape chunk stream (one jit shape per op),
    and stats are accounted per op;
  * **chunk accumulation** -- requests of any size are queued and packed
    into fixed ``chunk_size`` engine calls (the jit shape), padding only the
    final partial chunk per op; per-request results are sliced back out, so
    padded lanes never leak into answers or accounting;
  * **op-run queue** -- the pending queue is one list of each request's
    keys or range lows, the op RUNS (each run's start and op, one run per
    change of op) and, by queue position, the second operands of the
    requests that have one (range highs, write values); tickets are
    implicit: request
    ``i`` of the queue holds ticket ``first + i``.  A ``submit`` that
    continues its op's run costs the int32 check and one list append (an
    int32 vector is queued as it is: no copy, no conversion call, no
    counter); a new run, and every other request kind, takes ``_enqueue``.
    The drain reads the runs: a queue of one run is one read span and one
    group, whose keys are one ``np.concatenate``.  Where every request of
    a group holds one key (or one range), its answers are the result
    columns' rows, built in C as shape-``(1, ...)`` views
    (``ServerStats.row_answers`` counts them); any other group is sliced
    request by request;
  * **pluggable engine config** -- any ``EngineConfig`` (strategy, mapping,
    kernel/reference path) serves the same request API;
  * **live write path** (DESIGN.md §7) -- with
    ``EngineConfig(delta_capacity > 0)`` the server also takes ``write`` /
    ``delete`` request kinds (``submit_write`` / ``submit_delete``).  The
    drain preserves SUBMISSION ORDER across read/write boundaries: requests
    split into maximal read spans (order-independent, packed per op exactly
    as before) separated by write spans, each write span lands in the
    engine's device-side delta buffer as fixed-shape padded chunks, and
    compaction -- the engine's bulk merge into a fresh snapshot -- triggers
    between chunks at the high-water mark instead of a full O(n + m)
    rebuild per update.  Per-op stats cover writes too, plus cumulative
    ``updates`` and ``compactions`` counters;
  * **snapshot swap** -- ``apply_updates`` on a write-path engine routes
    through the delta buffer (above); otherwise it runs ``core.updates``
    bulk insert/delete and installs a new engine.  Lookups submitted before
    the swap but not yet drained see the new state (drain-before-swap if
    read-your-epoch consistency is required);
  * **one chunk loop** -- every read stream, on one chip or a mesh, runs
    a depth-2 pipeline: the next fixed-shape chunk is dispatched while
    the previous one is still in flight, and the sync and fetch trail one
    chunk behind, so host-side work overlaps device compute
    (``ServerStats.overlapped_chunks`` counts the overlapped chunks);
  * **busy accounting** -- busy seconds are the host time inside each
    chunk's dispatch, sync and fetch spans (the ingest span on the write
    path).  They are attributed per op by the engine lanes each request
    actually occupied (one per point/write/delete key, two per range
    request -- the lo||hi concatenated descent), so mixed spans cannot
    skew one op's busy time with another op's;
  * **phase spans** -- each drain is split into named host spans (drain,
    pack, dispatch, sync, fetch, unpack, ingest, compact, rewarm).  One
    helper feeds two outlets: the seconds land in ``ServerStats.phase_s``
    (always on), and each span is a ``bst.<name>`` profiler annotation, so
    a profiler trace shows it on the device trace's clock.  Spans are per
    drain and per chunk, never per request;
  * **sharded mode** (DESIGN.md §9) -- construct with ``mesh=`` and every
    read chunk routes through the strategy's shard_map-lowered plan
    (``core.distributed.make_sharded_query``: hrz shards the tree by
    subtree behind the all_to_all router, dup replicates the tree and
    splits the chunk, hyb shards the vertical forest and replicates the
    register layer), through the same chunk loop as one chip.  The
    snapshot stays in host memory and each device holds only its shard
    and the replicated parts; the engine never builds its one-chip plan.
    Ingest classifies writes with the sharded descent into a write
    buffer replicated on every device, which rides every sharded read as
    four operands folded on-device inside the sharded program; a
    compaction merges through one device and rebuilds the sharded
    programs via the engine's ``on_snapshot`` hook before the next read.
    ``ServerStats.routed_slots`` counts the router's send-buffer slots,
    and span ``shard_put`` times each chunk's sharded put.  ``chunk_size``
    must divide by the mesh axis size (chunks are always padded full, so
    no unpadded partial chunk can ever reach a sharded program).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis import invariants
from repro.analysis import runtime as analysis_runtime
from repro.core import distributed as dist_lib
from repro.core import plans as plans_lib
from repro.core import tree as tree_lib
from repro.core import updates as updates_lib
from repro.core.engine import BSTEngine, EngineConfig
from repro.core.tree import TreeData

# Derived from the plans-layer contract so a new op cannot drift past the
# server's request typing.
RANGE_OPS = plans_lib.RANGE_OPS
POINT_OPS = tuple(op for op in plans_lib.QUERY_OPS if op not in RANGE_OPS)
# Mutating request kinds (DESIGN.md §7); these are drain-order barriers.
WRITE_OPS = ("write", "delete")
_WRITE_SET = frozenset(WRITE_OPS)
_INT32 = np.dtype(np.int32)
# ``BSTServer._tail`` where no point-op run is open: no caller's op is it.
_NO_TAIL = object()


@dataclasses.dataclass
class OpStats:
    """Per-op serving counters (one entry per request kind actually seen)."""

    served: int = 0  # keys (point ops) / ranges (range ops) answered
    chunks: int = 0  # engine invocations
    busy_s: float = 0.0  # time inside the engine (incl. padding lanes)
    # Engine lanes the op's requests actually occupied (padding excluded):
    # one per key for point/write/delete ops, TWO per range request -- the
    # lo and hi bounds both descend (the lo||hi concatenated pass,
    # DESIGN.md §6).  Busy seconds in shared spans are attributed by this
    # number.
    lanes: int = 0


@dataclasses.dataclass
class ServerStats:
    """Cumulative serving counters (reset with ``BSTServer.reset_stats``).

    ``requests``, ``submitted`` and ``op_runs`` count DRAINED requests:
    each drain adds its queue's totals once, so a request still pending is
    in none of them and ``submit`` bumps no counter.
    """

    requests: int = 0  # requests drained
    submitted: int = 0  # keys/ranges of the drained requests
    served: int = 0  # keys/ranges/write-ops answered
    found: int = 0  # lookup hits (padding excluded)
    chunks: int = 0  # engine invocations
    # Host seconds inside the engine calls' dispatch, sync and fetch spans
    # and the ingest span (incl. padding lanes).
    busy_s: float = 0.0
    lanes: int = 0  # engine lanes occupied (see OpStats.lanes)
    snapshot_swaps: int = 0  # full-rebuild swaps (the non-delta path)
    updates: int = 0  # write/delete ops absorbed by the delta buffer
    compactions: int = 0  # delta-buffer merges into fresh snapshots
    per_op: Dict[str, OpStats] = dataclasses.field(default_factory=dict)
    # Host seconds per span name (see ``_Span``): each span's own time, a
    # nested span's time counting for the nested span only, so the values
    # add up to the wall time the spans cover.
    phase_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    drains: int = 0  # drain() calls that served a request
    # Summed over drains: the drain's start minus the enqueue time of its
    # oldest request.
    queue_wait_s: float = 0.0
    # Requests answered by row views: every request of their op group held
    # one key or one range (see ``BSTServer._unpack``).
    row_answers: int = 0
    # Op runs of the drained requests: one per request that began a run
    # (the first of its drain, or one whose op differs from the request
    # before it).  The other ``requests - op_runs`` were queued by
    # ``submit``'s one-append path.
    op_runs: int = 0
    # Sharded mode only.  Send-buffer slots the router's all_to_all
    # carried, summed over chunks (chips x chips x capacity per round):
    # beside ``lanes`` it shows the router's padding.
    routed_slots: int = 0
    # Chunks dispatched while an earlier chunk of the same stream was still
    # in flight (the chunk loop's overlap; one chip and sharded alike).
    overlapped_chunks: int = 0

    def op(self, name: str) -> OpStats:
        return self.per_op.setdefault(name, OpStats())


class _Span:
    """One named phase of the server's host work.

    On exit its host seconds are added to ``ServerStats.phase_s[name]``,
    less the time of any span opened inside it; around the block it is a
    ``jax.profiler.TraceAnnotation`` named ``bst.<name>`` with ``ids`` as
    its arguments, which records only while a profiler trace is active.
    """

    __slots__ = ("_server", "_name", "_annotation", "_t0", "_inner")

    def __init__(self, server: "BSTServer", name: str, ids: dict):
        self._server = server
        self._name = name
        self._annotation = jax.profiler.TraceAnnotation("bst." + name, **ids)
        self._inner = 0.0  # seconds of the spans opened inside this one

    def __enter__(self) -> None:
        self._annotation.__enter__()
        self._server._open_spans.append(self)
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        stack = self._server._open_spans
        stack.pop()
        if stack:
            stack[-1]._inner += dt
        phase_s = self._server.stats.phase_s
        phase_s[self._name] = phase_s.get(self._name, 0.0) + dt - self._inner
        self._annotation.__exit__(*exc)


def _order_spans(starts: List[int], ops: List[str], n: int) -> List[Tuple[int, int, list, list]]:
    """``(lo, hi, starts, ops)`` of the maximal spans of reads and of writes
    in a queue of ``n`` requests whose op runs start at ``starts`` with ops
    ``ops``, in order: each span's bounds, and its runs' starts and ops.  A
    queue of reads alone is one span, found in C; any other is walked run
    by run."""
    if _WRITE_SET.isdisjoint(ops):
        return [(0, n, starts, ops)]
    m = len(ops)
    cuts = [j for j in range(1, m) if (ops[j] in _WRITE_SET) != (ops[j - 1] in _WRITE_SET)]
    cuts = [0] + cuts + [m]
    return [
        (starts[j], starts[k] if k < m else n, starts[j:k], ops[j:k])
        for j, k in zip(cuts, cuts[1:])
    ]


def _run_lengths(starts: List[int], hi: int) -> np.ndarray:
    """Requests per run, for runs starting at ``starts`` and ending at ``hi``."""
    return np.diff(np.fromiter(starts, np.intp, len(starts)), append=hi)


class BSTServer:
    """Accumulate typed query requests, serve them in fixed-shape chunks.

    Single-threaded by design: the FPGA frontend is one stream of key
    chunks, and on TPU one jit shape per op amortises compilation.
    Thread-safety is the caller's concern (wrap submit/drain in a lock if
    shared).  ``scan_k`` fixes range_scan's bounded fan-out (part of the jit
    shape, so it is a server-level constant).
    """

    def __init__(
        self,
        keys,
        values,
        config: EngineConfig = EngineConfig(),
        chunk_size: int = 8192,
        scan_k: int = 8,
        mesh=None,
    ):
        if chunk_size < 1:
            raise ValueError("chunk_size must be positive")
        if scan_k < 1:
            raise ValueError("scan_k must be positive")
        self.config = config
        self.chunk_size = chunk_size
        self.scan_k = scan_k
        self.mesh = mesh
        self._squery = None
        if mesh is not None:
            axis = plans_lib.mesh_axis_for_strategy(config.strategy)
            if axis not in mesh.axis_names:
                raise ValueError(
                    f"strategy {config.strategy!r} shards over axis {axis!r}; "
                    f"the mesh has {mesh.axis_names} (see "
                    "distributed.make_serving_mesh)"
                )
            # Shared with repro.analysis.contracts: the checker verifies the
            # same bound statically, so neither side can drift (DESIGN.md §10).
            invariants.check_chunk_divides(chunk_size, mesh.shape[axis], axis)
        self.stats = ServerStats()
        self._open_spans: List[_Span] = []
        # The pending queue: each request's keys (point / write / delete
        # ops) or range lows; the op runs, a start and an op appended where
        # a request's op differs from the one before it; and the range
        # highs or write values by queue position, for the requests that
        # have them.  Request i holds ticket ``_first_ticket + i``.
        # ``_tail`` is the last run's op where ``submit`` may continue it
        # (a point op), else ``_NO_TAIL``.
        self._a: List[np.ndarray] = []
        self._starts: List[int] = []
        self._run_ops: List[str] = []
        self._b: Dict[int, np.ndarray] = {}
        self._tail: object = _NO_TAIL
        self._first_ticket = 0
        self._oldest_t = 0.0  # enqueue time of the oldest pending request
        self._warm_ops: Tuple[str, ...] = ()
        # Fixed jit shape for delta-buffer write chunks (DESIGN.md §7): one
        # compiled ingest program regardless of request sizes.
        self._write_chunk = (
            min(chunk_size, config.delta_capacity)
            if config.delta_capacity > 0
            else chunk_size
        )
        # Sharded, the snapshot stays on the host: only the shards and the
        # replicated parts are placed on the devices (DESIGN.md §9).
        self._install(
            tree_lib.build_tree(np.asarray(keys), np.asarray(values), host=mesh is not None)
        )

    # --------------------------------------------------------------- snapshot
    def _install(self, tree: TreeData) -> None:
        if self.mesh is None:
            self._engine = BSTEngine.from_tree(tree, self.config)
        else:
            tree = dist_lib.on_host(tree)
            self._engine = BSTEngine.from_tree(
                tree, self.config, sharded=self._install_sharded(tree)
            )
            # Compaction can swap the snapshot deep inside apply_ops'
            # chunk loop; the hook rebuilds the sharded programs before
            # any later read can see the stale tree (DESIGN.md §9).
            self._engine.on_snapshot = self._reshard
        self._engine.span = self._span  # compaction time lands in phase_s
        # The fresh engine's jit closes over the new snapshot; re-warm so
        # post-swap chunks (and busy accounting) stay compile-free.
        self._rewarm()

    def _rewarm(self) -> None:
        """Re-warm the warmed ops after a snapshot swap."""
        if self._warm_ops:
            with self._span("rewarm"):
                self.warmup(self._warm_ops)

    def _span(self, name: str, **ids) -> _Span:
        """A ``_Span`` of this server: ``with self._span("pack"): ...``."""
        return _Span(self, name, ids)

    def _install_sharded(self, tree: TreeData):
        """Place ``tree``'s shards and build the sharded reader over them."""
        cfg = self.config
        self._squery = dist_lib.make_sharded_query(
            tree,
            self.mesh,
            cfg.strategy,
            buffer_slack=cfg.buffer_slack,
            use_kernel=cfg.use_kernel,
        )
        return self._squery

    def _reshard(self, tree: TreeData) -> None:
        """The engine's snapshot-swap hook in sharded mode: the new
        snapshot's reader also takes the engine's writes."""
        self._engine.sharded = self._install_sharded(dist_lib.on_host(tree))

    @property
    def snapshot(self) -> TreeData:
        """The current immutable tree snapshot (pending delta-buffer writes,
        if any, overlay it until the next compaction)."""
        return self._engine.tree

    @property
    def engine(self) -> BSTEngine:
        return self._engine

    def warmup(self, ops: Tuple[str, ...] = ("lookup",)) -> None:
        """Populate the jit cache so timing excludes compilation.

        Pass the ops the workload will use; once called, every snapshot swap
        re-warms the same set on the fresh engine too.
        """
        dummy = np.zeros(self.chunk_size, np.int32)
        for op in ops:
            out = self._query_chunk(op, dummy, dummy)
            jax.block_until_ready(out)
        self._warm_ops = tuple(dict.fromkeys(self._warm_ops + tuple(ops)))

    def _query_chunk(self, op: str, a, b) -> tuple:
        """One fixed-shape chunk through the serving datapath: the sharded
        shard_map program when a mesh is installed, the local engine
        otherwise.  The pending delta buffer rides sharded reads as
        replicated operands (on-device fold, DESIGN.md §9); the engine
        threads its own buffer internally."""
        if self._squery is not None:
            kw = {"delta": self._engine.delta} if self._engine.delta is not None else {}
            with self._span("shard_put"):  # the chunk's host->device put
                a = self._squery.put(a)
                b = self._squery.put(b) if op in RANGE_OPS else None
            if op in RANGE_OPS:
                res = self._squery(op, a, b, k=self.scan_k, **kw)
            else:
                res = self._squery(op, a, **kw)
        elif op in RANGE_OPS:
            res = self._engine.query(op, a, b, k=self.scan_k)
        else:
            res = self._engine.query(op, a)
        return res if isinstance(res, tuple) else (res,)

    def apply_updates(
        self,
        insert_keys=None,
        insert_values=None,
        delete_keys=None,
    ) -> TreeData:
        """Bulk-maintain the store (deletes before inserts, so an upsert of
        a just-deleted key lands).  Returns the current snapshot.  Pending
        (undrained) requests will be served from the new state.

        With the write path enabled (``delta_capacity > 0``) the batch is
        absorbed by the engine's device-side delta buffer -- no rebuild,
        compaction at the high-water mark (DESIGN.md §7).  Otherwise this
        is the legacy full rebuild + snapshot swap.
        """
        n_ops = sum(
            len(np.atleast_1d(x)) for x in (insert_keys, delete_keys)
            if x is not None
        )
        if self._engine.delta is not None:
            before = self._engine.compactions
            self._engine.apply_updates(insert_keys, insert_values, delete_keys)
            self.stats.updates += n_ops
            self.stats.compactions += self._engine.compactions - before
            if self._engine.compactions != before:
                self._rewarm()  # compaction reset the jit cache
            return self._engine.tree
        tree = self._engine.tree
        if delete_keys is not None and len(np.atleast_1d(delete_keys)):
            tree = updates_lib.bulk_delete(tree, delete_keys)
        if insert_keys is not None and len(np.atleast_1d(insert_keys)):
            if insert_values is None:
                raise ValueError("insert_keys needs insert_values")
            tree = updates_lib.bulk_insert(tree, insert_keys, insert_values)
        self._install(tree)
        self.stats.snapshot_swaps += 1
        return tree

    # --------------------------------------------------------------- requests
    def submit(self, request_keys, op: str = "lookup") -> int:
        """Queue a point-query request; returns a ticket for drain().

        ``op`` is one of ``lookup`` (values, found), ``predecessor`` /
        ``successor`` (keys, values, ok) -- DESIGN.md §6 semantics.
        """
        req = request_keys
        # An int32 vector is what the conversion would return as it is.
        if not (type(req) is np.ndarray and req.dtype is _INT32 and req.ndim == 1):
            req = np.atleast_1d(np.asarray(req, np.int32))
            if req.ndim != 1:
                raise ValueError("request_keys must be scalar or 1-D")
        if op is self._tail:  # the run goes on; its op was checked at its start
            a = self._a
            a.append(req)
            return self._first_ticket + len(a) - 1
        if op not in POINT_OPS:
            raise ValueError(f"submit() op must be one of {POINT_OPS}, got {op!r}")
        return self._enqueue(op, req, None, op)

    def submit_range(self, lo, hi, op: str = "range_count") -> int:
        """Queue a range request over [lo, hi] (inclusive); returns a ticket.

        ``op`` is ``range_count`` (counts) or ``range_scan`` (keys (B,
        scan_k), values, counts).  lo/hi must be equal-length (or scalar).
        """
        if op not in RANGE_OPS:
            raise ValueError(f"submit_range() op must be one of {RANGE_OPS}, got {op!r}")
        lo = np.atleast_1d(np.asarray(lo, np.int32))
        hi = np.atleast_1d(np.asarray(hi, np.int32))
        if lo.shape != hi.shape or lo.ndim != 1:
            raise ValueError("lo/hi must be equal-length scalars or 1-D arrays")
        return self._enqueue(op, lo, hi)

    def submit_write(self, request_keys, request_values) -> int:
        """Queue an upsert request (DESIGN.md §7); returns a ticket.

        Requires a write-path engine (``delta_capacity > 0``).  The drain
        applies writes in SUBMISSION ORDER relative to every other request
        (reads before the write see the old state, reads after see it);
        the ticket resolves to ``(applied_count,)``.
        """
        self._require_write_path()
        k = np.atleast_1d(np.asarray(request_keys, np.int32))
        v = np.atleast_1d(np.asarray(request_values, np.int32))
        if k.shape != v.shape or k.ndim != 1:
            raise ValueError("keys/values must be equal-length scalars or 1-D")
        return self._enqueue("write", k, v)

    def submit_delete(self, request_keys) -> int:
        """Queue a delete (tombstone) request; returns a ticket.

        Same ordering contract as ``submit_write``; deleting an absent key
        is a no-op that still counts as applied.
        """
        self._require_write_path()
        k = np.atleast_1d(np.asarray(request_keys, np.int32))
        if k.ndim != 1:
            raise ValueError("request_keys must be scalar or 1-D")
        return self._enqueue("delete", k, None)

    def _require_write_path(self) -> None:
        if self._engine.delta is None:
            raise ValueError(
                "write/delete request kinds need EngineConfig(delta_capacity"
                " > 0); use apply_updates() for bulk snapshot swaps"
            )

    def _enqueue(self, op: str, a: np.ndarray, b: Optional[np.ndarray],
                 tail: object = _NO_TAIL) -> int:
        """Queue a checked request off ``submit``'s one-append path: start a
        run where ``op`` differs from the last run's, keep ``b``, and leave
        ``tail`` as the op ``submit`` may continue."""
        q = self._a
        i = len(q)
        if not i:
            self._oldest_t = time.perf_counter()  # once per drain, not per request
        if not i or self._run_ops[-1] != op:
            self._starts.append(i)
            self._run_ops.append(op)
        q.append(a)
        if b is not None:
            self._b[i] = b
        self._tail = tail
        return self._first_ticket + i

    def pending(self) -> int:
        """Keys/ranges queued but not yet served."""
        return sum(map(len, self._a))

    # ------------------------------------------------------------------ drain
    def drain(self) -> Dict[int, tuple]:
        """Serve every queued request; returns {ticket: op results}.

        Result shapes per op: ``lookup`` -> (values, found);
        ``predecessor``/``successor`` -> (keys, values, ok);
        ``range_count`` -> (counts,); ``range_scan`` -> (keys, values,
        counts); ``write``/``delete`` -> (applied_count,).

        Write requests are ORDER BARRIERS: the queue splits into maximal
        read spans separated by write spans, served in submission order, so
        a read observes exactly the writes submitted before it.  Within a
        read span (reads commute) each op's stream is packed into its own
        ``chunk_size`` engine calls exactly as before; write spans land in
        the delta buffer as fixed-shape padded chunks (DESIGN.md §7), with
        compaction between chunks when the high-water mark trips.  Only
        final partial chunks are padded, and padded lanes never reach
        results or accounting.
        """
        a = self._a
        if not a:
            return {}
        starts, ops, b = self._starts, self._run_ops, self._b
        first, n, keys = self._first_ticket, len(a), sum(map(len, a))
        self._a, self._starts, self._run_ops, self._b = [], [], [], {}
        self._tail = _NO_TAIL
        self._first_ticket = first + n
        stats = self.stats
        stats.requests += n
        stats.submitted += keys
        stats.op_runs += len(ops)
        stats.drains += 1
        stats.queue_wait_s += time.perf_counter() - self._oldest_t

        out: Dict[int, tuple] = {}
        # The ids link every request, by ticket, to the drain that served it.
        with self._span("drain", first_ticket=first, requests=n, keys=keys):
            for lo, hi, span_starts, span_ops in _order_spans(starts, ops, n):
                if span_ops[0] in _WRITE_SET:
                    lengths = _run_lengths(span_starts, hi).tolist()
                    each_op = [op for op, m in zip(span_ops, lengths) for _ in range(m)]
                    self._serve_write_span(first + lo, each_op, a[lo:hi],
                                           [b.get(i) for i in range(lo, hi)], out)
                else:
                    self._serve_read_span(first, lo, hi, span_starts, span_ops, a, b, out)
            # Freeing the served requests costs about as much as packing
            # them: free them inside the span, which then holds all of it.
            del a, b, starts, ops
        return out

    def _serve_read_span(self, first: int, lo: int, hi: int, starts: list, ops: list,
                         a: list, b: Dict[int, np.ndarray], out: Dict[int, tuple]):
        """Requests ``lo:hi`` of the queue ``a``, a writeless span whose op
        runs start at ``starts`` (request ``i`` holds ticket ``first + i``):
        requests commute, so pack per op kind."""
        with self._span("pack"):
            if len(ops) == 1:  # one run: one group, a slice of the queue
                groups = [(ops[0], range(first + lo, first + hi),
                           a if hi - lo == len(a) else a[lo:hi], range(lo, hi))]
            else:  # the runs of each op kind, expanded to requests in C
                lengths = _run_lengths(starts, hi)
                run_ops = np.array(ops, dtype=object)
                groups = []
                for op in dict.fromkeys(ops):  # in order of first appearance
                    idx = np.flatnonzero(np.repeat(run_ops == op, lengths)) + lo
                    at = idx.tolist()
                    groups.append((op, (idx + first).tolist(), list(map(a.__getitem__, at)), at))
            streams = [
                (op, tickets, lows, np.concatenate(lows),
                 np.concatenate(list(map(b.__getitem__, at))) if op in RANGE_OPS else None)
                for op, tickets, lows, at in groups
            ]
        for op, tickets, lows, a_all, b_all in streams:
            columns = self._serve_stream(op, a_all, b_all)
            with self._span("unpack"):
                self._unpack(tickets, lows, columns, out)

    def _unpack(self, tickets, lows: list, columns, out: Dict[int, tuple]):
        """Hand each request of one op group its slice of the result columns.

        Where every request holds one key (or one range), each answer is a
        row of the columns: a tuple of shape-``(1, ...)`` views, the shape,
        dtype and values that slicing gives, built in C.  A group with a
        request of any other size is sliced request by request.
        """
        n = len(tickets)
        if columns[0].shape[0] == n and min(map(len, lows)) == 1:
            rows = (col.reshape((n, 1) + col.shape[1:]) for col in columns)
            out.update(zip(tickets, zip(*rows)))
            self.stats.row_answers += n
            return
        lo = 0
        for ticket, x in zip(tickets, lows):
            hi = lo + x.size
            out[ticket] = tuple(col[lo:hi] for col in columns)
            lo = hi

    def _serve_write_span(self, first: int, ops: List[str], a: list, b: list,
                          out: Dict[int, tuple]):
        """One run of consecutive write/delete requests -> delta ingest
        (request ``i`` holds ticket ``first + i``).

        Consecutive mutations merge into a single submission-ordered batch
        (the buffer's last-wins dedup preserves exactly that order), padded
        to the fixed ``write_chunk`` jit shape.  Engine-side compaction may
        swap the snapshot between chunks; the server then re-warms the jit
        cache so later read chunks stay compile-free.
        """
        keys = np.concatenate(a)
        values = np.concatenate(
            [v if op == "write" else np.zeros(k.size, np.int32) for op, k, v in zip(ops, a, b)]
        )
        deletes = np.concatenate([np.full(k.size, op == "delete") for op, k in zip(ops, a)])
        pad = (-keys.size) % self._write_chunk
        valid = np.ones(keys.size + pad, bool)
        if pad:
            valid[keys.size:] = False
            keys = np.pad(keys, (0, pad))
            values = np.pad(values, (0, pad))
            deletes = np.pad(deletes, (0, pad))
        before = self._engine.compactions
        n_calls = keys.size // self._write_chunk
        t0 = time.perf_counter()
        # A compaction inside apply_ops is a ``compact`` span of its own.
        with self._span("ingest", chunks=n_calls):
            # One engine call per _write_chunk slice: every ingest reuses the
            # single compiled program regardless of span size (the engine
            # only re-slices by its own capacity, which may be larger).
            for lo in range(0, keys.size, self._write_chunk):
                sl = slice(lo, lo + self._write_chunk)
                self._engine.apply_ops(keys[sl], values[sl], deletes[sl], valid[sl])
            # dispatch is async: sync on the buffer so busy_s measures the
            # ingest compute, exactly as _serve_stream syncs on query results
            jax.block_until_ready(self._engine.delta)
        dt = time.perf_counter() - t0
        n = int(valid.sum())
        self.stats.busy_s += dt
        self.stats.updates += n
        self.stats.served += n
        self.stats.chunks += n_calls
        swept = self._engine.compactions - before
        self.stats.compactions += swept
        if swept:
            self._rewarm()
        self.stats.lanes += n
        for ticket, op, k in zip(range(first, first + len(ops)), ops, a):
            op_stats = self.stats.op(op)
            op_stats.served += k.size
            # Busy attribution is by the lanes the request actually
            # occupied in the span's engine calls (one per write/delete
            # key; ``n`` counts every occupied lane in the span, so shares
            # sum to exactly ``dt`` and padding cost is borne
            # proportionally -- a request's op kind never skews it).
            op_stats.busy_s += dt * (k.size / max(n, 1))
            op_stats.lanes += k.size
            out[ticket] = (np.asarray(k.size, np.int32),)
        for kind in set(ops):
            # a mixed span's engine calls served both kinds; each kind
            # records every call it rode in (same rule as busy_s sharing)
            self.stats.op(kind).chunks += n_calls

    def _empty_columns(self, op: str):
        """Result columns for a zero-key stream (no engine call needed)."""
        if op == "lookup":
            return [np.empty(0, np.int32), np.empty(0, bool)]
        if op in ("predecessor", "successor"):
            return [np.empty(0, np.int32), np.empty(0, np.int32), np.empty(0, bool)]
        if op == "range_count":
            return [np.empty(0, np.int32)]
        k = self.scan_k
        return [
            np.empty((0, k), np.int32),
            np.empty((0, k), np.int32),
            np.empty(0, np.int32),
        ]

    def _serve_stream(self, op: str, a: np.ndarray, b: Optional[np.ndarray]):
        """Run one op's packed stream through fixed-shape engine chunks.

        One depth-2 pipeline, on one chip and on a mesh alike (DESIGN.md
        §9): chunk ``i+1`` is dispatched while chunk ``i`` is in flight,
        and each chunk is retired -- ``sync``, then ``fetch`` -- one chunk
        behind.  ``busy_s`` is the host time inside each chunk's dispatch,
        sync and fetch spans (one thread: they never overlap, so the sum
        counts nothing twice).  Padded lanes never reach results or
        counters.
        """
        B = a.size
        if B == 0:
            return self._empty_columns(op)
        size = self.chunk_size
        pad = (-B) % size
        if pad:
            with self._span("pack"):
                a = np.pad(a, (0, pad))
                if b is not None:
                    b = np.pad(b, (0, pad))
        stats = self.stats
        columns = None
        busy = 0.0
        flight = None  # (chunk, slice, result) of the chunk in flight
        for chunk, lo in enumerate(range(0, a.size, size), stats.chunks):
            sl = slice(lo, lo + size)
            t0 = time.perf_counter()
            with self._span("dispatch", chunk=chunk, lanes=min(size, B - lo)):
                res = self._query_chunk(op, a[sl], None if b is None else b[sl])
            busy += time.perf_counter() - t0
            if flight is not None:
                stats.overlapped_chunks += 1
                columns, dt = self._retire(columns, a.size, *flight)
                busy += dt
            flight = (chunk, sl, res)
        columns, dt = self._retire(columns, a.size, *flight)
        busy += dt
        n_chunks = a.size // size
        # range requests occupy TWO engine lanes each: the lo||hi
        # concatenated descent (DESIGN.md §6)
        per = 2 if op in RANGE_OPS else 1
        if self._squery is not None:
            stats.routed_slots += n_chunks * self._squery.routed_slots(size * per)
        if op == "lookup":
            stats.found += int(columns[1][:B].sum())
        for s in (stats, stats.op(op)):
            s.busy_s += busy
            s.chunks += n_chunks
            s.lanes += B * per
            s.served += B
        return [col[:B] for col in columns]

    def _retire(self, columns, total: int, chunk: int, sl: slice, res: tuple):
        """Wait for one chunk and fetch it into the stream-sized host
        columns; returns the columns and the host seconds of both spans.

        The ONLY place read results cross device->host: one sanctioned
        ``device_fetch`` per chunk (the retire budget the runtime gate
        asserts -- DESIGN.md §10); found counts and per-request slices all
        read the fetched host columns afterwards.
        """
        t0 = time.perf_counter()
        with self._span("sync", chunk=chunk):
            jax.block_until_ready(res)
        with self._span("fetch", chunk=chunk):
            if columns is None:
                columns = [np.empty((total,) + c.shape[1:], c.dtype) for c in res]
            for col, c in zip(columns, analysis_runtime.device_fetch(res)):
                col[sl] = c
        return columns, time.perf_counter() - t0

    # ------------------------------------------------------------ convenience
    def lookup(self, request_keys) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous convenience: submit one request and drain the queue."""
        ticket = self.submit(request_keys)
        return self.drain()[ticket]

    def predecessor(self, request_keys):
        ticket = self.submit(request_keys, op="predecessor")
        return self.drain()[ticket]

    def successor(self, request_keys):
        ticket = self.submit(request_keys, op="successor")
        return self.drain()[ticket]

    def range_count(self, lo, hi) -> np.ndarray:
        ticket = self.submit_range(lo, hi, op="range_count")
        return self.drain()[ticket][0]

    def range_scan(self, lo, hi):
        ticket = self.submit_range(lo, hi, op="range_scan")
        return self.drain()[ticket]

    def write(self, request_keys, request_values) -> int:
        """Synchronous upsert: submit one write request and drain."""
        ticket = self.submit_write(request_keys, request_values)
        return int(self.drain()[ticket][0])

    def delete(self, request_keys) -> int:
        """Synchronous delete: submit one tombstone request and drain."""
        ticket = self.submit_delete(request_keys)
        return int(self.drain()[ticket][0])

    # ------------------------------------------------------------- accounting
    def reset_stats(self) -> None:
        self.stats = ServerStats()

    def memory_nodes(self) -> int:
        return self._engine.memory_nodes()

    def memory_nodes_per_device(self) -> int:
        """Stored key slots on the fullest device, MEASURED from the real
        shard layout in sharded mode (DESIGN.md §9's capacity figure;
        falls back to the snapshot's node count single-chip)."""
        if self._squery is not None:
            return int(self._squery.device_nodes)
        return int(self._engine.tree.n_nodes)
