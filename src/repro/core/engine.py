"""BSTEngine: the TPU-native query engine with the paper's three strategies.

Strategies (paper §II):
  * ``hrz``   -- horizontal partitioning.  One tree, level-major layout, the
                 whole query chunk descends one level per step: the SIMD
                 rendition of the FPGA's level pipeline.
  * ``dup``   -- duplicated horizontal partitioning.  ``n_trees`` replicas;
                 on one chip this splits the chunk across replicas (pure
                 bandwidth trade), across chips it becomes data parallelism.
  * ``hyb``   -- hybrid horizontal-vertical partitioning.  The top
                 ``register_levels`` levels are a broadcast "register layer";
                 survivors are routed to ``n_trees`` vertical subtrees through
                 direct- or queue-mapped buffers and descend locally.

All strategies return bit-identical results (property-tested); they differ in
memory layout, dispatch traffic and -- in the distributed engine -- collective
pattern.  Functional equivalence is exactly the paper's situation: every
implementation finds the same keys, only throughput differs.

The engine itself is a thin driver: each strategy compiles to a
``core.plans.SearchPlan`` whose phase implementations (route / dispatch /
descend / combine) are shared verbatim with ``core/distributed.py``, and
whose descent lowers to the single forest-batched Pallas kernel when
``use_kernel=True`` (DESIGN.md §2, §4).

The entry point is ``query(op, ...)`` -- one API for the whole ordered-query
workload family (DESIGN.md §6): ``lookup``, ``predecessor``, ``successor``,
``range_count`` and ``range_scan`` all lower through the same plan phases
and the same kernel; ``lookup()`` remains as the membership shorthand.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import invariants
from repro.core import delta as delta_lib
from repro.core import plans as plans_lib
from repro.core import tree as tree_lib
from repro.core import updates as updates_lib
from repro.core.tree import TreeData


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Compile-time reconfigurable parameters (paper: "fully reconfigurable")."""

    strategy: str = "hrz"  # hrz | dup | hyb
    n_trees: int = 1  # replicas (dup) or vertical subtrees (hyb)
    mapping: str = "queue"  # direct | queue   (hyb only)
    register_levels: Optional[int] = None  # default: log2(n_trees) for hyb
    # Buffer capacity per subtree as a multiple of the fair share B/n_trees.
    buffer_slack: float = 2.0
    use_kernel: bool = False  # route descent through the Pallas forest kernel
    # Live write path (DESIGN.md §7): > 0 attaches a delta buffer of that
    # many slots to every query, enabling device-side apply_updates with
    # bulk compaction at the high-water mark.  0 keeps the engine read-only
    # (updates then mean a full snapshot rebuild, the pre-§7 story).
    delta_capacity: int = 0
    delta_high_water: Optional[int] = None  # default: 3/4 of the capacity

    def __post_init__(self) -> None:
        # Shared with repro.analysis.contracts: the checker verifies the
        # same bounds statically, so neither side can drift (DESIGN.md §10).
        invariants.check_delta_config(self.delta_capacity, self.delta_high_water)

    def resolved_register_levels(self) -> int:
        return plans_lib.resolved_register_levels(self.n_trees, self.register_levels)

    def resolved_high_water(self) -> int:
        return invariants.resolved_high_water(
            self.delta_capacity, self.delta_high_water
        )

    @property
    def name(self) -> str:
        if self.strategy == "hrz":
            return "Hrz"
        if self.strategy == "dup":
            return f"Dup{self.n_trees}"
        suffix = "q" if self.mapping == "queue" else ""
        return f"Hyb{self.n_trees}{suffix}"


# Preset configurations matching the paper's evaluated implementations.
PAPER_CONFIGS = {
    "Hrz": EngineConfig(strategy="hrz"),
    "Dup4": EngineConfig(strategy="dup", n_trees=4),
    "Dup8": EngineConfig(strategy="dup", n_trees=8),
    "Hyb4": EngineConfig(strategy="hyb", n_trees=4, mapping="direct"),
    "Hyb4q": EngineConfig(strategy="hyb", n_trees=4, mapping="queue"),
    "Hyb8": EngineConfig(strategy="hyb", n_trees=8, mapping="direct"),
    "Hyb8q": EngineConfig(strategy="hyb", n_trees=8, mapping="queue"),
}


def _no_span(name: str) -> contextlib.AbstractContextManager:
    return contextlib.nullcontext()


class BSTEngine:
    """Build once, look up batches of keys many times."""

    def __init__(self, keys, values, config: EngineConfig = EngineConfig()):
        self.config = config
        self.tree = tree_lib.build_tree(np.asarray(keys), np.asarray(values))
        self._finalize()

    @classmethod
    def from_tree(cls, tree: TreeData, config: EngineConfig = EngineConfig(), sharded=None):
        """Wrap an existing immutable snapshot (serving's bulk-update swap);
        ``sharded`` is the serving layer's sharded reader of it, if any."""
        self = cls.__new__(cls)
        self.config = config
        self.tree = tree
        self.sharded = sharded
        self._finalize()
        return self

    # ------------------------------------------------------------------ build
    def _finalize(self) -> None:
        cfg = self.config
        # Sharded-reader hook (DESIGN.md §9): the serving layer's
        # ``make_sharded_query`` run over this snapshot, where a mesh is
        # installed.  Writes then classify through its sharded descent and
        # compact through it, and the one-chip plan (a whole-index copy on
        # one device) is not built.  None by default.
        self.sharded = getattr(self, "sharded", None)
        self._plan = None if self.sharded is not None else self._make_plan()
        self._query_cache: Dict[Tuple[str, int], callable] = {}
        # Live write path (DESIGN.md §7): a fresh empty buffer per snapshot.
        empty = delta_lib.empty if self.sharded is None else self.sharded.empty_delta
        self.delta = empty(cfg.delta_capacity) if cfg.delta_capacity > 0 else None
        self._ingest = jax.jit(self._ingest_step) if self.delta is not None else None
        # Host-side occupancy upper bound (<= sum of batch sizes since the
        # last compaction): the compaction trigger never syncs the device
        # count scalar, at the cost of compacting a touch early.
        self._pending_writes = 0
        self.compactions = getattr(self, "compactions", 0)
        # Snapshot-swap hook (DESIGN.md §9): a swap can fire deep inside
        # apply_ops' chunk loop (compaction) or in apply_updates' bulk
        # rebuild, and anything compiled against the OLD snapshot (the
        # sharded server's shard_map programs) must rebuild before the next
        # read.  Called with the fresh TreeData after EVERY snapshot swap;
        # None by default.
        self.on_snapshot = getattr(self, "on_snapshot", None)
        # Span hook: ``span("compact")`` wraps each compaction; the serving
        # layer installs its own, so compaction time lands in its phase
        # counters and the profiler trace.  No span by default.
        self.span = getattr(self, "span", _no_span)

    def _make_plan(self) -> plans_lib.SearchPlan:
        cfg = self.config
        return plans_lib.make_plan(
            self.tree,
            strategy=cfg.strategy,
            n_trees=cfg.n_trees,
            mapping=cfg.mapping,
            register_levels=cfg.register_levels,
            buffer_slack=cfg.buffer_slack,
        )

    @property
    def plan(self) -> plans_lib.SearchPlan:
        """The snapshot's one-chip search plan; a sharded engine builds it
        only if asked (its reads and writes never are)."""
        if self._plan is None:
            self._plan = self._make_plan()
        return self._plan

    # ------------------------------------------------------------------ query
    def query(self, op: str, queries, queries_hi=None, *, k: int = 8):
        """Run one query op over a 1-D int32 batch (DESIGN.md §6).

        * ``query("lookup", q)``            -> (values, found)
        * ``query("predecessor", q)``       -> (keys, values, ok): floor(q)
        * ``query("successor", q)``         -> (keys, values, ok): ceiling(q)
        * ``query("range_count", lo, hi)``  -> counts of keys in [lo, hi]
        * ``query("range_scan", lo, hi, k=8)`` -> (keys (B, k), values,
          counts): the first ``k`` in-order pairs per range.

        One jitted function per (op, k) -- every op runs the same plan
        phases and the single forest-batched descent.
        """
        plans_lib.validate_op(op, queries_hi is not None)
        # k shapes only range_scan's epilogue; other ops share one cache slot
        # so varying k cannot trigger redundant retraces.
        key = (op, k) if op == "range_scan" else (op, None)
        fn = self._query_cache.get(key)
        if fn is None:
            fn = jax.jit(
                functools.partial(
                    plans_lib.ordered_query,
                    self.plan,
                    op,
                    k=k,
                    use_kernel=self.config.use_kernel,
                )
            )
            self._query_cache[key] = fn
        queries = jnp.asarray(queries, dtype=jnp.int32)
        # The delta buffer is a traced argument (its arrays change per write
        # batch but never in shape), so writes do not retrace queries.
        kw = {} if self.delta is None else {"delta": self.delta}
        if op in plans_lib.RANGE_OPS:
            return fn(queries, jnp.asarray(queries_hi, dtype=jnp.int32), **kw)
        return fn(queries, **kw)

    # ----------------------------------------------------------------- lookup
    def lookup(self, queries) -> Tuple[jax.Array, jax.Array]:
        """(values, found) for a 1-D int32 query batch."""
        return self.query("lookup", queries)

    # ------------------------------------------------------------------ write
    def _ingest_step(self, delta, keys, values, deletes, valid):
        """One write-batch ingest (jitted in ``_finalize``; jax caches one
        trace per batch shape automatically).

        The batch descends the engine's OWN datapath (same plan, same
        kernel/reference choice as queries) to classify each key against
        the snapshot, then merges into the sorted buffer -- pure jnp end
        to end, so updates never leave the device (DESIGN.md §7).
        """
        res = plans_lib.execute_plan_ordered(
            self.plan,
            keys,
            use_kernel=self.config.use_kernel,
        )
        return delta_lib.ingest(
            delta, keys, values, deletes, valid, res.found, res.rank
        )

    def apply_ops(self, keys, values, deletes, valid=None) -> None:
        """Apply a mixed batch of upserts/tombstones in submission order.

        ``keys``/``values`` are int32 arrays, ``deletes`` a bool mask
        (True = tombstone; the value lane is ignored), ``valid`` an
        optional bool mask for padding lanes (fixed jit shapes upstream).
        Requires ``delta_capacity > 0``.  The buffer absorbs the batch on
        device; a batch larger than the capacity is chunked through
        interleaved compactions (every chunk's valid-lane count fits the
        buffer by construction, and a compaction runs before any chunk
        that would push occupancy past the capacity), so a single
        oversized batch can never overflow the buffer between triggers.
        The high-water mark additionally compacts after the batch -- never
        mid-chunk, so readers always see a consistent snapshot + buffer
        pair.
        """
        if self.delta is None:
            raise ValueError(
                "write path disabled (delta_capacity == 0): construct the "
                "engine with EngineConfig(delta_capacity > 0), or use "
                "core.updates bulk maintenance + snapshot swap"
            )
        keys = np.atleast_1d(np.asarray(keys, np.int32))
        values = np.atleast_1d(np.asarray(values, np.int32))
        deletes = np.atleast_1d(np.asarray(deletes, bool))
        if not (keys.shape == values.shape == deletes.shape) or keys.ndim != 1:
            raise ValueError("keys/values/deletes must be equal-length 1-D")
        valid = (
            np.ones(keys.shape, bool)
            if valid is None
            else np.atleast_1d(np.asarray(valid, bool))
        )
        if valid.shape != keys.shape:
            raise ValueError("valid mask must match the batch shape")
        cap = self.config.delta_capacity
        high = self.config.resolved_high_water()
        for lo in range(0, keys.size, cap):
            sl = slice(lo, lo + cap)
            m = int(valid[sl].sum())  # <= cap: the slice is cap lanes long
            if m == 0:
                continue
            if self._pending_writes + m > cap:
                self.compact()
            ingest = self._ingest if self.sharded is None else self.sharded.ingest
            self.delta = ingest(
                self.delta,
                jnp.asarray(keys[sl]),
                jnp.asarray(values[sl]),
                jnp.asarray(deletes[sl]),
                jnp.asarray(valid[sl]),
            )
            # _pending_writes upper-bounds buffer occupancy (ingest dedups,
            # so the true count can only be lower); the invariant the chunk
            # loop maintains is _pending_writes <= cap at every step.
            self._pending_writes += m
            assert self._pending_writes <= cap
        if self._pending_writes >= high:
            self.compact()

    def apply_updates(
        self, insert_keys=None, insert_values=None, delete_keys=None
    ) -> TreeData:
        """Insert/delete convenience over ``apply_ops`` (deletes first, so
        an upsert of a just-deleted key lands -- the historical contract).

        With the write path enabled the batch lands in the delta buffer
        and the snapshot only changes at compaction; without it, falls
        back to ``core.updates`` bulk maintenance (full rebuild).  Returns
        the current snapshot either way.
        """
        dk = np.atleast_1d(np.asarray(delete_keys, np.int32)) if (
            delete_keys is not None and len(np.atleast_1d(delete_keys))
        ) else np.empty(0, np.int32)
        ik = np.atleast_1d(np.asarray(insert_keys, np.int32)) if (
            insert_keys is not None and len(np.atleast_1d(insert_keys))
        ) else np.empty(0, np.int32)
        if ik.size and insert_values is None:
            raise ValueError("insert_keys needs insert_values")
        iv = (
            np.atleast_1d(np.asarray(insert_values, np.int32))
            if ik.size
            else np.empty(0, np.int32)
        )
        if self.delta is None:
            tree = self.tree
            if dk.size:
                tree = updates_lib.bulk_delete(tree, dk)
            if ik.size:
                tree = updates_lib.bulk_insert(tree, ik, iv)
            self.tree = tree
            self._finalize()
            if self.on_snapshot is not None:
                self.on_snapshot(self.tree)
            return tree
        keys = np.concatenate([dk, ik])
        values = np.concatenate([np.zeros(dk.size, np.int32), iv])
        deletes = np.concatenate([np.ones(dk.size, bool), np.zeros(ik.size, bool)])
        if keys.size:
            self.apply_ops(keys, values, deletes)
        return self.tree

    def compact(self) -> TreeData:
        """Absorb the delta buffer into a fresh perfect snapshot.

        Device-side merge + Eytzinger re-layout (one host sync for the new
        key count, which fixes the static height); the plan and jit caches
        rebuild against the new snapshot, and the buffer comes back empty.
        No-op while nothing is buffered.
        """
        if self.delta is None or self._pending_writes == 0:
            return self.tree
        with self.span("compact"):
            compact = delta_lib.compact if self.sharded is None else self.sharded.compact
            self.tree = compact(self.tree, self.delta)
            self.compactions += 1
            self._finalize()
            if self.on_snapshot is not None:
                self.on_snapshot(self.tree)
        return self.tree

    def pending_writes(self) -> int:
        """Upper bound on buffered entries (0 right after a compaction)."""
        return self._pending_writes

    # ------------------------------------------------------------- accounting
    def memory_nodes(self) -> int:
        """Stored nodes (the paper's Fig. 8 memory metric)."""
        return self.plan.memory_nodes()
