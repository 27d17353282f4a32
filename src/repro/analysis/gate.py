"""Runtime retrace/transfer gate: steady-state serving is compile- and
transfer-free (DESIGN.md §10).

``serve_gate(strategy)`` drives a real ``BSTServer`` drain -- kernel path,
sharded through the strategy's serving mesh -- through a WARM phase (every
program the workload needs compiles exactly once: read programs via
``warmup``, the write-ingest program via one write drain) and then a
MEASURED phase under ``runtime.compile_watch()`` +
``runtime.transfer_watch()``:

  * >= ``n_chunks`` fixed-shape chunks drain per op, with small writes
    interleaved between read drains so the delta buffer's CONTENT changes
    while every shape stays constant -- the exact situation where a
    content-dependent-shape bug retraces;
  * zero compile records: every chunk replayed a cached program;
  * zero implicit transfers: the ``transfer_guard`` raises on any
    unplanned host->device movement, and the sanctioned ``device_fetch``
    count must equal the drain's exact retire budget (one fetch per read
    chunk -- ``BSTServer._retire`` -- and nothing else);
  * zero compactions: the config pins ``delta_high_water`` to the
    capacity and writes far fewer entries, so the measured phase never
    pays the allowlisted one-sync-per-compaction.

Imports serving lazily so ``repro.analysis`` stays import-light for the
production modules that depend on ``invariants``/``runtime``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from repro.analysis import runtime
from repro.analysis.report import Violation

# Ops exercised by the gate: both point shapes, a range op (the lo||hi
# doubled-lane trace) -- each op is its own compiled program family.
GATE_OPS: Tuple[str, ...] = ("lookup", "predecessor", "range_scan")

_N_KEYS = 63
_CHUNK = 64
_DELTA_CAP = 64


def _violation(strategy: str, msg: str) -> Violation:
    return Violation("GAT001", f"serve-gate:{strategy}", 0, msg)


def serve_gate(
    strategy: str,
    *,
    n_chunks: int = 3,
    ops: Sequence[str] = GATE_OPS,
    n_trees: int = 4,
) -> List[Violation]:
    """Gate one strategy's steady-state drain; returns violations (empty =
    pass)."""
    from repro.core import distributed as dist_lib
    from repro.core.engine import EngineConfig
    from repro.serving.bst_server import BSTServer

    errors: List[Violation] = []
    keys = np.arange(1, _N_KEYS + 1, dtype=np.int32) * 3
    cfg = EngineConfig(
        strategy=strategy,
        n_trees=1 if strategy == "hrz" else n_trees,
        use_kernel=True,
        # High water == capacity and the measured writes stay far below it:
        # no compaction (and no sanctioned compaction sync) in the gate.
        delta_capacity=_DELTA_CAP,
        delta_high_water=_DELTA_CAP,
    )
    mesh = dist_lib.make_serving_mesh(strategy)
    srv = BSTServer(
        keys, keys * 7, cfg, chunk_size=_CHUNK, scan_k=4, mesh=mesh
    )

    # ---- warm phase: compile every program the measured phase replays.
    srv.warmup(tuple(ops))
    srv.submit_write(np.int32([keys[1], keys[3]]), np.int32([1, 3]))
    srv.drain()

    # ---- measured phase.
    compactions_before = srv.stats.compactions
    rng = np.random.default_rng(19120156)
    expected_fetches = 0
    with runtime.compile_watch() as cw, runtime.transfer_watch() as tw:
        for round_no in range(2):
            # Delta CONTENT changes between rounds; every shape constant.
            srv.submit_write(
                np.int32([keys[5 + round_no], keys[9 + round_no]]),
                np.int32([round_no, round_no + 1]),
            )
            srv.drain()
            for op in ops:
                B = n_chunks * _CHUNK
                q = rng.integers(0, keys[-1] + 2, size=B).astype(np.int32)
                if op in ("range_count", "range_scan"):
                    srv.submit_range(q, q + 17, op=op)
                else:
                    srv.submit(q, op=op)
                srv.drain()
                expected_fetches += n_chunks  # one device_fetch per chunk
    if cw.count:
        progs = "; ".join(cw.messages()[:4])
        errors.append(
            _violation(
                strategy,
                f"steady-state drain compiled {cw.count} program(s) -- "
                f"retrace detected: {progs}",
            )
        )
    if tw.fetches != expected_fetches:
        errors.append(
            _violation(
                strategy,
                f"{tw.fetches} sanctioned device fetches, budget is "
                f"{expected_fetches} (one per retired read chunk) -- an "
                "unplanned device->host sync crept onto the hot path",
            )
        )
    swept = srv.stats.compactions - compactions_before
    if swept:
        errors.append(
            _violation(
                strategy,
                f"{swept} compaction(s) fired in the measured phase -- the "
                "gate's write volume must stay below the high-water mark",
            )
        )
    return errors


def run_serve_gates(
    strategies: Sequence[str] = ("hrz", "dup", "hyb"), *, n_chunks: int = 3
) -> List[Violation]:
    errors: List[Violation] = []
    for strategy in strategies:
        errors.extend(serve_gate(strategy, n_chunks=n_chunks))
    return errors
