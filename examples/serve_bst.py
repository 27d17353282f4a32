"""End-to-end driver: serve a BST key-value store with batched requests.

    PYTHONPATH=src python examples/serve_bst.py [--requests 200000]

This is the paper-kind end-to-end scenario (a throughput accelerator): a
request stream is submitted to ``serving.BSTServer``, which packs it into
fixed-shape chunks, dispatches them through the engine configured with each
of the paper's strategies, and reports keys answered per second of wall
time (found counts accumulated per chunk) and where the host time went.
An ordered-workload mix (predecessor / range_count / range_scan request
kinds, DESIGN.md §6) exercises the typed-request scheduler with per-op
accounting.  A LIVE mixed read/write stream
(``--write-rate``) then runs through the delta write path (DESIGN.md §7):
upserts and deletes land in the engine's device-side buffer via
``submit_write`` / ``submit_delete`` in submission order, and compaction
merges them into fresh snapshots at the high-water mark -- no full
rebuilds.  A bulk insert/delete then swaps in a fresh immutable snapshot
the legacy way.  The distributed section demonstrates the multi-chip
hybrid engine: the tree vertically partitioned over a (data, model) mesh,
keys routed by the queue-mapped all_to_all (8 simulated devices), serving
the same ``query(op, ...)`` contract.  The final section scales the SERVER
itself out (DESIGN.md §9): ``BSTServer(mesh=...)`` routes every chunk
through the strategy's shard_map-lowered plan in the server's depth-2
chunk loop, live writes included -- the pending delta
buffer rides each sharded read as replicated operands and compactions
rebuild the sharded programs mid-service.
"""

import os

# Eight simulated devices for the multi-chip sections on a CPU host.  The
# flag sizes the host platform only: a TPU host keeps its real chips.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import PAPER_CONFIGS, build_tree
from repro.core.distributed import (
    make_distributed_query,
    make_dup_query,
    make_serving_mesh,
)
from repro.data.keysets import make_tree_data
from repro.serving import BSTServer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200_000)
    ap.add_argument("--chunk", type=int, default=8_192)
    ap.add_argument("--tree-keys", type=int, default=(1 << 16) - 1)
    ap.add_argument(
        "--write-rate",
        type=float,
        default=0.1,
        help="fraction of the live mixed stream that is writes (DESIGN.md §7)",
    )
    args = ap.parse_args()

    keys, values = make_tree_data(args.tree_keys, seed=0)
    rng = np.random.default_rng(1)
    stream = rng.choice(keys, args.requests).astype(np.int32)

    print(f"serving {args.requests} lookups in chunks of {args.chunk}")
    print(f"{'impl':8s} {'keys/s':>12s} {'found':>10s} {'memory(nodes)':>14s}")
    for name, cfg in PAPER_CONFIGS.items():
        srv = BSTServer(keys, values, cfg, chunk_size=args.chunk)
        srv.warmup()
        t0 = time.perf_counter()
        srv.submit(stream)
        srv.drain()
        dt = time.perf_counter() - t0
        s = srv.stats
        print(
            f"{name:8s} {s.served / dt:12.0f} {s.found:10d} "
            f"{srv.memory_nodes():14d}"
        )

    # ---- ordered workload mix: typed request kinds, per-op accounting
    srv = BSTServer(keys, values, PAPER_CONFIGS["Hyb8q"], chunk_size=args.chunk)
    srv.warmup(("predecessor", "range_count", "range_scan"))
    n_ord = max(args.chunk, args.requests // 8)
    ord_keys = rng.choice(np.concatenate([keys, keys + 1]), n_ord).astype(np.int32)
    lo = rng.choice(keys, n_ord).astype(np.int32)
    hi = (lo + rng.integers(0, 64, n_ord)).astype(np.int32)
    srv.submit(ord_keys, op="predecessor")
    srv.submit_range(lo, hi, op="range_count")
    srv.submit_range(lo, hi, op="range_scan")
    srv.drain()
    print("\nordered workload mix (Hyb8q):")
    print(f"{'op':12s} {'served':>10s} {'chunks':>7s} {'busy ms':>10s}")
    for op, st in srv.stats.per_op.items():
        print(f"{op:12s} {st.served:10d} {st.chunks:7d} {st.busy_s * 1e3:10.1f}")
    s = srv.stats
    print("host ms by phase: " + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in s.phase_s.items())
          + f"; row answers {s.row_answers} of {s.requests} requests, {s.op_runs} op runs")

    # ---- live write path: delta-buffered updates, compaction, no rebuilds
    cfg = dataclasses.replace(PAPER_CONFIGS["Hyb8q"], delta_capacity=4096)
    srv = BSTServer(keys, values, cfg, chunk_size=args.chunk)
    srv.warmup()
    n_live = max(args.chunk, args.requests // 4)
    n_w = int(n_live * args.write_rate)
    wk = rng.integers(1, 2**20, n_w).astype(np.int32)
    reads = rng.choice(np.concatenate([keys, wk]), n_live - n_w).astype(np.int32)
    t0 = time.perf_counter()
    half = n_w // 2
    srv.submit_write(wk[:half], wk[:half] * 3)  # upserts ...
    srv.submit(reads[: reads.size // 2])  # ... reads see them after the barrier
    srv.submit_delete(wk[:half:7])  # tombstones ride the same queue
    srv.submit_write(wk[half:], wk[half:] * 3)
    srv.submit(reads[reads.size // 2 :])
    srv.drain()
    dt = time.perf_counter() - t0
    s = srv.stats
    print(
        f"\nlive write path (Hyb8q, {args.write_rate:.0%} writes): "
        f"{s.served / dt:.0f} keys/s end-to-end, {s.updates} updates absorbed "
        f"on device, {s.compactions} compaction(s), 0 rebuilds"
    )
    print("  host ms by phase: " + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in s.phase_s.items())
          + f"; row answers {s.row_answers} of {s.requests} requests, {s.op_runs} op runs")
    v, f = srv.lookup(wk[half + 1 : half + 9])
    print(f"  post-write lookups: found {int(np.asarray(f).sum())}/8 fresh keys")

    # ---- snapshot swap: bulk updates land between chunk streams
    srv = BSTServer(keys, values, PAPER_CONFIGS["Hyb8q"], chunk_size=args.chunk)
    new_keys = np.arange(1, 2_001, 2, dtype=np.int32)  # odd keys: all absent
    srv.apply_updates(
        insert_keys=new_keys,
        insert_values=new_keys * 10,
        delete_keys=keys[:1000],
    )
    v, f = srv.lookup(new_keys)
    dead_v, dead_f = srv.lookup(keys[:1000])
    print(
        f"\nsnapshot swap: inserted {new_keys.size} (found {int(f.sum())}), "
        f"deleted 1000 (still found {int(dead_f.sum())}), "
        f"{srv.stats.snapshot_swaps} swap(s)"
    )

    if len(jax.devices()) < 8:
        print(f"\nmulti-device sections skipped: {len(jax.devices())} device(s), need 8")
        return

    # ---- multi-chip: vertical partitioning over the model axis
    print("\ndistributed hybrid engine (8 devices, 2x4 data x model mesh):")
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "model"))
    tree = build_tree(keys, values)
    chunks = [
        stream[i : i + args.chunk] for i in range(0, len(stream), args.chunk)
    ][:8]
    if len(chunks[-1]) != args.chunk:  # pad the final partial chunk (jit shape)
        chunks[-1] = np.pad(chunks[-1], (0, args.chunk - len(chunks[-1])))
    with mesh:
        for label, maker in (
            ("vertical(all_to_all)", lambda: make_distributed_query(tree, mesh, "model")),
            ("duplicated(DP)", lambda: make_dup_query(tree, mesh, "data")),
        ):
            query = maker()
            jax.block_until_ready(query("lookup", chunks[0]))
            t0 = time.perf_counter()
            for c in chunks:
                v, f = query("lookup", c)
            jax.block_until_ready(v)
            dt = time.perf_counter() - t0
            print(f"  {label:22s} {len(chunks) * args.chunk / dt:12.0f} keys/s")
            # the same handle serves ordered ops (predecessor shown)
            pk, pv, ok = query("predecessor", chunks[0])
            print(f"  {'':22s} predecessor ok for {int(np.asarray(ok).sum())} keys")

    # ---- sharded serving: the server itself over the mesh (DESIGN.md §9)
    print("\nsharded BSTServer (8 devices, depth-2 chunk loop):")
    print(f"{'strategy':10s} {'keys/s':>12s} {'chunks':>7s} {'found':>10s}")
    n_srv = max(args.chunk * 4, args.requests // 4)
    srv_stream = rng.choice(keys, n_srv).astype(np.int32)
    for strategy, n_trees in (("hrz", 1), ("dup", 8), ("hyb", 8)):
        cfg = dataclasses.replace(
            PAPER_CONFIGS["Hyb8q" if strategy == "hyb" else "Hrz"],
            strategy=strategy,
            n_trees=n_trees,
        )
        srv = BSTServer(
            keys, values, cfg, chunk_size=args.chunk,
            mesh=make_serving_mesh(strategy),
        )
        srv.warmup()
        t0 = time.perf_counter()
        srv.submit(srv_stream)
        srv.drain()
        dt = time.perf_counter() - t0
        s = srv.stats
        print(f"{strategy:10s} {s.served / dt:12.0f} {s.chunks:7d} {s.found:10d}"
              f"  (router: {s.routed_slots} slots for {s.lanes} lanes, "
              f"{s.overlapped_chunks} chunks overlapped)")

    # live writes through the sharded hybrid server: the delta buffer rides
    # every sharded read as replicated operands, folded on-device
    cfg = dataclasses.replace(
        PAPER_CONFIGS["Hyb8q"], delta_capacity=4096
    )
    srv = BSTServer(
        keys, values, cfg, chunk_size=args.chunk, mesh=make_serving_mesh("hyb")
    )
    srv.warmup()
    wk = rng.integers(1, 2**20, args.chunk).astype(np.int32)
    srv.submit_write(wk, wk * 5)
    srv.submit(wk[: args.chunk // 2])
    srv.drain()
    v, f = srv.lookup(wk[:8])
    print(
        f"  sharded write path: {srv.stats.updates} updates absorbed, "
        f"{int(np.asarray(f).sum())}/8 fresh keys found, "
        f"{srv.stats.compactions} compaction(s)"
    )


if __name__ == "__main__":
    main()
