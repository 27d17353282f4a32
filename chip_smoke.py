#!/usr/bin/env python3
"""Smoke run of the BST index on a TPU: the served path, end to end, checked.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # sharded servers on four chips

One chip: 2^20 - 1 keys (height 19, 8 MiB of keys and values on the
device) are loaded into ``BSTServer`` for each of the paper presets Hrz,
Dup8, Hyb8q and Hyb8, on the Pallas forest kernel with a 4096-slot write
buffer.  Each server is warmed, then serves chunks of every request kind --
lookups (hits and misses), predecessor, successor, range_count and
range_scan -- before and after a write/delete batch and again after the
compaction a second batch forces.  Every answer must equal a NumPy
``searchsorted`` + ``dict`` host reference and the XLA-gather path
(``use_kernel=False``) on the same chip, bit for bit, and every compiled
query and ingest program must hold a Mosaic kernel (``tpu_custom_call``).

``--chips 4`` runs only the sharded servers (hrz, dup, hyb over four
chips) against a one-chip server on the same submissions.

All data comes from ``--seed``.  The times printed are those of a smoke
run, not a benchmark.  The script exits non-zero, without the result line,
when JAX finds no TPU or any check fails; otherwise its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SMOKE_CONFIGS = ("Hrz", "Dup8", "Hyb8q", "Hyb8")
SHARDED_STRATEGIES = ("hrz", "dup", "hyb")
READ_OPS = ("lookup", "predecessor", "successor", "range_count", "range_scan")
N_KEYS = (1 << 20) - 1
CHUNK = 8192
N_CHUNKS = 2  # chunks per read op and phase
DELTA_CAPACITY = 4096
SCAN_K = 8
MAX_SPAN = 64  # range widths: up to 32 stored keys, past SCAN_K

SENTINEL_KEY = np.iinfo(np.int32).max
NO_PRED_KEY = np.iinfo(np.int32).min
SENTINEL_VALUE = -1


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------------ workload
def make_workload(keys, seed: int, chunk: int, n_chunks: int, delta_capacity: int):
    """The request sequence every server drains: read phases around two
    write/delete batches.  The first batch stays under the buffer's
    high-water mark (reads see it through the delta buffer); the second
    pushes the buffer past it, so the server compacts into a fresh
    snapshot before the last reads."""
    rng = np.random.default_rng(seed)
    n = chunk * n_chunks
    lo_key, hi_key = int(keys[0]), int(keys[-1])

    def reads():
        phase = []
        for op in READ_OPS:
            # stored keys, the gaps between them, and both ends of the range
            a = rng.integers(lo_key - 4, hi_key + 4, size=n).astype(np.int32)
            b = None
            if op.startswith("range"):
                b = (a + rng.integers(0, MAX_SPAN, size=n)).astype(np.int32)
            phase.append(("read", op, a, b))
        return phase

    def writes(n_ops):
        upserts = rng.choice(keys, n_ops // 2, replace=False)
        inserts = (rng.choice(keys, n_ops // 4, replace=False) + 1).astype(np.int32)
        deletes = rng.choice(keys, n_ops // 4, replace=False)
        wk = np.concatenate([upserts, inserts]).astype(np.int32)
        wv = rng.integers(0, 2**31 - 1, size=wk.size, dtype=np.int32)
        return [("write", wk, wv), ("delete", deletes.astype(np.int32))]

    high_water = (3 * delta_capacity) // 4
    first = high_water // 2
    return (
        reads()
        + writes(first)
        + reads()
        + writes(high_water - first + delta_capacity // 8)
        + reads()
    )


def host_answers(keys, values, workload, scan_k: int):
    """The reference answer of every read, from a ``dict`` of the live
    key-value pairs and NumPy ``searchsorted`` over its sorted keys."""
    kv = dict(zip(keys.tolist(), values.tolist()))
    sk = np.asarray(keys, np.int64)
    sv = np.asarray(values, np.int64)
    out = []
    for step in workload:
        if step[0] == "write":
            kv.update(zip(step[1].tolist(), step[2].tolist()))
            sk = None
        elif step[0] == "delete":
            for k in step[1].tolist():
                kv.pop(k, None)
            sk = None
        else:
            if sk is None:
                sk = np.fromiter(sorted(kv), np.int64, len(kv))
                sv = np.fromiter((kv[k] for k in sk.tolist()), np.int64, len(kv))
            out.append(_answer(step[1], step[2], step[3], sk, sv, scan_k))
    return out


def _answer(op, a, b, sk, sv, scan_k):
    n = sk.size
    a = a.astype(np.int64)
    if op == "lookup":
        pos = np.minimum(np.searchsorted(sk, a), n - 1)
        found = sk[pos] == a
        return (np.where(found, sv[pos], SENTINEL_VALUE), found)
    if op == "predecessor":
        pos = np.searchsorted(sk, a, side="right") - 1
        ok = pos >= 0
        at = np.maximum(pos, 0)
        return (
            np.where(ok, sk[at], NO_PRED_KEY),
            np.where(ok, sv[at], SENTINEL_VALUE),
            ok,
        )
    if op == "successor":
        pos = np.searchsorted(sk, a, side="left")
        ok = pos < n
        at = np.minimum(pos, n - 1)
        return (
            np.where(ok, sk[at], SENTINEL_KEY),
            np.where(ok, sv[at], SENTINEL_VALUE),
            ok,
        )
    start = np.searchsorted(sk, a, side="left")
    count = np.maximum(np.searchsorted(sk, b.astype(np.int64), side="right") - start, 0)
    if op == "range_count":
        return (count,)
    take = np.minimum(count, scan_k)
    cols = np.arange(scan_k)
    valid = cols[None, :] < take[:, None]
    at = np.minimum(start[:, None] + cols[None, :], n - 1)
    return (
        np.where(valid, sk[at], SENTINEL_KEY),
        np.where(valid, sv[at], SENTINEL_VALUE),
        take,
    )


# ------------------------------------------------------------------- serving
def drive(srv, workload):
    """Drain the workload through ``srv`` one step at a time; returns the
    read results in order.  Writes go through the typed write/delete
    request kinds, so they land in the device-side delta buffer."""
    out = []
    for step in workload:
        if step[0] == "write":
            srv.submit_write(step[1], step[2])
            srv.drain()
        elif step[0] == "delete":
            srv.submit_delete(step[1])
            srv.drain()
        else:
            _, op, a, b = step
            if b is None:
                ticket = srv.submit(a, op=op)
            else:
                ticket = srv.submit_range(a, b, op=op)
            out.append(srv.drain()[ticket])
    return out


def check_answers(tag: str, got, want, workload) -> None:
    """Every column of every read equal, bit for bit."""
    ops = [step[1] for step in workload if step[0] == "read"]
    for i, (op, g, w) in enumerate(zip(ops, got, want)):
        if len(g) != len(w):
            raise AssertionError(f"{tag}: read {i} ({op}) has {len(g)} columns, want {len(w)}")
        for c, (gc, wc) in enumerate(zip(g, w)):
            gc, wc = np.asarray(gc), np.asarray(wc)
            if gc.shape != wc.shape or not np.array_equal(gc.astype(np.int64), wc.astype(np.int64)):
                bad = np.flatnonzero(
                    gc.reshape(gc.shape[0], -1) != wc.reshape(wc.shape[0], -1)
                ) if gc.shape == wc.shape else []
                raise AssertionError(
                    f"{tag}: read {i} ({op}) column {c} differs "
                    f"({len(bad)} mismatches, first at {list(bad[:4])})"
                )
    if len(got) != len(want):
        raise AssertionError(f"{tag}: {len(got)} reads answered, want {len(want)}")


def assert_kernel_programs(srv, chunk: int) -> int:
    """Every compiled query program and the ingest program of ``srv``'s
    engine holds a Mosaic kernel.  Returns the number checked."""
    import jax
    import jax.numpy as jnp

    eng = srv.engine
    q = jnp.zeros((chunk,), jnp.int32)
    programs = []
    for (op, _), fn in eng._query_cache.items():
        args = (q, q) if op in ("range_count", "range_scan") else (q,)
        programs.append((op, fn.lower(*args, delta=eng.delta)))
    w = jnp.zeros((srv._write_chunk,), jnp.int32)
    programs.append(("ingest", eng._ingest.lower(eng.delta, w, w, w != 0, w == 0)))
    for name, lowered in programs:
        if "tpu_custom_call" not in lowered.compile().as_text():
            raise AssertionError(f"{eng.config.name}: {name} program runs no Mosaic kernel")
    return len(programs)


def serve_config(cfg, keys, values, workload, chunk: int, scan_k: int, check_kernel: bool):
    """Build, warm and drive one server; returns its read results."""
    from repro.serving import BSTServer

    t0 = time.perf_counter()
    srv = BSTServer(keys, values, cfg, chunk_size=chunk, scan_k=scan_k)
    t1 = time.perf_counter()
    srv.warmup(READ_OPS)
    t2 = time.perf_counter()
    got = drive(srv, workload)
    t3 = time.perf_counter()
    n_programs = assert_kernel_programs(srv, chunk) if check_kernel else 0
    s = srv.stats
    if s.compactions != 1:
        raise AssertionError(f"{cfg.name}: {s.compactions} compactions, want 1")
    per_chunk = {
        op: f"{s.op(op).busy_s / max(s.op(op).chunks, 1) * 1e3:.3f}ms"
        for op in READ_OPS
    }
    log(
        f"{cfg.name} use_kernel={cfg.use_kernel}: build {t1 - t0:.2f}s, "
        f"warm-up compile {t2 - t1:.2f}s, drive {t3 - t2:.2f}s "
        f"(compaction re-warm included); wall per chunk {per_chunk}; "
        f"{n_programs} programs hold tpu_custom_call"
    )
    return got


def smoke_single(keys, values, seed, chunk, n_chunks, delta_capacity, scan_k,
                 configs=SMOKE_CONFIGS, check_kernel=True):
    """The one-chip phases: the XLA-gather reference server, then each
    preset on the kernel path, all against the host reference."""
    from repro.core.engine import PAPER_CONFIGS, EngineConfig

    workload = make_workload(keys, seed, chunk, n_chunks, delta_capacity)
    want = host_answers(keys, values, workload, scan_k)
    ref_cfg = EngineConfig(strategy="hrz", delta_capacity=delta_capacity)
    xla = serve_config(ref_cfg, keys, values, workload, chunk, scan_k, False)
    check_answers("XLA-gather path vs host", xla, want, workload)
    for name in configs:
        cfg = dataclasses.replace(
            PAPER_CONFIGS[name], use_kernel=True, delta_capacity=delta_capacity
        )
        got = serve_config(cfg, keys, values, workload, chunk, scan_k, check_kernel)
        check_answers(f"{name} vs host", got, want, workload)
        check_answers(f"{name} vs XLA-gather path", got, xla, workload)
        log(f"{name}: answers to {len(got)} reads match host and XLA path")


def smoke_sharded(keys, values, seed, chunk, n_chunks, delta_capacity, scan_k, devices):
    """Sharded servers over ``devices`` against one one-chip server."""
    from repro.core.distributed import make_serving_mesh
    from repro.core.engine import EngineConfig
    from repro.serving import BSTServer

    n_dev = len(devices)
    workload = make_workload(keys, seed, chunk, n_chunks, delta_capacity)
    want = host_answers(keys, values, workload, scan_k)
    single = BSTServer(
        keys, values,
        EngineConfig(strategy="hrz", use_kernel=True, delta_capacity=delta_capacity),
        chunk_size=chunk, scan_k=scan_k,
    )
    single.warmup(READ_OPS)
    one = drive(single, workload)
    check_answers("one-chip server vs host", one, want, workload)
    n_single = single.memory_nodes_per_device()
    for strategy in SHARDED_STRATEGIES:
        cfg = EngineConfig(
            strategy=strategy,
            n_trees=1 if strategy == "hrz" else n_dev,
            use_kernel=True,
            delta_capacity=delta_capacity,
        )
        mesh = make_serving_mesh(strategy, devices=devices)
        t0 = time.perf_counter()
        srv = BSTServer(keys, values, cfg, chunk_size=chunk, scan_k=scan_k, mesh=mesh)
        srv.warmup(READ_OPS)
        t1 = time.perf_counter()
        got = drive(srv, workload)
        t2 = time.perf_counter()
        check_answers(f"sharded {strategy} vs one-chip server", got, one, workload)
        nodes = srv.memory_nodes_per_device()
        # hrz/hyb: each device holds a 1/n_dev share of the tree plus the
        # replicated register layer (fewer than n_dev nodes).
        if strategy != "dup" and nodes > n_single // n_dev + n_dev:
            raise AssertionError(
                f"sharded {strategy}: {nodes} nodes on the fullest device of "
                f"{n_dev}, not a 1/{n_dev} share of {n_single}"
            )
        log(
            f"sharded {strategy} x {n_dev} devices: build+warm-up {t1 - t0:.2f}s, "
            f"drive {t2 - t1:.2f}s, {srv.stats.compactions} compaction(s), "
            f"nodes on fullest device {nodes} (one chip: {n_single}); "
            f"answers to {len(got)} reads bit-identical to the one-chip server"
        )


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.data.keysets import make_tree_data
    from repro.launch.compile_cache import enable_compile_cache

    log("smoke run, not a benchmark: times are single observations")
    log(f"compile cache at {enable_compile_cache()}")
    log(f"device {dev.device_kind} x {len(devices)}, jax {jax.__version__}")
    keys, values = make_tree_data(N_KEYS, seed=args.seed)
    sizes = dict(chunk=CHUNK, n_chunks=N_CHUNKS, delta_capacity=DELTA_CAPACITY, scan_k=SCAN_K)
    t0 = time.perf_counter()
    if args.chips == 4:
        smoke_sharded(keys, values, args.seed + 1, devices=devices[:4], **sizes)
    else:
        smoke_single(keys, values, args.seed + 1, **sizes)
    stats = dev.memory_stats() or {}
    log(
        f"all checks passed in {time.perf_counter() - t0:.1f}s; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
