"""Real-engine throughput: keys/second on this host for every strategy x op.

This is the TPU-native performance plane (jit'd JAX); on the CPU container
it measures real executed work, demonstrating the throughput ordering the
partitioning strategies produce outside the cycle model.

Rows come in four flavours per strategy: the jnp reference path for plain
lookups over every paper key set, the ordered-query ops (predecessor /
range_count / range_scan -- DESIGN.md §6) on the ``random`` set, (at a
smaller batch) the Pallas forest-kernel path (``use_kernel=True``), so the
bench trajectory tracks the kernel the TPU actually runs and not just the
oracle, and MIXED read/write streams (90/10 and 50/50) through
``BSTServer``'s delta write path (DESIGN.md §7) -- the rows CI publishes
to watch live-update serving throughput.  Interpret-mode kernel timings
measure executed semantics on CPU, not TPU performance (DESIGN.md §2).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import subprocess
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, time_fn
from repro.core import distributed as dist_lib
from repro.core import plans as plans_lib
from repro.core import tree as tree_lib
from repro.core.engine import BSTEngine, EngineConfig, PAPER_CONFIGS
from repro.data.keysets import make_key_sets, make_tree_data
from repro.serving import BSTServer

# Ordered ops benchmarked per strategy (lookup is the baseline row family).
ORDERED_OPS = ("predecessor", "range_count", "range_scan")


def _time_op(eng: BSTEngine, op: str, q, q_hi, warmup=1, iters=3) -> float:
    if q_hi is None:
        return time_fn(lambda a: eng.query(op, a), q, warmup=warmup, iters=iters)
    return time_fn(
        lambda a, b: eng.query(op, a, b), q, q_hi, warmup=warmup, iters=iters
    )


def run(n_keys=(1 << 16) - 1, batch=16384, kernel_batch=2048, quick=False) -> List[Row]:
    # batch sized so the retired-driver baseline rows (hyb_kernel_vs_driver
    # below -- the one place the old O(B * n * capacity) direct dispatch
    # still runs, as the regression-gate baseline) finish in seconds;
    # keys/s is batch-size stable for the engines themselves.
    keys, values = make_tree_data(n_keys, seed=0)
    rows: List[Row] = []
    engines = {n: BSTEngine(keys, values, c) for n, c in PAPER_CONFIGS.items()}
    sets = make_key_sets(engines["Hrz"].tree, batch)
    for set_name, q in sets.items():
        for name, eng in engines.items():
            us = time_fn(eng.lookup, q, warmup=1, iters=3)
            rows.append(
                Row(
                    name=f"engine/{set_name}/{name}",
                    us_per_call=us,
                    derived=f"keys_per_sec={batch / (us / 1e6):.3e};batch={batch}",
                )
            )

    # Ordered-query ops (DESIGN.md §6) per strategy on the random set: one
    # descent per op (range ops descend lo||hi), so keys/s is comparable to
    # the lookup rows above.
    rng = np.random.default_rng(3)
    q = sets["random"]
    span = rng.integers(0, 4 * n_keys // batch + 2, size=batch).astype(np.int32)
    lo, hi = q, (q + span).astype(np.int32)
    for op in ORDERED_OPS:
        a, b = (lo, hi) if op.startswith("range") else (q, None)
        for name, eng in engines.items():
            us = _time_op(eng, op, a, b)
            rows.append(
                Row(
                    name=f"engine/random/{name}/{op}",
                    us_per_call=us,
                    derived=f"keys_per_sec={batch / (us / 1e6):.3e};batch={batch}",
                )
            )

    # Pallas forest-kernel path (interpret mode): smaller batch, one key set,
    # so the full matrix stays tractable on CPU while still exercising the
    # exact kernel datapath every strategy lowers to.  One ordered op rides
    # along per strategy (the same single pallas_call; see DESIGN.md §6).
    kq = sets["random"][:kernel_batch]
    klo, khi = lo[:kernel_batch], hi[:kernel_batch]
    for name, cfg in PAPER_CONFIGS.items():
        eng = BSTEngine(keys, values, dataclasses.replace(cfg, use_kernel=True))
        us = time_fn(eng.lookup, kq, warmup=1, iters=2)
        rows.append(
            Row(
                name=f"engine/random/{name}/kernel",
                us_per_call=us,
                derived=(
                    f"keys_per_sec={kernel_batch / (us / 1e6):.3e};"
                    f"batch={kernel_batch};use_kernel=1"
                ),
            )
        )
        us = _time_op(eng, "range_count", klo, khi, warmup=1, iters=2)
        rows.append(
            Row(
                name=f"engine/random/{name}/range_count/kernel",
                us_per_call=us,
                derived=(
                    f"keys_per_sec={kernel_batch / (us / 1e6):.3e};"
                    f"batch={kernel_batch};use_kernel=1"
                ),
            )
        )

    rows.extend(hyb_kernel_vs_driver_rows(keys, values, batch=kernel_batch))
    rows.extend(mixed_rw_rows(keys, values, batch=min(batch, 8192)))
    # quick halves the chunk and trims stream/trials so CI's engine suite
    # stays quick (the 8192-row chunks still clear the gate's 4k floor).
    # The tree stays full-size on purpose: against a shallow tree the
    # per-chunk fixed costs drown the descent and the comparison measures
    # dispatch overhead, not serving.
    rows.extend(
        sharded_serve_rows(chunk=8192, n_chunks=6, trials=5)
        if quick
        else sharded_serve_rows()
    )
    return rows


def _retired_hyb_driver(tree, n_trees: int, mapping: str, slack: float = 2.0):
    """The RETIRED driver-level hyb composition, reconstructed from the
    shared phase functions (route -> jnp dispatch -> gather -> forest-kernel
    subtree descent -> combine -> jnp stall round).  It exists ONLY here,
    as the regression-gate baseline recorded in every BENCH_*.json run:
    the engine itself now lowers the whole pipeline through the single
    forest ``pallas_call`` (DESIGN.md §8), and CI fails if that in-kernel
    path ever drops below this composition's throughput.
    """
    split = int(math.log2(n_trees))
    idx = tree_lib.all_subtree_gather_indices(tree.height, split)
    fk, fv = tree.keys[jnp.asarray(idx)], tree.values[jnp.asarray(idx)]
    reg_n = (1 << max(split, 1)) - 1
    rk, rv = tree.keys[:reg_n], tree.values[:reg_n]
    sub_h = tree.height - split

    def run(queries):
        B = queries.shape[0]
        dest, reg_val, reg_found = plans_lib.route_phase(rk, rv, queries, split)
        capacity = int(math.ceil(B / n_trees * slack))
        dplan = plans_lib.dispatch_phase(
            mapping, dest, n_trees, capacity, active=~reg_found
        )
        per_q, per_act = plans_lib.gather_phase(queries, dplan)
        sub_v, sub_f = plans_lib.descend_phase(
            fk, fv, sub_h, per_q, per_act, use_kernel=True
        )
        val, found = plans_lib.combine_phase(
            sub_v, sub_f, dplan, B, reg_val, reg_found
        )

        def retry(args):
            val, found = args
            r_val, r_found = tree_lib.search_reference(tree, queries)
            return (
                jnp.where(dplan.overflow, r_val, val),
                jnp.where(dplan.overflow, r_found, found),
            )

        return jax.lax.cond(
            jnp.any(dplan.overflow), retry, lambda a: a, (val, found)
        )

    return jax.jit(run)


def hyb_kernel_vs_driver_rows(keys, values, batch: int) -> List[Row]:
    """Hyb in-kernel pipeline vs the retired driver composition, same run.

    Two rows per hyb preset, tagged ``pair=<name>``: ``hyb_kernel`` is the
    engine's real path (route + dispatch + descent + stall replay in ONE
    ``pallas_call``), ``hyb_driver`` the retired composition above.  CI's
    regression gate (scripts/check_bench.py) reads these pairs out of
    BENCH_4.json and fails when the kernel path is the slower one.
    """
    rng = np.random.default_rng(5)
    q = rng.choice(np.concatenate([keys, keys + 1]), batch).astype(np.int32)
    tree = tree_lib.build_tree(np.asarray(keys), np.asarray(values))
    rows: List[Row] = []
    for name, cfg in PAPER_CONFIGS.items():
        if cfg.strategy != "hyb":
            continue
        plan = plans_lib.make_plan(
            tree, strategy="hyb", n_trees=cfg.n_trees, mapping=cfg.mapping
        )
        ker = jax.jit(
            lambda qq, plan=plan: plans_lib.execute_plan(
                plan, qq, use_kernel=True
            )
        )
        drv = _retired_hyb_driver(tree, cfg.n_trees, cfg.mapping)
        qj = jnp.asarray(q)
        # both paths must agree before either is worth timing -- the gate
        # downstream assumes the rows measure equivalent work
        kv, kf = ker(qj)
        dv, df = drv(qj)
        bad = int(
            np.sum(np.asarray(kv) != np.asarray(dv))
            + np.sum(np.asarray(kf) != np.asarray(df))
        )
        if bad:
            raise RuntimeError(
                f"{name}: in-kernel hyb path disagrees with the retired "
                f"driver composition on {bad} lanes -- refusing to record "
                "a kernel-vs-driver pair for non-equivalent work"
            )
        for kind, fn in (("hyb_kernel", ker), ("hyb_driver", drv)):
            us = time_fn(fn, qj, warmup=1, iters=5)
            rows.append(
                Row(
                    name=f"engine/random/{name}/{kind}",
                    us_per_call=us,
                    derived=(
                        f"keys_per_sec={batch / (us / 1e6):.3e};"
                        f"batch={batch};pair={name}"
                    ),
                )
            )
    return rows


def mixed_rw_rows(keys, values, batch: int, rounds: int = 4) -> List[Row]:
    """Mixed read/write serving throughput through the delta write path.

    Each round submits an interleaved write batch + read batch to a
    ``BSTServer`` whose engine carries a delta buffer (DESIGN.md §7), then
    drains; ``keys_per_sec`` covers reads AND absorbed updates over
    engine-busy time, with compaction cost included whenever the stream
    trips the high-water mark.  One row per (mix, strategy).
    """
    rng = np.random.default_rng(7)
    rows: List[Row] = []
    for mix, write_frac in (("90_10", 0.10), ("50_50", 0.50)):
        for name in ("Hrz", "Dup8", "Hyb8q"):
            cfg = dataclasses.replace(PAPER_CONFIGS[name], delta_capacity=2048)
            srv = BSTServer(keys, values, cfg, chunk_size=batch)
            srv.warmup(("lookup",))
            # warm the (padded, fixed-shape) ingest program too
            srv.submit_write(np.int32(1), np.int32(1))
            srv.drain()
            srv.reset_stats()
            n_w = int(batch * write_frac)
            for _ in range(rounds):
                wk = rng.integers(1, 2**20, n_w).astype(np.int32)
                srv.submit_write(wk, wk)
                srv.submit(rng.choice(keys, batch - n_w).astype(np.int32))
                srv.drain()
            s = srv.stats
            rows.append(
                Row(
                    name=f"serve/mixed_{mix}/{name}",
                    us_per_call=s.busy_s / rounds * 1e6,  # one mixed round
                    derived=(
                        f"keys_per_sec={s.served / s.busy_s:.3e};batch={batch};"
                        f"write_frac={write_frac};updates={s.updates};"
                        f"compactions={s.compactions}"
                    ),
                )
            )
    return rows


def _sharded_rows(devices, chunk, n_chunks, trials, n_keys) -> List[dict]:
    """Sharded vs single-chip rows over the first ``devices`` JAX devices,
    measured in THIS process (the body of ``sharded_serve_rows``)."""
    devs = jax.devices()[:devices]
    rng = np.random.default_rng(11)
    keys, values = make_tree_data(n_keys, seed=0)
    stream = rng.choice(keys, n_chunks * chunk).astype(np.int32)
    rows = []

    def drain_stream(srv):
        srv.submit(stream)
        t0 = time.perf_counter()
        srv.drain()
        return time.perf_counter() - t0

    for strategy in ("dup", "hrz", "hyb"):
        n_trees = max(2, devices) if strategy != "hrz" else 1
        cfg = EngineConfig(strategy=strategy, n_trees=n_trees)
        mesh = dist_lib.make_serving_mesh(strategy, devices=devs)
        servers = {
            "single": BSTServer(keys, values, cfg, chunk_size=chunk),
            "sharded": BSTServer(keys, values, cfg, chunk_size=chunk, mesh=mesh),
        }
        for srv in servers.values():
            srv.warmup(("lookup",))
        # Interleaved A/B trials so host noise hits both modes alike; the
        # row records the per-mode MEDIAN drain wall (keys/sec over the
        # stream).
        times = {name: [] for name in servers}
        for _ in range(trials):
            for name, srv in servers.items():
                times[name].append(drain_stream(srv))
        # Per-device stored nodes: the capacity axis subtree sharding buys
        # (DESIGN.md §9) -- dup replicates (no win), hrz/hyb hold 1/M of
        # the tree plus the replicated register layer.  MEASURED from each
        # server's real shard layout, so a sharding regression (an operand
        # silently replicated) trips the gate instead of a formula hiding it.
        mem = {name: srv.memory_nodes_per_device() for name, srv in servers.items()}
        for name in servers:
            dt = statistics.median(times[name])
            rows.append({
                "name": f"serve/sharded_{strategy}/{name}",
                "us_per_call": dt * 1e6,
                "derived": ";".join([
                    f"spair={strategy}",
                    f"mode={name}",
                    f"keys_per_sec={stream.size / dt:.3e}",
                    f"batch={chunk}",
                    f"devices={devices}",
                    f"mem_nodes_dev={mem[name]}",
                ]),
            })

    # One sharded mixed read/write row: the delta buffer riding the sharded
    # program as replicated operands, compactions included (DESIGN.md §9).
    cfg = EngineConfig(strategy="dup", n_trees=max(2, devices), delta_capacity=2048)
    mesh = dist_lib.make_serving_mesh("dup", devices=devs)
    srv = BSTServer(keys, values, cfg, chunk_size=chunk, mesh=mesh)
    srv.warmup(("lookup",))
    srv.submit_write(np.int32(1), np.int32(1))
    srv.drain()
    srv.reset_stats()
    n_w = chunk // 10
    t0 = time.perf_counter()
    for _ in range(4):
        wk = rng.integers(1, 2**20, n_w).astype(np.int32)
        srv.submit_write(wk, wk)
        srv.submit(rng.choice(keys, chunk - n_w).astype(np.int32))
        srv.drain()
    dt = time.perf_counter() - t0
    s = srv.stats
    rows.append({
        "name": "serve/sharded_mixed_90_10/dup",
        "us_per_call": dt / 4 * 1e6,
        "derived": ";".join([
            f"keys_per_sec={s.served / dt:.3e}",
            f"batch={chunk}",
            f"devices={devices}",
            "write_frac=0.10",
            f"updates={s.updates}",
            f"compactions={s.compactions}",
        ]),
    })
    return rows


def sharded_serve_rows(
    chunk: int = 16384,
    n_chunks: int = 8,
    trials: int = 7,
    n_keys: int = (1 << 16) - 1,
) -> List[Row]:
    """Sharded vs single-chip serving, same run.

    Two rows per strategy (``serve/sharded_<strategy>/{sharded,single}``,
    tagged ``spair=<strategy>``) plus one sharded mixed read/write row.
    scripts/check_bench.py gates each strategy on ITS scaling axis: dup
    (replicate-and-split, the throughput play) must serve at least as many
    keys/sec as the single-chip server; hrz/hyb (subtree sharding, the
    capacity play) must store strictly fewer nodes per device
    (``mem_nodes_dev``) -- the deterministic figure a host-simulated mesh
    can gate without CPU timing noise.

    On a TPU the rows run in this process over the real chips: this
    process holds them, so a child could not reach them.  Elsewhere a
    child process simulates the mesh, because the XLA host device count
    must be set before JAX starts.  The count tracks the PHYSICAL cores
    (a host-simulated mesh wider than the cores measures oversubscription,
    not scaling), and subtree sharding needs a power of two.
    """
    args = (chunk, n_chunks, trials, n_keys)
    if jax.default_backend() == "tpu":
        devices = 1 << int(math.log2(len(jax.devices())))
        return [Row(**r) for r in _sharded_rows(devices, *args)]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    devices = 1 << int(math.log2(max(2, min(8, os.cpu_count() or 2))))
    code = (
        "import json, sys\n"
        f"sys.path[:0] = [{root!r}, {os.path.join(root, 'src')!r}]\n"
        "from benchmarks.engine_throughput import _sharded_rows\n"
        f"print('ROWS_JSON:' + json.dumps(_sharded_rows({devices}, *{args!r})))\n"
    )
    env = dict(
        os.environ, XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=1800,
        env=env,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"sharded bench subprocess failed:\nSTDOUT:\n{out.stdout}\n"
            f"STDERR:\n{out.stderr}"
        )
    payload = [
        line for line in out.stdout.splitlines() if line.startswith("ROWS_JSON:")
    ]
    if not payload:
        raise RuntimeError(f"sharded bench emitted no rows:\n{out.stdout}")
    return [Row(**r) for r in json.loads(payload[-1][len("ROWS_JSON:"):])]
