"""BSTServer: chunk accumulation, accounting, snapshot-swap serving."""

import dataclasses
import os
import time
import types

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import tree as T
from repro.core.engine import PAPER_CONFIGS, EngineConfig
from repro.data.keysets import make_tree_data
from repro.serving import BSTServer


def _reference(tree, queries):
    v, f = T.search_reference(tree, jnp.asarray(np.asarray(queries, np.int32)))
    return np.asarray(v), np.asarray(f)


def test_chunk_accumulation_and_accounting():
    keys, values = make_tree_data(1000, seed=7)
    srv = BSTServer(keys, values, EngineConfig(strategy="hrz"), chunk_size=256)
    rng = np.random.default_rng(0)
    reqs = [
        rng.choice(np.concatenate([keys, keys + 1]), size=n).astype(np.int32)
        for n in (3, 256, 100, 517)  # odd sizes straddle chunk boundaries
    ]
    tickets = [srv.submit(r) for r in reqs]
    assert srv.pending() == sum(r.size for r in reqs)
    results = srv.drain()
    assert srv.pending() == 0
    total_found = 0
    for t, r in zip(tickets, reqs):
        v, f = results[t]
        ref_v, ref_f = _reference(srv.snapshot, r)
        np.testing.assert_array_equal(v, ref_v)
        np.testing.assert_array_equal(f, ref_f)
        total_found += int(ref_f.sum())
    s = srv.stats
    assert s.submitted == s.served == sum(r.size for r in reqs)
    assert s.found == total_found  # accumulated per chunk, padding excluded
    assert s.chunks == -(-sum(r.size for r in reqs) // 256)
    assert s.requests == len(reqs)


def test_scalar_and_empty_drain():
    keys, values = make_tree_data(100, seed=1)
    srv = BSTServer(keys, values, chunk_size=64)
    assert srv.drain() == {}
    v, f = srv.lookup(int(keys[5]))
    assert bool(f[0]) and int(v[0]) == int(values[5])


@pytest.mark.parametrize("name", sorted(PAPER_CONFIGS))
@pytest.mark.parametrize("use_kernel", [False, True])
def test_snapshot_swap_every_strategy(name, use_kernel):
    """bulk_insert/bulk_delete then lookups agree with search_reference
    through every paper strategy, kernel and reference paths alike."""
    keys, values = make_tree_data(500, seed=3)
    cfg = dataclasses.replace(PAPER_CONFIGS[name], use_kernel=use_kernel)
    srv = BSTServer(keys, values, cfg, chunk_size=128)

    ins_k = np.array([1, 3, 5, 7, int(keys[0]), int(keys[42])], np.int32)
    ins_v = np.array([10, 30, 50, 70, 999, 888], np.int32)
    del_k = keys[10:20]
    srv.apply_updates(insert_keys=ins_k, insert_values=ins_v, delete_keys=del_k)
    assert srv.stats.snapshot_swaps == 1

    rng = np.random.default_rng(4)
    probes = np.concatenate(
        [ins_k, del_k, rng.choice(np.concatenate([keys, keys + 1]), 300)]
    ).astype(np.int32)
    v, f = srv.lookup(probes)
    ref_v, ref_f = _reference(srv.snapshot, probes)
    np.testing.assert_array_equal(v, ref_v, err_msg=f"{name} kernel={use_kernel}")
    np.testing.assert_array_equal(f, ref_f, err_msg=f"{name} kernel={use_kernel}")

    # semantic spot-checks against the update stream itself
    kv = dict(zip(keys.tolist(), values.tolist()))
    for k in del_k.tolist():
        kv.pop(k)
    kv.update(dict(zip(ins_k.tolist(), ins_v.tolist())))
    got = dict(zip(probes.tolist(), v.tolist()))
    hit = dict(zip(probes.tolist(), f.tolist()))
    for k in ins_k.tolist():
        assert hit[k] and got[k] == kv[k]
    for k in del_k.tolist():
        assert not hit[k]


def test_per_op_busy_attribution_by_lanes():
    """Busy seconds attribute by the engine lanes each request occupied:
    range requests count their lo AND hi descent lanes, write/delete
    requests sharing a span split its time by key count, and per-op busy
    always sums to the span total (nothing double-booked or skewed)."""
    keys, values = make_tree_data(500, seed=11)
    srv = BSTServer(
        keys,
        values,
        EngineConfig(strategy="hrz", delta_capacity=64),
        chunk_size=128,
        scan_k=4,
    )
    rng = np.random.default_rng(2)
    q = rng.choice(keys, 100).astype(np.int32)
    lo = rng.choice(keys, 60).astype(np.int32)
    srv.submit(q)
    srv.submit_range(lo, (lo + 10).astype(np.int32), op="range_count")
    srv.drain()
    s = srv.stats
    assert s.per_op["lookup"].lanes == 100
    assert s.per_op["range_count"].lanes == 120  # lo||hi: 2 lanes per range
    assert s.lanes == 220
    assert sum(o.busy_s for o in s.per_op.values()) == pytest.approx(s.busy_s)
    # one drain of one read span: the engine calls' dispatch and sync
    # spans lie inside the busy time, and every span inside the drain
    assert s.drains == 1
    assert set(s.phase_s) == {"drain", "pack", "dispatch", "sync", "fetch", "unpack"}
    assert s.phase_s["dispatch"] + s.phase_s["sync"] <= s.busy_s

    srv.reset_stats()
    # a mixed write+delete span rides shared engine calls: time splits by
    # occupied lanes (30 write keys vs 10 delete keys -> exactly 3:1)
    srv.submit_write(
        np.arange(2001, 2031, dtype=np.int32), np.ones(30, np.int32)
    )
    srv.submit_delete(np.arange(2001, 2011, dtype=np.int32))
    srv.drain()
    s = srv.stats
    w, d = s.per_op["write"], s.per_op["delete"]
    assert w.lanes == 30 and d.lanes == 10 and s.lanes == 40
    assert w.busy_s + d.busy_s == pytest.approx(s.busy_s)
    assert w.busy_s == pytest.approx(3 * d.busy_s)


def test_swap_applies_to_pending_requests():
    """Requests drained after a swap see the new snapshot (documented)."""
    keys, values = make_tree_data(300, seed=9)
    srv = BSTServer(keys, values, chunk_size=64)
    absent = np.array([1], np.int32)  # odd -> not in the seed tree
    t = srv.submit(absent)
    srv.apply_updates(insert_keys=absent, insert_values=np.array([42], np.int32))
    v, f = srv.drain()[t]
    assert bool(f[0]) and int(v[0]) == 42


READ_SPANS = {"drain", "pack", "dispatch", "sync", "fetch", "unpack"}


def _fake_engine_clock(monkeypatch, srv):
    """Give the server a fake clock that moves 1 µs per reading and 1 ms in
    each engine call, each wait and each fetch, so that timing comparisons
    do not hang on how busy the test machine is; returns the clock."""
    import jax

    from repro.serving import bst_server

    now = [0.0]

    def clock():
        now[0] += 1e-6
        return now[0]

    def slow(fn):
        def call(*args):
            now[0] += 1e-3
            return fn(*args)
        return call

    monkeypatch.setattr(bst_server, "time", types.SimpleNamespace(perf_counter=clock))
    monkeypatch.setattr(bst_server, "jax", types.SimpleNamespace(
        profiler=jax.profiler, block_until_ready=slow(jax.block_until_ready)))
    monkeypatch.setattr(bst_server, "analysis_runtime", types.SimpleNamespace(
        device_fetch=slow(bst_server.analysis_runtime.device_fetch)))
    monkeypatch.setattr(srv, "_query_chunk", slow(srv._query_chunk))
    return clock


def test_drain_records_phase_spans(monkeypatch):
    """A read drain is split into its phase spans: together they are at
    most the drain's wall time, and the engine calls' dispatch, sync and
    fetch spans are its busy time (on the fake clock of
    ``_fake_engine_clock``)."""
    keys, values = make_tree_data(4095, seed=3)
    srv = BSTServer(keys, values, EngineConfig(strategy="hrz"), chunk_size=2048)
    srv.warmup()
    clock = _fake_engine_clock(monkeypatch, srv)
    rng = np.random.default_rng(4)
    for n in (1, 2047, 3000):  # two full chunks and a padded one
        srv.submit(rng.choice(keys, n).astype(np.int32))
    t0 = clock()
    srv.drain()
    wall = clock() - t0
    s = srv.stats
    assert set(s.phase_s) == READ_SPANS
    assert sum(s.phase_s.values()) <= wall
    engine = s.phase_s["dispatch"] + s.phase_s["sync"] + s.phase_s["fetch"]
    assert s.chunks == 3 and s.busy_s >= 6e-3
    assert 0.9 * s.busy_s <= engine <= s.busy_s, (engine, s.busy_s)


def test_one_chunk_loop_overlaps_chunks_on_one_chip(monkeypatch):
    """One drain of three chunks (two full, one padded) on one chip runs
    the depth-2 loop: it answers exactly as ``lookup`` does chunk by
    chunk, dispatches two chunks while an earlier one is in flight, routes
    nothing, and its busy time is the dispatch, sync and fetch spans'."""
    keys, values = make_tree_data(4095, seed=8)
    chunk = 1024
    srv = BSTServer(keys, values, EngineConfig(strategy="hrz"), chunk_size=chunk)
    alone = BSTServer(keys, values, EngineConfig(strategy="hrz"), chunk_size=chunk)
    srv.warmup()
    q = np.random.default_rng(9).choice(np.concatenate([keys, keys + 1]),
                                        2 * chunk + 300).astype(np.int32)
    expect = [alone.lookup(q[lo:lo + chunk]) for lo in range(0, q.size, chunk)]
    _fake_engine_clock(monkeypatch, srv)
    v, f = srv.lookup(q)
    np.testing.assert_array_equal(v, np.concatenate([e[0] for e in expect]))
    np.testing.assert_array_equal(f, np.concatenate([e[1] for e in expect]))
    s = srv.stats
    assert (s.chunks, s.overlapped_chunks, s.routed_slots) == (3, 2, 0)
    assert s.found == int(f.sum()) and s.served == s.lanes == q.size
    engine = s.phase_s["dispatch"] + s.phase_s["sync"] + s.phase_s["fetch"]
    assert engine <= s.busy_s <= 1.05 * engine, (engine, s.busy_s)


def test_queue_wait_and_drains_count_once_per_drain(monkeypatch):
    """Each drain adds one to ``drains`` and the wait of its oldest request
    to ``queue_wait_s``; submit reads the clock once per drain, not per
    request, and an empty drain counts nothing."""
    from repro.serving import bst_server

    keys, values = make_tree_data(255, seed=5)
    srv = BSTServer(keys, values, chunk_size=64)
    srv.warmup()
    reads = []

    def counted():
        reads.append(1)
        return time.perf_counter()

    for _ in range(3):
        with monkeypatch.context() as m:
            m.setattr(bst_server, "time", types.SimpleNamespace(perf_counter=counted))
            for k in keys[:20]:
                srv.submit(k)
        time.sleep(0.01)
        srv.drain()
    assert len(reads) == 3  # one per drain, at its first request
    assert srv.drain() == {}
    assert srv.stats.drains == 3
    assert 0.03 <= srv.stats.queue_wait_s < 3.0


def test_write_span_through_a_compaction_records_ingest_compact_rewarm():
    """Writes that cross the high-water mark run the ingest, compaction and
    re-warm spans; the compaction's time counts for ``compact`` alone, and
    ingest and compaction together are the write span's busy time."""
    keys, values = make_tree_data(500, seed=6)
    cfg = EngineConfig(strategy="hrz", delta_capacity=64, delta_high_water=48)
    srv = BSTServer(keys, values, cfg, chunk_size=128)
    srv.warmup()
    srv.submit_write(np.arange(1, 101, 2, dtype=np.int32), np.ones(50, np.int32))
    srv.drain()
    s = srv.stats
    assert s.compactions == 1
    assert {"drain", "ingest", "compact", "rewarm"} == set(s.phase_s)
    assert s.phase_s["ingest"] + s.phase_s["compact"] <= s.busy_s


def test_sharded_server_records_the_same_spans(multi_device_host):
    """The chunk loop over four forced host devices records the span names
    it records on one chip, write path included, and its own sharded put
    of each chunk (``shard_put``)."""
    out = multi_device_host("""
        from repro.core import distributed as D
        from repro.core.engine import EngineConfig
        from repro.data.keysets import make_tree_data
        from repro.serving import BSTServer

        keys, values = make_tree_data(500, seed=6)
        cfg = EngineConfig(strategy="hrz", delta_capacity=64, delta_high_water=48)
        srv = BSTServer(keys, values, cfg, chunk_size=64, mesh=D.make_serving_mesh("hrz"))
        srv.warmup()
        srv.submit(keys[:100])
        srv.submit_write(np.arange(1, 101, 2, dtype=np.int32), np.ones(50, np.int32))
        srv.submit(keys[100:150])
        srv.drain()
        s = srv.stats
        assert s.compactions == 1 and s.drains == 1
        print(sorted(s.phase_s))
    """, devices=4, timeout=900)
    names = out.strip().splitlines()[-1]
    assert names == str(sorted(READ_SPANS | {"ingest", "compact", "rewarm", "shard_put"}))


READ_OPS = ("lookup", "predecessor", "successor", "range_count", "range_scan")


def _drain_script(shape: str, keys, seed: int = 0):
    """One drain's requests, in submission order, as ``(method, args)``
    pairs of ``BSTServer``.  ``one-key``: lookups of one key each, as
    arrays and as scalars, over several chunks; ``one-key-every-op``: one
    key or one range each, every read op in one span; ``two-and-none``:
    lookups of two keys and of none in turn, as many keys as requests;
    ``alternating``: one-key lookups and predecessors in turn, an op run
    each; ``mixed``: scalar, one-key, multi-key and empty requests of every read
    op, with writes and deletes between them."""
    rng = np.random.default_rng(seed)
    pool = np.concatenate([keys, keys + 1])

    def some(n):
        return rng.choice(pool, n).astype(np.int32)

    def read(op, form):
        n = {"scalar": 1, "one": 1, "two": 2, "multi": 3, "empty": 0}[form]
        lo = some(n)
        if form == "scalar":
            lo = int(lo[0]) if rng.random() < 0.5 else np.int32(lo[0])
        if op in ("range_count", "range_scan"):
            return ("submit_range", (lo, lo + 9, op))
        return ("submit", (lo, op))

    if shape == "one-key":
        return [read("lookup", "scalar" if i % 5 == 0 else "one") for i in range(150)]
    if shape == "one-key-every-op":
        return [read(READ_OPS[i % 5], "scalar" if i % 7 == 0 else "one") for i in range(60)]
    if shape == "two-and-none":
        return [read("lookup", ("two", "empty")[i % 2]) for i in range(40)]
    if shape == "alternating":
        return [read(("lookup", "predecessor")[i % 2], "scalar" if i % 7 == 0 else "one")
                for i in range(60)]
    script = []
    for i in range(80):
        if i % 9 == 4:
            k = some(1 + i % 3)
            script.append(("submit_write", (k, k * 3)))
        elif i % 13 == 7:
            script.append(("submit_delete", (some(2),)))
        else:
            script.append(read(READ_OPS[i % 5], ("scalar", "one", "multi", "empty")[i % 4]))
    return script


def _row_answers(script) -> int:
    """Requests of the op groups, per read span, whose every request holds
    one key or one range: those the drain answers by row views."""
    count, groups = 0, {}
    for method, args in script + [("submit_write", None)]:
        if method in ("submit_write", "submit_delete"):
            count += sum(len(g) for g in groups.values() if all(n == 1 for n in g))
            groups = {}
        else:
            groups.setdefault(args[-1], []).append(np.size(args[0]))
    return count


def check_columnar_drain(make_server, script, rounds: int = 2) -> None:
    """Serve ``script`` as one drain per round and each request alone on a
    second server: every answer agrees in shape, dtype and values, the
    tickets run on from round to round, and ``row_answers`` counts the
    requests of the all-single-key groups.  Alone, a read is drained beside
    an empty request of its kind, so its answer is sliced, never a row."""
    srv, alone = make_server(), make_server()
    empty = np.zeros(0, np.int32)
    n = len(script)
    for r in range(rounds):
        tickets = [getattr(srv, m)(*args) for m, args in script]
        assert tickets == list(range(r * n, (r + 1) * n))
        out = srv.drain()
        assert sorted(out) == tickets
        for t, (m, args) in zip(tickets, script):
            solo = getattr(alone, m)(*args)
            if m == "submit":
                alone.submit(empty, args[-1])
            elif m == "submit_range":
                alone.submit_range(empty, empty, args[-1])
            want, got = alone.drain()[solo], out[t]
            assert len(got) == len(want), (m, args)
            for g, w in zip(got, want):
                assert (g.shape, g.dtype) == (w.shape, w.dtype), (m, args)
                np.testing.assert_array_equal(g, w)
        assert srv.stats.row_answers == (r + 1) * _row_answers(script)
    assert alone.stats.row_answers == 0
    srv.reset_stats()
    assert srv.stats.row_answers == 0


@pytest.mark.parametrize("shape", ["one-key", "one-key-every-op", "two-and-none", "mixed"])
@pytest.mark.parametrize("strategy", ["hrz", "hyb"])
def test_columnar_drain_answers_as_each_request_alone(shape, strategy):
    keys, values = make_tree_data(500, seed=8)
    cfg = dataclasses.replace(
        PAPER_CONFIGS["Hrz" if strategy == "hrz" else "Hyb8q"],
        delta_capacity=64, delta_high_water=48,
    )

    def make():
        return BSTServer(keys, values, cfg, chunk_size=64)

    script = _drain_script(shape, keys)
    if shape == "one-key":
        assert _row_answers(script) == len(script)
    check_columnar_drain(make, script)


def test_sharded_columnar_drain_answers_as_each_request_alone(multi_device_host):
    """The same check through the sharded server."""
    out = multi_device_host(f"""
        sys.path.insert(0, {os.path.dirname(os.path.abspath(__file__))!r})
        from repro.core import distributed as D
        from repro.core.engine import EngineConfig
        from repro.data.keysets import make_tree_data
        from repro.serving import BSTServer
        from test_serving_bst import _drain_script, check_columnar_drain

        keys, values = make_tree_data(500, seed=8)
        cfg = EngineConfig(strategy="hrz", delta_capacity=64, delta_high_water=48)
        mesh = D.make_serving_mesh("hrz")
        check_columnar_drain(
            lambda: BSTServer(keys, values, cfg, chunk_size=64, mesh=mesh),
            _drain_script("mixed", keys), rounds=1)
        print("columnar ok")
    """, devices=4, timeout=900)
    assert out.strip().splitlines()[-1] == "columnar ok"


_K = np.arange(4, dtype=np.int32)
_BAD_REQUESTS = {
    "point-op-range": ("submit", (_K, "range_count")),
    "point-op-write": ("submit", (_K, "write")),
    "range-op-point": ("submit_range", (_K, _K, "lookup")),
    "2d-int32-keys": ("submit", (_K.reshape(2, 2),)),
    "2d-int64-keys": ("submit", (_K.reshape(2, 2).astype(np.int64),)),
    "2d-range": ("submit_range", (_K.reshape(2, 2), _K.reshape(2, 2))),
    "lo-hi-lengths": ("submit_range", (_K, _K[:3])),
    "keys-values-lengths": ("submit_write", (_K, _K[:3])),
    "2d-write": ("submit_write", (_K.reshape(2, 2), _K.reshape(2, 2))),
    "2d-delete": ("submit_delete", (_K.reshape(2, 2),)),
}


@pytest.mark.parametrize("name", sorted(_BAD_REQUESTS))
def test_bad_requests_raise_and_queue_nothing(name):
    keys, values = make_tree_data(100, seed=2)
    srv = BSTServer(keys, values, EngineConfig(delta_capacity=64), chunk_size=64)
    method, args = _BAD_REQUESTS[name]
    with pytest.raises(ValueError):
        getattr(srv, method)(*args)
    assert srv.pending() == 0 and srv.stats.requests == 0
    assert srv.drain() == {}
    assert srv.submit(keys[:2]) == 0  # the ticket count did not move


_SUBMIT_OP = {"submit_write": "write", "submit_delete": "delete"}


def _op_changes(script) -> int:
    """Requests of ``script`` whose op differs from the request before it."""
    ops = [_SUBMIT_OP.get(m) or args[-1] for m, args in script]
    return sum(x != y for x, y in zip(ops, ops[1:]))


@pytest.mark.parametrize("shape", ["one-key", "alternating", "mixed"])
def test_op_run_queue_answers_and_counts_per_drain(shape):
    """Queues of one op run, of alternating point ops, and of reads and
    range ops between writes and deletes answer as each request alone; the
    queue counts nothing while it fills, and each drain adds its requests,
    their keys and its op runs (one, plus one per change of op)."""
    keys, values = make_tree_data(500, seed=8)
    cfg = EngineConfig(strategy="hrz", delta_capacity=64, delta_high_water=48)

    def make():
        return BSTServer(keys, values, cfg, chunk_size=64)

    script = _drain_script(shape, keys, seed=3)
    check_columnar_drain(make, script)
    srv = make()
    n_keys = sum(int(np.size(args[0])) for _, args in script)
    for r in range(1, 3):
        for m, args in script:
            getattr(srv, m)(*args)
        s = srv.stats
        assert (s.requests, s.submitted, s.op_runs) == ((r - 1) * len(script),
                                                        (r - 1) * n_keys,
                                                        (r - 1) * (1 + _op_changes(script)))
        srv.drain()
        assert (s.requests, s.submitted, s.op_runs) == (r * len(script), r * n_keys,
                                                        r * (1 + _op_changes(script)))
    if shape == "one-key":
        assert srv.stats.op_runs == 2  # one run per drain


@pytest.mark.parametrize("order", ["continues-the-run", "returns-to-an-earlier-op"])
def test_op_equal_to_a_run_but_not_the_same_object_joins_its_group(order):
    """An op string equal to a queued op, but another object, joins that
    op's run where it continues it, and its group (one engine call per op)
    in either case."""
    keys, values = make_tree_data(255, seed=4)
    srv = BSTServer(keys, values, chunk_size=64)
    literal, lookup = "lookup", "".join(["look", "up"])
    assert lookup == literal and lookup is not literal
    ops = {
        "continues-the-run": ["lookup", lookup, "lookup", lookup, "predecessor"],
        "returns-to-an-earlier-op": ["lookup", "predecessor", lookup, "predecessor", lookup],
    }[order]
    reqs = [keys[i : i + 1] for i in range(len(ops))]
    tickets = [srv.submit(q, op) for q, op in zip(reqs, ops)]
    assert tickets == list(range(len(ops)))
    out = srv.drain()
    for t, q, op in zip(tickets, reqs, ops):
        want = srv.engine.query(op, q)
        want = want if isinstance(want, tuple) else (want,)
        assert len(out[t]) == len(want)
        for got, w in zip(out[t], want):
            np.testing.assert_array_equal(got, np.asarray(w))
    s = srv.stats
    assert s.op_runs == 1 + sum(x != y for x, y in zip(ops, ops[1:]))
    assert {op: st.chunks for op, st in s.per_op.items()} == {"lookup": 1, "predecessor": 1}
    assert s.row_answers == len(ops)  # one group per op, every request one key


@pytest.mark.parametrize("run", ["lookup", "range_count"])
@pytest.mark.parametrize("name", sorted(_BAD_REQUESTS))
def test_bad_request_mid_run_queues_nothing(name, run):
    """A request rejected while a run of one-key lookups or of ranges is
    open leaves the queue, the run and the next ticket as they were: the
    drain answers as a server that never saw it."""
    keys, values = make_tree_data(100, seed=2)

    def make():
        return BSTServer(keys, values, EngineConfig(delta_capacity=64), chunk_size=64)

    def good(srv, i):
        k = keys[i : i + 1]
        return srv.submit(k) if run == "lookup" else srv.submit_range(k, k + 5)

    srv, clean = make(), make()
    assert [good(srv, i) for i in range(3)] == [0, 1, 2]
    method, args = _BAD_REQUESTS[name]
    with pytest.raises(ValueError):
        getattr(srv, method)(*args)
    assert srv.pending() == 3
    assert good(srv, 3) == 3  # the ticket count did not move
    assert [good(clean, i) for i in range(4)] == [0, 1, 2, 3]
    out, want = srv.drain(), clean.drain()
    assert sorted(out) == sorted(want)
    for t in want:
        for got, w in zip(out[t], want[t], strict=True):
            np.testing.assert_array_equal(got, w)
    assert (srv.stats.requests, srv.stats.op_runs, srv.stats.row_answers) == (4, 1, 4)
