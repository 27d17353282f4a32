"""Property-based differential harness for the live write path (DESIGN.md §7).

Random op sequences -- insert / delete / re-insert / lookup / predecessor /
successor / range_count / range_scan -- run through the delta-buffered
engine and are checked BIT-FOR-BIT against a plain Python ``dict`` +
``sorted`` oracle, preserving submission order (a read sees exactly the
writes before it).  Coverage axes:

  * hrz / dup / hyb strategies, reference AND Pallas-kernel descent paths;
  * pre-compaction (live buffer) and post-compaction (fresh snapshot)
    states -- every sequence is re-probed right after a forced ``compact()``;
  * the ≥ 500-op mixed-stream acceptance gate through ``BSTServer``'s typed
    write/delete request kinds, per strategy;
  * the SHARDED serving paths (DESIGN.md §9): on a forced 8-device host
    (the ``multi_device_host`` conftest fixture -- XLA device counts must
    precede jax init, so the body runs subprocess-side), a sharded
    ``BSTServer`` drains the same submission sequence as a single-chip
    server and must match BIT-FOR-BIT, for hrz / dup / hyb x kernel /
    reference descent x pre-/post-compaction, live writes included; plus
    a ≥ 500-op mixed read/write soak per mix ratio that cross-checks the
    per-op ``OpStats`` lane accounting against the submitted op counts
    and the phase counters against the drains.

Runs under real hypothesis or the deterministic ``_hypothesis_fallback``
shim alike (the strategies stick to the shim's subset).  Reads are flushed
in write-bounded spans at fixed padded shapes so each engine epoch compiles
once; correctness never depends on the batching.
"""

import zlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.engine import BSTEngine, EngineConfig
from repro.data.keysets import make_tree_data
from repro.serving import BSTServer

KEYSPACE = 500  # small universe -> plenty of overwrites / re-inserts
SCAN_K = 4
PROBE_PAD = 32  # fixed read-span batch shape (one compile per op kind)
WRITE_PAD = 16  # fixed write-span batch shape

READ_OPS = ("lookup", "predecessor", "successor", "range_count", "range_scan")
ALL_OPS = ("insert", "delete") + READ_OPS

SENT_K = np.iinfo(np.int32).max
NO_PRED = np.iinfo(np.int32).min


def op_stream(min_size, max_size):
    return st.lists(
        st.tuples(
            st.sampled_from(ALL_OPS),
            st.integers(1, KEYSPACE),
            st.integers(0, 10**6),
            st.integers(0, 40),  # range span
        ),
        min_size=min_size,
        max_size=max_size,
    )


# ------------------------------------------------------------------ oracle
def oracle_answer(kv, op, q, span):
    """The Python dict + sorted ground truth for one read op."""
    sk = sorted(kv)
    if op == "lookup":
        return (kv.get(q, -1) if q in kv else -1, q in kv)
    if op == "predecessor":
        cands = [x for x in sk if x <= q]
        if not cands:
            return (NO_PRED, -1, False)
        return (cands[-1], kv[cands[-1]], True)
    if op == "successor":
        cands = [x for x in sk if x >= q]
        if not cands:
            return (SENT_K, -1, False)
        return (cands[0], kv[cands[0]], True)
    in_range = [x for x in sk if q <= x <= q + span]
    if op == "range_count":
        return (len(in_range),)
    head = in_range[:SCAN_K]
    keys = head + [SENT_K] * (SCAN_K - len(head))
    vals = [kv[x] for x in head] + [-1] * (SCAN_K - len(head))
    return (keys, vals, min(len(in_range), SCAN_K))


def check_read(name, kv, op, q, span, got):
    exp = oracle_answer(kv, op, q, span)
    ctx = f"{name}: {op}({q}, span={span})"
    if op == "lookup":
        val, found = got
        assert bool(found) == exp[1], ctx
        if exp[1]:
            assert int(val) == exp[0], ctx
    elif op in ("predecessor", "successor"):
        key, val, ok = got
        assert bool(ok) == exp[2], ctx
        assert int(key) == exp[0], f"{ctx}: key {int(key)} != {exp[0]}"
        if exp[2]:
            assert int(val) == exp[1], ctx
    elif op == "range_count":
        assert int(got) == exp[0], f"{ctx}: count {int(got)} != {exp[0]}"
    else:
        keys, vals, taken = got
        assert int(taken) == exp[2], ctx
        assert np.asarray(keys).tolist() == exp[0], ctx
        assert np.asarray(vals).tolist() == exp[1], ctx


# ----------------------------------------------------------------- driving
def flush_reads(name, eng, kv, reads):
    """Evaluate a read span at fixed padded shapes, checking each lane."""
    by_op = {}
    for op, q, span in reads:
        by_op.setdefault(op, []).append((q, span))
    for op, items in by_op.items():
        qs = np.array([q for q, _ in items], np.int32)
        spans = np.array([s for _, s in items], np.int32)
        pad = PROBE_PAD - qs.size
        qp = np.pad(qs, (0, pad), mode="edge")
        sp = np.pad(spans, (0, pad), mode="edge")
        if op in ("range_count", "range_scan"):
            res = eng.query(op, qp, qp + sp, k=SCAN_K)
        else:
            res = eng.query(op, qp)
        cols = res if isinstance(res, tuple) else (res,)
        for i, (q, span) in enumerate(items):
            lane = tuple(np.asarray(c)[i] for c in cols)
            check_read(name, kv, op, q, span, lane if len(lane) > 1 else lane[0])


def flush_writes(eng, pending):
    """Apply a write span through the device ingest at a fixed jit shape."""
    keys = np.array([k for k, _, _ in pending], np.int32)
    vals = np.array([v for _, v, _ in pending], np.int32)
    dels = np.array([d for _, _, d in pending], bool)
    pad = (-keys.size) % WRITE_PAD
    valid = np.ones(keys.size + pad, bool)
    if pad:
        valid[keys.size:] = False
        keys, vals, dels = (np.pad(a, (0, pad)) for a in (keys, vals, dels))
    eng.apply_ops(keys, vals, dels, valid)


def run_stream(name, eng, kv, ops):
    """One submission-ordered pass: write spans flush before the next read."""
    reads, writes = [], []
    for op, key, value, span in ops:
        if op in ("insert", "delete"):
            if reads:
                flush_reads(name, eng, kv, reads)
                reads = []
            writes.append((key, value, op == "delete"))
            if op == "delete":
                kv.pop(key, None)
            else:
                kv[key] = value
            if len(writes) == WRITE_PAD:
                flush_writes(eng, writes)
                writes = []
        else:
            if writes:
                flush_writes(eng, writes)
                writes = []
            reads.append((op, key, span))
            if len(reads) == PROBE_PAD:
                flush_reads(name, eng, kv, reads)
                reads = []
    if writes:
        flush_writes(eng, writes)
    if reads:
        flush_reads(name, eng, kv, reads)


def probe_all_ops(name, eng, kv, rng):
    """One fixed probe batch over every op kind (pre/post-compaction pin)."""
    qs = rng.integers(1, KEYSPACE + 60, PROBE_PAD).astype(np.int32)
    reads = [(op, int(q), int(q) % 37) for op in READ_OPS for q in qs[:6]]
    flush_reads(name, eng, kv, reads)


# The engines persist across hypothesis examples: each example extends the
# same live stream (state evolves through buffer fills and compactions),
# and compile costs amortize.  The oracle dict travels with its engine.
_ENGINES = {}


def _get_engine(name, cfg):
    if name not in _ENGINES:
        keys, values = make_tree_data(120, seed=zlib.crc32(name.encode()) % 97, spacing=3)
        eng = BSTEngine(keys, values, cfg)
        _ENGINES[name] = (eng, dict(zip(keys.tolist(), values.tolist())))
    return _ENGINES[name]


REF_CONFIGS = {
    "hrz": EngineConfig(strategy="hrz", delta_capacity=48, delta_high_water=40),
    "dup4": EngineConfig(
        strategy="dup", n_trees=4, delta_capacity=48, delta_high_water=40
    ),
    "hyb4q": EngineConfig(
        strategy="hyb", n_trees=4, mapping="queue",
        delta_capacity=48, delta_high_water=40,
    ),
}


@given(op_stream(30, 60), st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_engine_differential_ref(ops, seed):
    """Random op streams == dict oracle, all strategies, reference path."""
    rng = np.random.default_rng(seed % 2**32)
    for name, cfg in REF_CONFIGS.items():
        eng, kv = _get_engine(name, cfg)
        run_stream(name, eng, kv, ops)
        probe_all_ops(name, eng, kv, rng)


def test_engine_differential_ref_post_compaction():
    """The same engines, probed immediately after a forced compaction."""
    rng = np.random.default_rng(7)
    for name, cfg in REF_CONFIGS.items():
        eng, kv = _get_engine(name, cfg)
        run_stream(name, eng, kv, [("insert", 17, 1700, 0), ("delete", 18, 0, 0)])
        kv[17] = 1700
        kv.pop(18, None)
        probe_all_ops(name + "/pre", eng, kv, rng)
        eng.compact()
        assert eng.pending_writes() == 0
        probe_all_ops(name + "/post", eng, kv, rng)


@given(op_stream(14, 24), st.integers(0, 2**31 - 1))
@settings(max_examples=2, deadline=None)
def test_engine_differential_kernel(ops, seed):
    """The Pallas forest-kernel path (interpret mode): same differential,
    shorter streams -- the kernel is exercised per span, pre- and (via the
    buffer filling up) post-compaction."""
    rng = np.random.default_rng(seed % 2**32)
    for name, strategy, n in (("khrz", "hrz", 1), ("kdup4", "dup", 4)):
        cfg = EngineConfig(
            strategy=strategy, n_trees=n, use_kernel=True,
            delta_capacity=32, delta_high_water=28,
        )
        eng, kv = _get_engine(name, cfg)
        run_stream(name, eng, kv, ops)
        probe_all_ops(name, eng, kv, rng)


def test_engine_differential_kernel_hyb_post_compaction():
    """Hybrid through the kernel path, pre/post explicit compaction."""
    cfg = EngineConfig(
        strategy="hyb", n_trees=4, mapping="queue", use_kernel=True,
        delta_capacity=32, delta_high_water=28,
    )
    eng, kv = _get_engine("khyb4q", cfg)
    rng = np.random.default_rng(11)
    ops = [
        ("insert", 7, 70, 0), ("lookup", 7, 0, 0), ("delete", 7, 0, 0),
        ("lookup", 7, 0, 0), ("insert", 7, 71, 0),  # re-insert
        ("predecessor", 8, 0, 0), ("range_scan", 1, 0, 39),
    ]
    run_stream("khyb4q", eng, kv, ops)
    probe_all_ops("khyb4q/pre", eng, kv, rng)
    eng.compact()
    probe_all_ops("khyb4q/post", eng, kv, rng)


# ------------------------------------------------- adversarial hyb skew
def _assert_all_ops_match(tag, eng, kv, q, spans):
    """Every read op over the full batch, each lane against the module's
    one dict+sorted oracle (``oracle_answer`` via ``check_read``)."""
    for op in READ_OPS:
        if op in ("range_count", "range_scan"):
            got = eng.query(op, q, q + spans, k=SCAN_K)
        else:
            got = eng.query(op, q)
        cols = got if isinstance(got, tuple) else (got,)
        arrs = [np.asarray(c) for c in cols]
        for i in range(q.size):
            lane = tuple(a[i] for a in arrs)
            check_read(
                f"{tag}", kv, op, int(q[i]), int(spans[i]),
                lane if len(lane) > 1 else lane[0],
            )


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=2, deadline=None)
def test_hyb_adversarial_skew_replay(seed):
    """Worst-case hybrid skew: every query routes to vertical subtree 0,
    overflowing the per-subtree dispatch buffers so most lanes resolve
    through the stall-round replay (in-kernel on the Pallas path,
    DESIGN.md §8).  Both mappings and both paths must stay bit-identical
    to the dict+sorted oracle, with a live delta buffer, pre- and
    post-compaction."""
    from repro.core import plans as plans_lib
    from repro.core import tree as tree_lib

    rng = np.random.default_rng(seed % 2**32)
    keys, values = make_tree_data(150, seed=13, spacing=3)
    engines = {
        f"{mapping}/kernel={uk}": BSTEngine(
            keys,
            values,
            EngineConfig(
                strategy="hyb", n_trees=4, mapping=mapping, use_kernel=uk,
                delta_capacity=32, delta_high_water=28,
            ),
        )
        for mapping, uk in (
            ("queue", False),
            ("queue", True),
            ("direct", False),
            ("direct", True),
        )
    }
    kv = dict(zip(keys.tolist(), values.tolist()))

    any_eng = next(iter(engines.values()))
    # every key strictly below the root's left child routes left-left:
    # vertical subtree 0 (split_level 2 -> the register layer is the top
    # two levels of the flat operand)
    bound = int(np.asarray(any_eng.tree.keys)[1])
    B = 600
    q = rng.integers(1, bound, B).astype(np.int32)
    dest, _, found = tree_lib.register_layer_route(any_eng.tree, q, 2)
    assert np.all((np.asarray(dest) == 0) | np.asarray(found))
    # the scenario must actually overflow: one subtree receives a whole
    # chunk while its buffer holds only the slack-scaled fair share
    plan = any_eng.plan
    assert B > plans_lib.hyb_capacity(plan, B)  # reference-path granularity
    assert 512 > plans_lib.hyb_capacity(plan, 512)  # kernel block_q chunks

    spans = rng.integers(0, 30, B).astype(np.int32)
    wk = rng.choice(np.arange(1, bound, dtype=np.int32), 24, replace=False)
    wv = rng.integers(0, 10**6, 24).astype(np.int32)
    wd = rng.integers(0, 3, 24) == 0
    for tag, eng in engines.items():
        eng.apply_ops(wk, wv, wd)
    for k_, v_, d_ in zip(wk.tolist(), wv.tolist(), wd.tolist()):
        if d_:
            kv.pop(k_, None)
        else:
            kv[k_] = v_

    for tag, eng in engines.items():
        assert eng.pending_writes() > 0  # the delta buffer rides the replay
        _assert_all_ops_match(f"{tag}/pre", eng, kv, q, spans)
        eng.compact()
        _assert_all_ops_match(f"{tag}/post", eng, kv, q, spans)


# ------------------------------------------------------- server acceptance
@pytest.mark.parametrize("name", sorted(REF_CONFIGS))
def test_server_mixed_stream_500_ops(name):
    """≥ 500 mixed ops (90/10 read/write) through BSTServer's typed write
    request kinds, drained in chunks, bit-identical to the oracle across
    every strategy -- the DESIGN.md §7 acceptance gate."""
    cfg = REF_CONFIGS[name]
    keys, values = make_tree_data(150, seed=3, spacing=3)
    srv = BSTServer(keys, values, cfg, chunk_size=64, scan_k=SCAN_K)
    kv = dict(zip(keys.tolist(), values.tolist()))
    rng = np.random.default_rng(zlib.crc32(name.encode()) % 2**31)

    n_ops = 520
    kinds = rng.choice(
        np.array(ALL_OPS), n_ops, p=[0.06, 0.04, 0.35, 0.15, 0.15, 0.15, 0.10]
    )
    tickets = []  # (ticket, op, key, span, kv-at-submit-time)
    for i, op in enumerate(kinds.tolist()):
        q = int(rng.integers(1, KEYSPACE))
        span = int(rng.integers(0, 40))
        if op == "insert":
            v = int(rng.integers(0, 10**6))
            t = srv.submit_write(q, v)
            kv[q] = v
            tickets.append((t, op, q, span, None))
        elif op == "delete":
            t = srv.submit_delete(q)
            kv.pop(q, None)
            tickets.append((t, op, q, span, None))
        else:
            if op in ("range_count", "range_scan"):
                t = srv.submit_range(q, q + span, op=op)
            else:
                t = srv.submit(q, op=op)
            tickets.append((t, op, q, span, dict(kv)))
        if (i + 1) % 50 == 0 or i == n_ops - 1:
            results = srv.drain()
            for t, top, tq, tspan, snap in tickets:
                got = results[t]
                if top in ("insert", "delete"):
                    assert int(got[0]) == 1
                    continue
                lane = tuple(np.asarray(c)[0] for c in got)
                check_read(
                    f"{name}/server", snap, top, tq, tspan,
                    lane if len(lane) > 1 else lane[0],
                )
            tickets = []
    assert srv.stats.updates > 0
    assert srv.stats.compactions > 0, "stream must cross the high-water mark"
    assert srv.pending() == 0


# --------------------------------------------------- sharded serving paths
def test_sharded_differential_all_strategies(multi_device_host):
    """Sharded == single-chip, bit for bit, on the same op sequence.

    A sharded BSTServer (forced 8-device host) and a single-chip server
    take IDENTICAL submissions -- mixed writes, deletes and every read op
    -- and every drained column must match exactly, for hrz / dup / hyb,
    reference and Pallas-kernel descents, with reads landing both before
    and after compactions (the delta capacity is sized so the stream
    crosses the high-water mark mid-sequence)."""
    multi_device_host("""
        from repro.core import distributed as D
        from repro.core.engine import EngineConfig
        from repro.data.keysets import make_tree_data
        from repro.serving import BSTServer

        keys, values = make_tree_data(150, seed=3, spacing=3)
        rng = np.random.default_rng(5)

        def drive(srv, ref, rounds, n_writes, n_reads):
            compact_seen = 0
            for r in range(rounds):
                tickets = []
                wk = rng.integers(1, 600, n_writes).astype(np.int32)
                wv = rng.integers(0, 10**6, n_writes).astype(np.int32)
                tickets.append((srv.submit_write(wk, wv), ref.submit_write(wk, wv)))
                dk = rng.integers(1, 600, max(1, n_writes // 3)).astype(np.int32)
                tickets.append((srv.submit_delete(dk), ref.submit_delete(dk)))
                q = rng.integers(1, 660, n_reads).astype(np.int32)
                span = rng.integers(0, 40, n_reads).astype(np.int32)
                for op in ("lookup", "predecessor", "successor"):
                    tickets.append((srv.submit(q, op=op), ref.submit(q, op=op)))
                for op in ("range_count", "range_scan"):
                    tickets.append((
                        srv.submit_range(q, q + span, op=op),
                        ref.submit_range(q, q + span, op=op),
                    ))
                out_s, out_r = srv.drain(), ref.drain()
                for ts, tr in tickets:
                    for cs, cr in zip(out_s[ts], out_r[tr]):
                        assert np.array_equal(np.asarray(cs), np.asarray(cr)), (
                            r, ts)
                if compact_seen == 0 and srv.stats.compactions > 0:
                    compact_seen = r + 1  # later rounds probe post-compaction
            assert srv.stats.compactions == ref.stats.compactions
            return compact_seen

        for strategy, use_kernel, rounds, n_reads in (
            ("hrz", False, 4, 96), ("dup", False, 4, 96), ("hyb", False, 4, 96),
            ("hrz", True, 2, 48), ("dup", True, 2, 48), ("hyb", True, 2, 48),
        ):
            cfg = EngineConfig(
                strategy=strategy,
                n_trees=1 if strategy == "hrz" else 4,
                use_kernel=use_kernel,
                delta_capacity=48,
                delta_high_water=40,
            )
            mesh = D.make_serving_mesh(strategy)
            srv = BSTServer(keys, values, cfg, chunk_size=32, scan_k=4, mesh=mesh)
            ref = BSTServer(keys, values, cfg, chunk_size=32, scan_k=4)
            compact_round = drive(srv, ref, rounds, n_writes=24, n_reads=n_reads)
            # pre- AND post-compaction reads must both have been compared
            assert srv.stats.compactions > 0, (strategy, use_kernel)
            assert 0 < compact_round <= rounds, (strategy, use_kernel)
            print("ok", strategy, "kernel" if use_kernel else "ref",
                  "compactions", srv.stats.compactions)
        print("ALL OK")
    """, timeout=2400)


def test_sharded_server_soak_mixed_accounting(multi_device_host):
    """≥ 500-op mixed read/write soak through the sharded server, per mix.

    Beyond correctness (lookups cross-checked against a dict oracle), the
    per-op ``OpStats`` lane accounting must tie out EXACTLY against the
    submitted op counts: one lane per point/write/delete key, two per
    range request, busy seconds partitioning into the per-op
    attributions; and the phase counters must count every drain and hold
    the chunk loop's spans inside the read busy time."""
    multi_device_host("""
        from repro.core import distributed as D
        from repro.core.engine import EngineConfig
        from repro.data.keysets import make_tree_data
        from repro.serving import BSTServer

        keys, values = make_tree_data(150, seed=9, spacing=3)
        for mix, write_frac in (("90_10", 0.10), ("50_50", 0.50)):
            rng = np.random.default_rng(17 if mix == "90_10" else 23)
            cfg = EngineConfig(
                strategy="hyb", n_trees=4,
                delta_capacity=64, delta_high_water=24,
            )
            srv = BSTServer(
                keys, values, cfg, chunk_size=64, scan_k=4,
                mesh=D.make_serving_mesh("hyb"),
            )
            kv = dict(zip(keys.tolist(), values.tolist()))
            n_ops = 520
            counts = {}
            expected = {}  # ticket -> (op, key, kv-at-submit)
            kinds = ("write", "delete", "lookup", "predecessor",
                     "successor", "range_count", "range_scan")
            w = write_frac
            probs = [w * 0.7, w * 0.3] + [(1 - w) / 5] * 5
            choice = rng.choice(np.array(kinds), n_ops, p=probs)
            for i, op in enumerate(choice.tolist()):
                q = int(rng.integers(1, 500))
                counts[op] = counts.get(op, 0) + 1
                if op == "write":
                    v = int(rng.integers(0, 10**6))
                    t = srv.submit_write(q, v)
                    kv[q] = v
                elif op == "delete":
                    t = srv.submit_delete(q)
                    kv.pop(q, None)
                elif op in ("range_count", "range_scan"):
                    t = srv.submit_range(q, q + 30, op=op)
                else:
                    t = srv.submit(q, op=op)
                    if op == "lookup":
                        expected[t] = (q, dict(kv))
                if (i + 1) % 80 == 0 or i == n_ops - 1:
                    results = srv.drain()
                    for t, (q, snap) in expected.items():
                        val, found = results[t]
                        assert bool(found[0]) == (q in snap), (mix, q)
                        if q in snap:
                            assert int(val[0]) == snap[q], (mix, q)
                    expected = {}
            s = srv.stats
            assert s.requests == n_ops and s.submitted == n_ops
            assert s.served == n_ops and srv.pending() == 0
            # --- per-op lane accounting ties out against the op counts:
            # singleton requests -> one lane per point/write/delete op, two
            # per range request (the lo||hi concatenated descent)
            for op, n in counts.items():
                st = s.per_op[op]
                assert st.served == n, (mix, op)
                lanes = 2 * n if op.startswith("range") else n
                assert st.lanes == lanes, (mix, op, st.lanes, lanes)
                assert st.chunks > 0 and st.busy_s > 0, (mix, op)
            assert s.lanes == sum(
                (2 * n if op.startswith("range") else n)
                for op, n in counts.items()
            )
            assert sum(st.lanes for st in s.per_op.values()) == s.lanes
            assert s.drains == -(-n_ops // 80)  # one per flush of the loop
            # read busy attributions partition the read-span walls; write
            # spans attribute their whole wall across their requests
            read_busy = sum(
                st.busy_s for op, st in s.per_op.items()
                if op not in ("write", "delete")
            )
            write_busy = sum(
                st.busy_s for op, st in s.per_op.items()
                if op in ("write", "delete")
            )
            assert abs(read_busy + write_busy - s.busy_s) < 1e-6, mix
            # the engine calls' spans lie inside the read busy time, the
            # ingest span is the write spans' busy time less compaction
            p = s.phase_s
            assert p["dispatch"] + p["sync"] + p["fetch"] <= read_busy, mix
            assert p["ingest"] + p["compact"] <= write_busy, mix
            assert s.updates == counts["write"] + counts["delete"]
            assert s.compactions > 0, mix  # the soak crosses the high-water
            print("ok", mix, "ops", n_ops, "compactions", s.compactions)
        print("ALL OK")
    """, timeout=2400)
