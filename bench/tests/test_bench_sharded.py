"""The four-chip configuration (``hrz-24m-x4``) at a tiny size, on four
forced host devices, in one subprocess: correct through the harness under
reads and under writes that compact, no device holding an array of the
whole index's size, the router's counters, and the ordered ops with a
non-empty write buffer against the plain reference."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

SCRIPT = """
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import numpy as np
import jax
from bench import harness, reference, traffic
from bench.tests.test_bench_harness import WRITE_MIX, tiny_mix

devices = jax.devices()
# the configuration as the file holds it, cut in size only
config = dict(harness.load_json("configs", "hrz-24m-x4"),
              records=4 * 511, chunk_size=512, delta_capacity=64)
built = []

def make(config, devs):
    built.append(harness.build_server(config, devs))
    return built[-1]

def execute(mix, seconds):
    run, checked = harness.execute(config, mix, 2**31 + 16, seconds, False, time.perf_counter(),
                                   devices, make_server=make)
    return run, checked

out = {{}}
run, checked = execute(tiny_mix(), 0.5)
srv = built[-1]
out["reads"] = {{"correct": checked.correct, "attempted": checked.attempted,
                "chunks": run.engine_calls}}

# what each device holds after build, warm-up and the read window
per_device, largest = {{}}, 0
for a in jax.live_arrays():
    for shard in a.addressable_shards:
        size = int(np.prod(shard.data.shape))
        per_device[shard.device.id] = per_device.get(shard.device.id, 0) + size
        largest = max(largest, size)
out["memory"] = {{"index_slots": srv.snapshot.n_nodes, "largest_shard": largest,
                 "fullest_device": max(per_device.values()), "devices": len(per_device),
                 "subtree_slots": int(srv.memory_nodes_per_device())}}

# the router's counters: one chunk per drain, then one drain of three chunks
srv.reset_stats()
chunk = config["chunk_size"]
keys = traffic.record_keys(config["records"])
srv.submit(keys[:8])
srv.drain()
one = (srv.stats.chunks, srv.stats.routed_slots, srv.stats.overlapped_chunks)
srv.reset_stats()
srv.submit(keys[: 3 * chunk])
srv.drain()
three = (srv.stats.chunks, srv.stats.routed_slots, srv.stats.overlapped_chunks)
out["counters"] = {{"one": one, "three": three, "phases": sorted(srv.stats.phase_s)}}

# ordered ops with a non-empty write buffer, against the plain reference
ref = reference.Reference(keys, traffic.record_values(config["records"]))
rng = np.random.default_rng(5)
wk = rng.integers(0, 2 * config["records"] + 40, 24).astype(np.int32)  # odd keys insert
wv = rng.integers(0, 10**6, wk.size).astype(np.int32)
srv.submit_write(wk, wv)
srv.drain()
ref.upsert(wk, wv)
live = np.flatnonzero(ref.present) + ref.lo
vals = ref.value[live - ref.lo]
q = rng.integers(-5, 2 * config["records"] + 45, 300).astype(np.int32)
hi = (q + rng.integers(0, 30, q.size)).astype(np.int32)
k = srv.scan_k
pk, pv, pok = srv.predecessor(q)
sk, sv, sok = srv.successor(q)
counts = srv.range_count(q, hi)
rk, rv, rc = srv.range_scan(q, hi)
ip = np.searchsorted(live, q, "right") - 1
isu = np.searchsorted(live, q, "left")
end = np.searchsorted(live, hi, "right")
want_counts = np.maximum(end - isu, 0)
bad = {{
    "predecessor": int(np.sum((pok != (ip >= 0)) | (pok & (pk != live[ip]))
                             | (pok & (pv != vals[ip])))),
    "successor": int(np.sum((sok != (isu < live.size))
                           | (sok & (sk != live[np.minimum(isu, live.size - 1)]))
                           | (sok & (sv != vals[np.minimum(isu, live.size - 1)])))),
    "range_count": int(np.sum(counts != want_counts)),
}}
take = np.minimum(want_counts, k)
cols = isu[:, None] + np.arange(k)[None, :]
valid = np.arange(k)[None, :] < take[:, None]
safe = np.minimum(cols, live.size - 1)
want_rk = np.where(valid, live[safe], np.iinfo(np.int32).max)
want_rv = np.where(valid, vals[safe], reference.SENTINEL_VALUE)
bad["range_scan"] = int(np.sum((rc != take)[:, None] | (rk != want_rk) | (rv != want_rv)))
out["ordered"] = {{"bad": bad, "buffered": int(srv.engine.pending_writes()),
                  "inserted": int(np.sum(~np.isin(wk, keys)))}}
del srv, built[:]

# a mix with updates, few requests of many keys each: the warm-up and the
# window compact the buffer
run, checked = execute(tiny_mix(**dict(WRITE_MIX, clients=4, keys_per_request=16)), 3.0)
out["writes"] = {{"correct": checked.correct, "compactions": run.compactions,
                 "attempted": checked.attempted, "chunks": run.engine_calls}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded():
    code = SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_reads_are_correct(sharded):
    assert sharded["reads"]["correct"] and sharded["reads"]["attempted"] > 0


def test_writes_compact_and_stay_correct(sharded):
    assert sharded["writes"]["correct"] and sharded["writes"]["compactions"] >= 1


def test_no_device_holds_the_whole_index(sharded):
    m = sharded["memory"]
    assert m["devices"] == 4
    sub = m["subtree_slots"]  # keys of one subtree plus the register layer
    assert m["largest_shard"] < m["index_slots"]
    # the subtree's keys and values, the replicated register layer and
    # write buffer, and a chunk's worth of small arrays
    assert m["fullest_device"] <= 2 * sub + m["index_slots"] // 2, m


def test_router_counters(sharded):
    c = sharded["counters"]
    chunks, routed, overlapped = c["one"]
    # chips x chips x capacity, the capacity being the local batch of 512 / 4
    assert (chunks, routed, overlapped) == (1, 4 * 4 * 128, 0)
    chunks, routed, overlapped = c["three"]
    assert (chunks, routed, overlapped) == (3, 3 * 4 * 4 * 128, 2)
    assert {"shard_put", "dispatch", "sync", "fetch"} <= set(c["phases"])


@pytest.mark.parametrize("op", ["predecessor", "successor", "range_count", "range_scan"])
def test_ordered_ops_with_a_buffer_match_the_reference(sharded, op):
    o = sharded["ordered"]
    assert o["buffered"] > 0 and o["inserted"] > 0
    assert o["bad"][op] == 0
