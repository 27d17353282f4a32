"""A whole run of a tiny cell on the CPU, through the harness's own phases:
answers checked against the reference, the control and the faults caught,
cells, mixes and metrics found by name, and no result without a chip."""

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, reference, traffic  # noqa: E402

# 4095 records (height 11), 512-lane chunks, a 64-slot write buffer: the
# interpreted kernel stays fast, and a few hundred updates compact it.
TINY = {
    "records": 4095, "strategy": "hrz", "n_trees": 1, "mapping": "queue",
    "delta_capacity": 64, "chunk_size": 512, "scan_k": 8, "chips": 1,
}


def tiny_mix(name: str = "ycsb-c-sat", **over) -> dict:
    mix = harness.load_json("mixes", name)
    mix.update(clients=8, pool_requests=64, warmup={"drains": 2})
    mix.update(over)
    return mix


# reads and writes of several keys each: a drain packs both kinds, and a
# few drains fill the tiny write buffer past its high-water mark
WRITE_MIX = {"ops": {"lookup": 0.5, "update": 0.5}, "keys_per_request": 4, "clients": 16}


class CPU:
    device_kind = "cpu"

    def memory_stats(self):
        return None


def run_tiny(config, mix, seconds=0.5, seed=2**31 + 77, **kw):
    return harness.execute(config, mix, seed, seconds, False, time.perf_counter(), [CPU()], **kw)


def reference_server(control=None):
    def make(config, devices):
        n = config["records"]
        return reference.ReferenceServer(
            traffic.record_keys(n), traffic.record_values(n), control
        )
    return make


def test_closed_cell_is_correct():
    run, checked = run_tiny(TINY, tiny_mix())
    assert checked.correct, checked.checks
    assert checked.attempted > 0 and checked.failed == 0
    assert checked.attempted % 8 == 0  # every drain answered all 8 clients
    assert run.keys_answered == checked.attempted and run.engine_calls > 0
    assert harness.reader("ops_per_s")(run) > 0
    assert harness.reader("frontend_ms")(run) >= 0


def test_write_cell_is_correct_across_a_compaction():
    run, checked = run_tiny(TINY, tiny_mix(**WRITE_MIX), seconds=1.0)
    assert checked.correct, checked.checks
    assert run.compactions >= 1
    assert run.live_records == TINY["records"]


@pytest.mark.parametrize(
    "mix, control, correct",
    [
        ({}, None, True),
        ({}, "int16_keys", False),
        (WRITE_MIX, None, True),
    ],
    ids=["reference", "control-int16-keys", "reference-writes"],
)
def test_control_in_the_programs_place(mix, control, correct):
    _, checked = run_tiny(TINY, tiny_mix(**mix), make_server=reference_server(control))
    assert checked.correct is correct, checked.checks
    if not correct:
        assert checked.checks["mismatched_keys"]["value"] > 0


def _drop_writes(monkeypatch):
    """A step that returns its state unchanged: ingest keeps the buffer."""
    from repro.core.engine import BSTEngine

    monkeypatch.setattr(BSTEngine, "_ingest_step", lambda self, delta, *a: delta)


def _patch_chunks(monkeypatch, alter):
    from repro.serving import BSTServer

    orig = BSTServer._query_chunk

    def broken(self, op, a, b):
        return alter(tuple(np.array(c) for c in orig(self, op, a, b)))

    monkeypatch.setattr(BSTServer, "_query_chunk", broken)


def _half_batch(monkeypatch):
    """Half of each batch left out: the second half of the keys a drain
    packs for the engine is never answered."""
    from repro.serving import BSTServer

    orig = BSTServer._serve_stream

    def broken(self, op, a, b):
        cols = [np.array(c) for c in orig(self, op, a, b)]
        half = cols[0].shape[0] // 2
        cols[0][half:] = reference.SENTINEL_VALUE
        cols[1][half:] = False
        return cols

    monkeypatch.setattr(BSTServer, "_serve_stream", broken)


def _altered_answer(monkeypatch):
    """One answer altered where it is produced: the first lane's value."""
    def alter(cols):
        values, found = cols
        values[0] += 1
        return values, found
    _patch_chunks(monkeypatch, alter)


def _dropped_answer(monkeypatch):
    """An answer that never comes: each drain leaves its first ticket out."""
    from repro.serving import BSTServer

    orig = BSTServer.drain

    def broken(self):
        out = orig(self)
        out.pop(min(out), None)
        return out

    monkeypatch.setattr(BSTServer, "drain", broken)


@pytest.mark.parametrize(
    "fault, mix",
    [
        (_drop_writes, WRITE_MIX),
        (_half_batch, {}),
        (_altered_answer, {}),
        (_dropped_answer, {}),
    ],
    ids=["state-unchanged", "half-batch", "altered-answer", "dropped-answer"],
)
def test_faults_make_the_run_incorrect(monkeypatch, fault, mix):
    fault(monkeypatch)
    _, checked = run_tiny(TINY, tiny_mix(**mix))
    assert not checked.correct
    assert checked.failed > 0


SHARDED = """
import json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness
from bench.tests.test_bench_harness import TINY, tiny_mix

devices = jax.devices()
config = dict(TINY, chips=4)
built = []

def make(config, devs):
    built.append(harness.build_server(config, devs))
    return built[-1]

run, checked = harness.execute(config, tiny_mix(), 5, 0.5, False, time.perf_counter(),
                               devices, make_server=make)
try:
    harness.build_server(config, devices[:1])
    refused = False
except ValueError:
    refused = True
mesh = built[0].mesh
print(json.dumps({{"correct": checked.correct, "attempted": checked.attempted,
                  "mesh_devices": 0 if mesh is None else mesh.devices.size,
                  "refused": refused}}))
"""


def test_a_multi_chip_config_is_served_sharded():
    """A configuration of four chips builds a server sharded over a mesh of
    four devices (forced host devices here) and answers correctly; given
    another number of devices it is refused."""
    code = SHARDED.format(root=str(ROOT), src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env=env, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {"correct": True, "attempted": out["attempted"], "mesh_devices": 4,
                   "refused": True}
    assert out["attempted"] > 0


def test_new_files_are_found_by_name(tmp_path):
    (tmp_path / "mixes").mkdir()
    (tmp_path / "metrics").mkdir()
    mix = dict(harness.load_json("mixes", "ycsb-c-sat"), clients=3)
    (tmp_path / "mixes" / "ycsb-x-new.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "answered.py").write_text("def read(run):\n    return run.keys_answered\n")
    assert harness.load_json("mixes", "ycsb-x-new", tmp_path)["clients"] == 3
    run = harness.Run(config=TINY, mix=mix, seconds=1.0, keys_answered=42)
    assert harness.reader("answered.sat", tmp_path)(run) == 42
    assert harness.split_cell("hrz-6m.ycsb-x-new") == ("hrz-6m", "ycsb-x-new")
    bench = {
        "end_to_end": [
            {"name": "setup_s"},
            {"name": "ops_per_s", "workloads": ["a.m"]},
        ],
        "per_layer": [
            {"name": "answered.sat", "moves": "ops_per_s"},
            {"name": "kernel_ms.rate", "moves": "p99_ms", "workloads": ["b.m"]},
        ],
    }
    assert [m["name"] for m in harness.metric_specs(bench, "a.m", False)] == ["setup_s", "ops_per_s"]
    assert [m["name"] for m in harness.metric_specs(bench, "a.m", True)] == ["answered.sat"]
    assert [m["name"] for m in harness.metric_specs(bench, "b.m", True)] == ["kernel_ms.rate"]


def test_every_metric_and_cell_file_exists():
    bench = harness.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
    for cell in bench["workloads"]:
        config, mix = harness.split_cell(cell["name"])
        assert (config, mix) == (cell["config"], cell["traffic"])
        assert harness.load_json("configs", config)["chips"] == cell["chips"]
        harness.load_json("mixes", mix)


def test_reference_applies_writes_in_order():
    ref = reference.Reference(np.array([2, 4, 6]), np.array([0, 1, 2]))
    ref.upsert([4, 4, 5], [10, 11, 12])  # last write of a key wins; 5 is new
    values, found = ref.lookup([4, 5, 7])
    np.testing.assert_array_equal(values, [11, 12, reference.SENTINEL_VALUE])
    np.testing.assert_array_equal(found, [True, True, False])


@pytest.mark.parametrize("workload", ["hrz-6m.ycsb-c-sat", "no-such.cell"])
def test_run_cell_without_a_chip_prints_no_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run_cell.py"), "--workload", workload,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_checked_compares_against_limits():
    ok = harness.Checked(1, 0, {"a": {"value": 0, "limit": 0}})
    bad = dataclasses.replace(ok, checks={"a": {"value": 1, "limit": 0}})
    assert ok.correct and not bad.correct


def test_reference_table_grows_and_controls_break_it():
    ref = reference.Reference(np.array([10, 12]), np.array([0, 1]))
    ref.upsert([3, 70000], [5, 6])
    values, found = ref.lookup([3, 10, 70000, 11, -4])
    np.testing.assert_array_equal(values, [5, 0, 6, reference.SENTINEL_VALUE, reference.SENTINEL_VALUE])
    np.testing.assert_array_equal(found, [True, True, True, False, False])
    assert ref.live() == 4
    low = reference.Reference(np.array([10, 12, 70000]), np.array([0, 1, 2]), "int16_keys")
    # 12 shares its high half with 10, the first live key: answered as 10
    np.testing.assert_array_equal(low.lookup([12, 70000])[0], [0, 2])
    with pytest.raises(ValueError):
        reference.Reference(np.array([10]), np.array([0]), "stale")
