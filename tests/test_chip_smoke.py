"""chip_smoke.py: its phases at a tiny size on the CPU, and its refusals.

The script's phases are plain functions, so the tests drive them here at
small sizes (kernels in interpret mode) with the sizes chosen by the test.
Run as a program, the script must refuse the CPU: it exits non-zero and
prints no result line.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.data.keysets import make_tree_data
from repro.launch import compile_cache
from repro.serving import BSTServer

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = dict(chunk=128, n_chunks=1, delta_capacity=32, scan_k=4)


def test_single_chip_phases_tiny(smoke, capsys):
    """A hybrid preset on the kernel path against the host reference and
    the XLA-gather server, across one compaction."""
    keys, values = make_tree_data(1023, seed=3)
    smoke.smoke_single(keys, values, 4, configs=("Hyb8",), check_kernel=False, **TINY)
    assert "Hyb8: answers to 15 reads match host and XLA path" in capsys.readouterr().out


def test_host_reference_sees_writes_in_order(smoke):
    keys = np.int32([2, 4, 6])
    values = np.int32([20, 40, 60])
    q = np.int32([1, 2, 3, 4, 6, 7])
    workload = [
        ("write", np.int32([3, 4]), np.int32([30, 41])),
        ("delete", np.int32([2, 3])),
        ("read", "lookup", q, None),
        ("read", "predecessor", q, None),
        ("read", "range_scan", q, q + 3),
    ]
    (val, found), (pk, pv, ok), (sk, sv, taken) = smoke.host_answers(
        keys, values, workload, scan_k=2
    )
    assert found.tolist() == [False, False, False, True, True, False]
    assert val.tolist() == [-1, -1, -1, 41, 60, -1]
    assert pk.tolist() == [smoke.NO_PRED_KEY, smoke.NO_PRED_KEY, smoke.NO_PRED_KEY, 4, 6, 6]
    assert ok.tolist() == [False, False, False, True, True, True]
    assert sk[0].tolist() == [4, smoke.SENTINEL_KEY] and taken.tolist() == [1, 1, 2, 2, 1, 0]


def test_kernel_check_refuses_interpret_programs(smoke):
    """On the CPU the programs run the kernel in the interpreter: no
    Mosaic kernel in them, and the check must say so."""
    keys, values = make_tree_data(255, seed=1)
    cfg = EngineConfig(strategy="hrz", use_kernel=True, delta_capacity=16)
    srv = BSTServer(keys, values, cfg, chunk_size=128)
    srv.warmup(("lookup",))
    with pytest.raises(AssertionError, match="no Mosaic kernel"):
        smoke.assert_kernel_programs(srv, 128)


def test_sharded_phase_on_forced_host(multi_device_host):
    """``--chips 4``'s phase over four simulated devices."""
    out = multi_device_host(
        f"""
        sys.path.insert(0, {str(ROOT)!r})
        import chip_smoke
        from repro.data.keysets import make_tree_data
        keys, values = make_tree_data(1023, seed=5)
        chip_smoke.smoke_sharded(
            keys, values, 6, chunk=128, n_chunks=1, delta_capacity=32,
            scan_k=4, devices=jax.devices()[:4],
        )
        """,
        devices=4,
    )
    for strategy in ("hrz", "dup", "hyb"):
        assert f"sharded {strategy} x 4 devices" in out


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


def _ok_line(stdout):
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_script_refuses_cpu():
    out = _run_script(ROOT, SCRIPT)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)
    assert "no TPU" in out.stderr


def test_script_alone_fails(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run_script(tmp_path, lone)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)


def test_compile_cache_dir_env_wins():
    assert compile_cache.cache_dir({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) == "/elsewhere"


def test_compile_cache_dir_fixed_default():
    path = compile_cache.cache_dir({})
    assert path == str(ROOT / ".jax_cache")
    assert path == compile_cache.cache_dir({"JAX_COMPILATION_CACHE_DIR": ""})


def test_enable_compile_cache_sets_only_the_default(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == "/from/env"
        assert jax.config.jax_compilation_cache_dir is None  # JAX reads the env
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
