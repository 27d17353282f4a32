"""repro.analysis: lint rules, contracts, dead-code drift, runtime gate.

The regression heart of the suite: re-introduce the exact bug classes the
analyzer exists to catch (a tracer-bool leak, host ops under jit, a
delta-content-dependent shape that retraces per drain) and assert the
right pass flags each one -- then assert the real tree is clean and the
steady-state serve gate holds on every strategy.
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, deadcode, gate, invariants, lint, report, runtime


def _lint_src(tmp_path, src, name="case.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    hard, _soft = lint.lint_paths([str(p)], allowlist=None)
    return {v.rule for v in hard}, hard


# --------------------------------------------------------------------- lint
def test_lint_catches_tracer_leak(tmp_path):
    # The classic leak symptom: branching on a traced value.  Outside jit
    # it is a silent sync; inside it is TracerBoolConversionError.
    rules, _ = _lint_src(
        tmp_path,
        """
        import jax.numpy as jnp

        def route(x):
            y = jnp.abs(x)
            if y > 0:
                return y
            return x
        """,
    )
    assert "ANA001" in rules


def test_lint_catches_host_ops_under_jit(tmp_path):
    rules, hard = _lint_src(
        tmp_path,
        """
        import jax
        import numpy as np

        @jax.jit
        def bad(x):
            v = np.asarray(x)
            print(v)
            return x
        """,
    )
    assert "ANA002" in rules
    assert sum(v.rule == "ANA002" for v in hard) == 2  # np.asarray + print


def test_lint_catches_jit_in_loop_retrace(tmp_path):
    rules, _ = _lint_src(
        tmp_path,
        """
        import jax

        def drain(chunks):
            out = []
            for c in chunks:
                f = jax.jit(lambda v: v + 1)
                out.append(f(c))
            return out
        """,
    )
    assert "ANA004" in rules


def test_lint_catches_implicit_host_pull(tmp_path):
    rules, _ = _lint_src(
        tmp_path,
        """
        import jax.numpy as jnp

        def count(x):
            total = jnp.sum(x)
            return int(total)
        """,
    )
    assert "ANA005" in rules


def test_lint_catches_kernel_host_op(tmp_path):
    rules, _ = _lint_src(
        tmp_path,
        """
        import numpy as np

        def step_kernel(keys_ref, out_ref):
            out_ref[...] = np.asarray(keys_ref)
        """,
    )
    assert "ANA003" in rules


def test_lint_array_metadata_is_not_a_pull(tmp_path):
    # int(x.shape[0]) is host metadata, not a device sync.
    rules, _ = _lint_src(
        tmp_path,
        """
        import jax.numpy as jnp

        def pad(x):
            y = jnp.abs(x)
            n = int(y.shape[0])
            return n
        """,
    )
    assert "ANA005" not in rules


def test_lint_flags_unallowlisted_explicit_fetch(tmp_path):
    serving = tmp_path / "serving"
    serving.mkdir()
    p = serving / "hot.py"
    p.write_text("import jax\n\ndef pull(x):\n    return jax.device_get(x)\n")
    hard, _ = lint.lint_paths([str(p)], allowlist=None)
    assert {v.rule for v in hard} == {"ANA006"}


def test_hot_path_tree_is_lint_clean():
    hard, soft = lint.lint_paths(
        [
            "src/repro/core",
            "src/repro/kernels",
            "src/repro/serving",
            "src/repro/launch",
        ]
    )
    assert hard == [], report.render_all(hard)
    # the sanctioned syncs stay visible as allowlisted, not invisible
    assert {v.rule for v in soft} >= {"ANA006"}


# ---------------------------------------------------------- runtime detector
def test_compile_watch_catches_content_dependent_shape_retrace():
    # The PR4-era bug class: syncing the delta count and slicing to it
    # gives every drain a fresh shape -- a retrace per content change.
    f = jax.jit(lambda a: a * 2)
    f(jnp.arange(8))  # warm
    with runtime.compile_watch() as cw:
        f(jnp.arange(8))
    assert cw.count == 0, cw.messages()
    count = jnp.int32(5)
    with runtime.compile_watch() as cw:
        n = int(count)  # the content sync
        f(jnp.arange(8)[:n])  # content-dependent shape
    assert cw.count >= 1


def test_transfer_watch_counts_sanctioned_fetches():
    f = jax.jit(lambda a: a + 1)
    x = jnp.arange(4)
    f(x)  # warm
    with runtime.transfer_watch() as tw:
        got = runtime.device_fetch(f(x))
    np.testing.assert_array_equal(got, np.arange(4) + 1)
    assert tw.fetches == 1


def test_transfer_watch_blocks_implicit_host_to_device():
    f = jax.jit(lambda a: a + 1)
    f(jnp.arange(4))  # warm
    with runtime.transfer_watch():
        with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
            f(np.arange(4))  # numpy operand = implicit h2d under the guard


# ---------------------------------------------------------------- contracts
def test_contracts_pass_on_current_tree():
    errors = contracts.run_contracts()
    assert errors == [], report.render_all(errors)


def test_contract_rows_catch_output_drift():
    errors = []
    # lookup declares (values, found); a bare values row must fail
    contracts._check_outputs(
        "t", "lookup", (jax.ShapeDtypeStruct((8,), jnp.int32),), 8, 4, errors
    )
    assert errors
    errors = []
    # wrong dtype on found
    contracts._check_outputs(
        "t",
        "lookup",
        (
            jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
        ),
        8,
        4,
        errors,
    )
    assert errors


def test_invariants_reject_bad_configs():
    with pytest.raises(ValueError):
        invariants.check_delta_config(8, 9)
    with pytest.raises(ValueError):
        invariants.check_chunk_divides(100, 8, "model")
    with pytest.raises(ValueError):
        invariants.check_forest_nodes(30, 4)
    assert invariants.split_level_for(4) == 2


# ----------------------------------------------------------------- deadcode
def test_deadcode_flags_unreachable_module(tmp_path):
    pkg = tmp_path / "src" / "repro"
    (pkg / "launch").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "launch" / "__init__.py").write_text("")
    (pkg / "launch" / "serve.py").write_text("from repro import used\n")
    (pkg / "used.py").write_text("")
    (pkg / "unused.py").write_text("")
    classes = deadcode.dead_modules(str(tmp_path))
    assert classes == {"repro.unused": "DEAD"}


def test_deadcode_follows_dynamic_registry_imports(tmp_path):
    pkg = tmp_path / "src" / "repro"
    (pkg / "configs").mkdir(parents=True)
    (pkg / "launch").mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "launch" / "__init__.py").write_text("")
    (pkg / "launch" / "serve.py").write_text("import repro.configs\n")
    (pkg / "configs" / "__init__.py").write_text(
        "import importlib\n"
        "def load(name):\n"
        "    return importlib.import_module(f'repro.configs.{name}')\n"
    )
    (pkg / "configs" / "tiny.py").write_text("")
    classes = deadcode.dead_modules(str(tmp_path))
    assert classes == {}  # tiny.py kept alive through the registry


def test_deadcode_quarantine_covers_real_tree():
    errors, classes = deadcode.report_dead(".")
    assert errors == [], report.render_all(errors)
    # the quarantined seed modules stay tracked, not silently dead
    assert set(classes) == set(deadcode.load_quarantine())


# ------------------------------------------------------------ runtime gate
@pytest.mark.parametrize("strategy", ["hrz", "dup", "hyb"])
def test_serve_gate_steady_state_clean(strategy):
    errors = gate.serve_gate(strategy, n_chunks=3)
    assert errors == [], report.render_all(errors)


# ---------------------------------------------------------------------- CLI
def test_cli_exit_codes(tmp_path):
    from repro.analysis.__main__ import main

    bad = tmp_path / "hot.py"
    bad.write_text(
        "import jax.numpy as jnp\n\ndef f(x):\n"
        "    return int(jnp.sum(x))\n"
    )
    assert main([str(bad), "--skip-contracts", "--repo-root", "."]) == 1
    clean = tmp_path / "ok.py"
    clean.write_text("def f(x):\n    return x\n")
    out = tmp_path / "report.json"
    assert (
        main(
            [str(clean), "--skip-contracts", "--repo-root", ".",
             "--report", str(out)]
        )
        == 0
    )
    assert out.exists()


def test_gc_watch_counts_a_collection_and_unhooks():
    import gc

    hooks = list(gc.callbacks)
    with runtime.gc_watch() as watch:
        assert len(gc.callbacks) == len(hooks) + 1
        gc.collect()
    assert watch.collections[2] >= 1 and watch.seconds[2] > 0
    assert watch.total_s >= watch.seconds[2]
    assert gc.callbacks == hooks
