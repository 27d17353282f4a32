"""Abstract-eval contract checker (DESIGN.md §10).

Declares the shape/dtype/layout contracts the stack's layers exchange --
``SearchPlan`` and the per-op query outputs (§6), the forest kernel
operands (§2/§8), the delta-buffer quadruple (§7), the sharded program
builders and their replicated-delta / chunk-divisibility / capacity
invariants (§9) -- and verifies them WITHOUT running real workloads:
everything that can be checked abstractly goes through ``jax.eval_shape``
on representative specs (no FLOPs, no device buffers beyond the tiny plan
constants), and the cross-module bounds delegate to
``repro.analysis.invariants`` so the checker and the runtime asserts can
never disagree.

To declare a contract on a NEW op or kernel: add its output row to
``OP_CONTRACTS`` (or extend ``check_*`` below with an ``eval_shape`` over
its entry point) -- the checker fails on any drift between the declared
row and what the code abstractly evaluates to.
"""

from __future__ import annotations

import functools
from typing import Callable, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.analysis import invariants
from repro.analysis.report import Violation


def _spec(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# The §6 per-op output contract for a B-lane batch with scan fan-out k:
# op -> tuple of (shape-lambda, dtype).  The single source the engine, the
# distributed runners and the server all must honor (their outputs are
# abstractly evaluated against these rows below).
OP_CONTRACTS = {
    "lookup": (
        (lambda B, k: (B,), jnp.int32),
        (lambda B, k: (B,), jnp.bool_),
    ),
    "predecessor": (
        (lambda B, k: (B,), jnp.int32),
        (lambda B, k: (B,), jnp.int32),
        (lambda B, k: (B,), jnp.bool_),
    ),
    "successor": (
        (lambda B, k: (B,), jnp.int32),
        (lambda B, k: (B,), jnp.int32),
        (lambda B, k: (B,), jnp.bool_),
    ),
    "range_count": ((lambda B, k: (B,), jnp.int32),),
    "range_scan": (
        (lambda B, k: (B, k), jnp.int32),
        (lambda B, k: (B, k), jnp.int32),
        (lambda B, k: (B,), jnp.int32),
    ),
}

# Representative spec sizes: tiny, but non-degenerate (multi-level tree,
# batch > n_trees, k smaller than the key count).
_N_KEYS = 31  # height-4 perfect tree
_BATCH = 8
_K = 4


def _violation(check: str, msg: str) -> Violation:
    return Violation("CON001", f"contracts:{check}", 0, msg)


def _check_outputs(
    check: str, op: str, out, B: int, k: int, errors: List[Violation]
) -> None:
    rows = OP_CONTRACTS[op]
    out = out if isinstance(out, tuple) else (out,)
    if len(out) != len(rows):
        errors.append(
            _violation(
                check,
                f"{op}: {len(out)} outputs, contract declares {len(rows)}",
            )
        )
        return
    for i, (o, (shape_fn, dtype)) in enumerate(zip(out, rows)):
        want = tuple(shape_fn(B, k))
        if tuple(o.shape) != want or o.dtype != jnp.dtype(dtype):
            errors.append(
                _violation(
                    check,
                    f"{op} output[{i}]: {o.dtype}{tuple(o.shape)} != "
                    f"declared {jnp.dtype(dtype)}{want}",
                )
            )


def _tiny_tree():
    from repro.core import tree as tree_lib

    keys = np.arange(1, _N_KEYS + 1, dtype=np.int32) * 3
    return tree_lib.build_tree(keys, keys * 7)


def _delta_spec(capacity: int):
    from repro.core import delta as delta_lib

    return delta_lib.DeltaBuffer(
        keys=_spec((capacity,), jnp.int32),
        values=_spec((capacity,), jnp.int32),
        tombstone=_spec((capacity,), jnp.bool_),
        in_tree=_spec((capacity,), jnp.bool_),
        tree_rank=_spec((capacity,), jnp.int32),
        count=_spec((), jnp.int32),
    )


# ----------------------------------------------------------------- the checks
def check_ordered_packing() -> List[Violation]:
    """OrderedResult field order == the packed-collective lane layout."""
    from repro.core import plans as plans_lib
    from repro.core import tree as tree_lib

    errors: List[Violation] = []
    if tree_lib.OrderedResult._fields != invariants.ORDERED_FIELDS:
        errors.append(
            _violation(
                "packing",
                f"OrderedResult fields {tree_lib.OrderedResult._fields} != "
                f"invariants.ORDERED_FIELDS {invariants.ORDERED_FIELDS}",
            )
        )
        return errors
    res = tree_lib.OrderedResult(
        value=_spec((_BATCH,), jnp.int32),
        found=_spec((_BATCH,), jnp.bool_),
        pred_key=_spec((_BATCH,), jnp.int32),
        pred_value=_spec((_BATCH,), jnp.int32),
        succ_key=_spec((_BATCH,), jnp.int32),
        succ_value=_spec((_BATCH,), jnp.int32),
        rank=_spec((_BATCH,), jnp.int32),
    )
    packed = jax.eval_shape(plans_lib.pack_ordered, res)
    want = (_BATCH, invariants.ORDERED_PACK_WIDTH)
    if tuple(packed.shape) != want or packed.dtype != jnp.int32:
        errors.append(
            _violation(
                "packing",
                f"pack_ordered: {packed.dtype}{tuple(packed.shape)} != "
                f"int32{want} -- the packed all_to_all image drifted",
            )
        )
    else:
        unpacked = jax.eval_shape(plans_lib.unpack_ordered, packed)
        if unpacked.found.dtype != jnp.bool_ or any(
            tuple(f.shape) != (_BATCH,) for f in unpacked
        ):
            errors.append(
                _violation("packing", "unpack_ordered round-trip drifted")
            )
    return errors


def check_plan_layout() -> List[Violation]:
    """SearchPlan operand layout per strategy (§2/§8): one flat level-major
    row of 2^(h+1)-1 int32 nodes; hyb's split level == log2(n_trees)."""
    from repro.core import plans as plans_lib

    errors: List[Violation] = []
    tree = _tiny_tree()
    for strategy, n_trees in (("hrz", 1), ("dup", 4), ("hyb", 4)):
        plan = plans_lib.make_plan(tree, strategy=strategy, n_trees=n_trees)
        rows, n = plan.forest_keys.shape
        try:
            invariants.check_forest_nodes(n, plan.forest_height)
        except ValueError as e:
            errors.append(_violation("plan", f"{strategy}: {e}"))
        if plan.forest_values.shape != plan.forest_keys.shape:
            errors.append(
                _violation("plan", f"{strategy}: keys/values shape mismatch")
            )
        if plan.forest_keys.dtype != jnp.int32:
            errors.append(
                _violation(
                    "plan", f"{strategy}: operands {plan.forest_keys.dtype}"
                )
            )
        if rows != 1:
            errors.append(
                _violation(
                    "plan",
                    f"{strategy}: {rows} operand rows -- the single-chip "
                    "strategies carry ONE flat tree row (DESIGN.md §8)",
                )
            )
        if plan.rank_to_bfs.shape[0] != tree.n_nodes:
            errors.append(
                _violation("plan", f"{strategy}: rank_to_bfs size drifted")
            )
        if strategy == "hyb":
            want_split = invariants.split_level_for(n_trees)
            if plan.split_level != want_split:
                errors.append(
                    _violation(
                        "plan",
                        f"hyb split_level {plan.split_level} != "
                        f"log2(n_trees) {want_split}",
                    )
                )
    return errors


def check_query_contracts() -> List[Violation]:
    """Every (strategy, op, kernel/ref, with/without delta) combination
    abstractly evaluates to the declared §6 output rows.  This is the check
    that catches an epilogue or kernel output drifting shape/dtype."""
    from repro.core import delta as delta_lib
    from repro.core import plans as plans_lib

    errors: List[Violation] = []
    tree = _tiny_tree()
    q = _spec((_BATCH,), jnp.int32)
    dspec = _delta_spec(8)
    for strategy, n_trees in (("hrz", 1), ("dup", 2), ("hyb", 4)):
        plan = plans_lib.make_plan(tree, strategy=strategy, n_trees=n_trees)
        for use_kernel in (False, True):
            for with_delta in (False, True):
                tag = (
                    f"{strategy}/{'kernel' if use_kernel else 'ref'}/"
                    f"{'delta' if with_delta else 'plain'}"
                )
                for op in plans_lib.QUERY_OPS:
                    fn = functools.partial(
                        plans_lib.ordered_query,
                        plan,
                        op,
                        k=_K,
                        use_kernel=use_kernel,
                    )
                    args = (q, q) if op in plans_lib.RANGE_OPS else (q,)
                    try:
                        if with_delta:
                            # the delta spec must be an eval_shape ARGUMENT
                            # (abstract leaves), not a closure constant
                            out = jax.eval_shape(
                                lambda *a, _fn=fn: _fn(*a[:-1], delta=a[-1]),
                                *args,
                                dspec,
                            )
                        else:
                            out = jax.eval_shape(fn, *args)
                    except Exception as e:  # contract: must abstractly eval
                        errors.append(
                            _violation(
                                "query",
                                f"{tag} {op}: eval_shape failed: {e}",
                            )
                        )
                        continue
                    _check_outputs(f"query[{tag}]", op, out, _BATCH, _K, errors)
    # the delta quadruple (§7): four flat (C,) int32 operands
    ops = jax.eval_shape(delta_lib.operands, dspec)
    if len(ops) != invariants.DELTA_OPERANDS or any(
        tuple(o.shape) != (8,) or o.dtype != jnp.int32 for o in ops
    ):
        errors.append(
            _violation(
                "delta",
                f"delta.operands: {[(str(o.dtype), o.shape) for o in ops]} "
                f"!= {invariants.DELTA_OPERANDS} x int32(C,)",
            )
        )
    return errors


def check_invariant_bounds() -> List[Violation]:
    """The shared bounds themselves: good values pass, bad values raise.
    Guards against someone weakening ``invariants`` (both the checker and
    the runtime asserts would silently rot together otherwise)."""
    errors: List[Violation] = []
    cases: Tuple[Tuple[str, Callable[[], object], bool], ...] = (
        ("chunk divides axis", lambda: invariants.check_chunk_divides(8192, 8, "model"), True),
        ("chunk !divides axis", lambda: invariants.check_chunk_divides(100, 8, "model"), False),
        ("delta config ok", lambda: invariants.check_delta_config(64, 48), True),
        ("delta negative cap", lambda: invariants.check_delta_config(-1, None), False),
        ("high water > cap", lambda: invariants.check_delta_config(64, 65), False),
        ("high water zero", lambda: invariants.check_delta_config(64, 0), False),
        ("pow2 ok", lambda: invariants.check_power_of_two(8, "n"), True),
        ("pow2 bad", lambda: invariants.check_power_of_two(6, "n"), False),
        ("capacity_frac bad", lambda: invariants.capacity_for_trace(512, 8, 0.0), False),
        ("forest nodes ok", lambda: invariants.check_forest_nodes(31, 4), True),
        ("forest nodes bad", lambda: invariants.check_forest_nodes(30, 4), False),
    )
    for name, fn, should_pass in cases:
        try:
            fn()
            ok = True
        except ValueError:
            ok = False
        if ok != should_pass:
            errors.append(
                _violation(
                    "bounds",
                    f"invariants self-check {name!r}: "
                    f"{'passed' if ok else 'raised'}, expected "
                    f"{'pass' if should_pass else 'raise'}",
                )
            )
    # capacity_frac bounds over a representative grid: 1 <= cap <= B, and
    # depth doubles when the traced batch doubles (the lo||hi property).
    for B in (8, 512, 8192):
        for M in (1, 2, 8):
            for frac in (0.25, 1.0, 2.0):
                cap = invariants.capacity_for_trace(B, M, frac)
                if not 1 <= cap <= B:
                    errors.append(
                        _violation(
                            "bounds",
                            f"capacity_for_trace({B}, {M}, {frac}) = {cap} "
                            f"outside [1, {B}]",
                        )
                    )
    # high-water default stays inside (0, capacity]
    for cap in (1, 4, 64, 8192):
        hw = invariants.resolved_high_water(cap, None)
        if not 1 <= hw <= cap:
            errors.append(
                _violation(
                    "bounds",
                    f"resolved_high_water({cap}) = {hw} outside [1, {cap}]",
                )
            )
    return errors


def check_engine_delegation() -> List[Violation]:
    """EngineConfig/BSTServer must enforce the shared bounds (the
    delegation the bugfix sweep installed): constructing with values the
    invariants reject must raise ValueError."""
    from repro.core.engine import EngineConfig
    from repro.serving.bst_server import BSTServer

    errors: List[Violation] = []
    for kwargs in ({"delta_capacity": -1}, {"delta_capacity": 8, "delta_high_water": 9}):
        try:
            EngineConfig(**kwargs)
            errors.append(
                _violation(
                    "delegation", f"EngineConfig({kwargs}) did not raise"
                )
            )
        except ValueError:
            pass
    # chunk/mesh divisibility: exercised abstractly via the shared check
    # (constructing a real mesh here would need forced devices); the
    # server's constructor path is covered by tests/test_analysis.py.
    del BSTServer
    return errors


def check_sharded_builders() -> List[Violation]:
    """The §9 sharded-builder contract on the current (possibly 1-device)
    host: mesh axis naming per strategy, the replicated delta operand
    specs, capacity sizing, and the run(op, ...) outputs against the §6
    rows -- executed on a tiny tree, so this stays cheap."""
    from repro.core import delta as delta_lib
    from repro.core import distributed as dist_lib
    from repro.core import plans as plans_lib

    errors: List[Violation] = []
    # the replicated-delta layout is a module-level constant now: verify
    # every spec is fully replicated (P() with no named axes)
    specs = dist_lib.DELTA_IN_SPECS
    if len(specs) != invariants.DELTA_OPERANDS or any(
        tuple(s) != tuple(P()) for s in specs
    ):
        errors.append(
            _violation(
                "sharded",
                f"DELTA_IN_SPECS {specs} != {invariants.DELTA_OPERANDS} "
                "fully-replicated P() entries -- the delta buffer must be "
                "REPLICATED on every chip (DESIGN.md §9)",
            )
        )
    for strategy in plans_lib.SHARDED_STRATEGIES:
        axis = plans_lib.mesh_axis_for_strategy(strategy)
        want = "data" if strategy == "dup" else "model"
        if axis != want:
            errors.append(
                _violation(
                    "sharded", f"{strategy} shards over {axis!r}, want {want!r}"
                )
            )
        mesh = dist_lib.make_serving_mesh(strategy, devices=jax.devices()[:1])
        if mesh.axis_names != (axis,):
            errors.append(
                _violation(
                    "sharded",
                    f"make_serving_mesh({strategy!r}) axes {mesh.axis_names}",
                )
            )
        tree = _tiny_tree()
        run = dist_lib.make_sharded_query(tree, mesh, strategy, use_kernel=False)
        # per-device stored nodes: the subtree shard plus the replicated
        # register layer (< axis size nodes) -- an M-fold replication
        # regression of a PARTITIONED operand blows straight through this.
        bound = tree.n_nodes + mesh.shape[axis]
        if run.device_nodes > bound:
            errors.append(
                _violation(
                    "sharded",
                    f"{strategy}: {run.device_nodes} stored nodes/device > "
                    f"single-chip bound {bound}",
                )
            )
        q = jnp.arange(_BATCH, dtype=jnp.int32) * 3 + 1
        delta = delta_lib.empty(8)
        for op in plans_lib.QUERY_OPS:
            args = (q, q) if op in plans_lib.RANGE_OPS else (q,)
            for kw in ({}, {"delta": delta}):
                out = run(op, *args, k=_K, **kw)
                _check_outputs(
                    f"sharded[{strategy}/{'delta' if kw else 'plain'}]",
                    op,
                    out,
                    _BATCH,
                    _K,
                    errors,
                )
    return errors


ALL_CHECKS = (
    check_ordered_packing,
    check_plan_layout,
    check_query_contracts,
    check_invariant_bounds,
    check_engine_delegation,
    check_sharded_builders,
)


def run_contracts() -> List[Violation]:
    errors: List[Violation] = []
    for check in ALL_CHECKS:
        errors.extend(check())
    return errors
