"""Pallas kernel microbenchmarks vs jnp oracles.

Off-TPU the BST kernel runs in interpret mode (``pallas_interpret`` rows):
those timings measure the *semantics* executed on CPU, not TPU
performance; the derived field carries the shapes so chip runs slot into
the same harness.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, time_fn
from repro.core import tree as T
from repro.data.keysets import make_tree_data
from repro.kernels import ops

def run() -> List[Row]:
    rows: List[Row] = []

    # bst_search: 64K-node tree, 8K query chunk
    keys, values = make_tree_data((1 << 16) - 1, seed=0)
    tree = T.build_tree(keys, values)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.choice(keys, 8192).astype(np.int32))
    kernel_label = "pallas_interpret" if ops.interpret_mode() else "pallas_mosaic"
    for use_ref in (True, False):
        us = time_fn(
            lambda q: ops.bst_search(
                tree.keys, tree.values, q, height=tree.height, use_ref=use_ref
            ),
            q, warmup=1, iters=3,
        )
        rows.append(
            Row(
                name=f"kernel/bst_search/{'ref' if use_ref else kernel_label}",
                us_per_call=us,
                derived=f"keys_per_sec={8192 / (us / 1e6):.3e};tree_nodes={tree.n_nodes}",
            )
        )

    # queue_dispatch: 4K chunk over 16 destinations
    dest = jnp.asarray(rng.integers(0, 16, 4096).astype(np.int32))
    for use_ref in (True, False):
        us = time_fn(
            lambda d: ops.queue_dispatch(d, n_dest=16, capacity=512, use_ref=use_ref),
            dest, warmup=1, iters=3,
        )
        rows.append(
            Row(
                name=f"kernel/queue_dispatch/{'ref' if use_ref else 'pallas_interpret'}",
                us_per_call=us,
                derived="chunk=4096;n_dest=16;capacity=512",
            )
        )

    # flash_attention: 1k sequence, GQA 8->2 heads
    kq = jax.random.normal(jax.random.key(0), (8, 1024, 64), jnp.float32)
    kk = jax.random.normal(jax.random.key(1), (2, 1024, 64), jnp.float32)
    kv = jax.random.normal(jax.random.key(2), (2, 1024, 64), jnp.float32)
    for use_ref in (True, False):
        us = time_fn(
            lambda a, b, c: ops.flash_attention(a, b, c, causal=True, use_ref=use_ref),
            kq, kk, kv, warmup=1, iters=3,
        )
        flops = 2 * 8 * 1024 * 1024 * 64 * 2 / 2  # causal half
        rows.append(
            Row(
                name=f"kernel/flash_attention/{'ref' if use_ref else 'pallas_interpret'}",
                us_per_call=us,
                derived=f"gflops_effective={flops / (us / 1e6) / 1e9:.2f};BH=8;S=1024;d=64",
            )
        )
    return rows
