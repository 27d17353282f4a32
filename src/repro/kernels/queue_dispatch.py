"""Pallas TPU kernel: queue-mapped buffer placement (paper §II.C.3).

The paper's "labeling network" assigns each key the count of earlier
same-destination keys in the chunk, then stores it at write_ptr + label.
On the FPGA this serial check is the critical path that costs the queue
implementation 7-8 % clock frequency; on the TPU the same computation is a
vectorized one-hot + cumulative sum over lanes -- one of the cheapest VPU
patterns there is.  This inversion (serial labeling -> parallel prefix) is
the key hardware-adaptation insight for the whole paper: it is why the
queue mapping is strictly preferable on TPU and why we default MoE dispatch
to it (models/moe.py).

Single grid step per chunk: the chunk, label matrix and buffer image all fit
comfortably in VMEM for the paper's chunk sizes (<= a few thousand lanes).

Since DESIGN.md §8 the BST hybrid strategy no longer calls this kernel:
its dispatch executes INSIDE the forest search kernel
(``bst_search._dispatch_lanes``, the same labeling arithmetic without a
materialized buffer image, because the lanes never move).  This standalone
kernel remains the buffer-image primitive for workloads that do move
items -- the buffer-semantics tests.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _queue_dispatch_kernel(
    dest_ref, buf_ref, count_ref, overflow_ref, *, n_dest: int, capacity: int
):
    dest = dest_ref[...]  # (B,) int32, -1 = inactive
    B = dest.shape[0]
    active = dest >= 0
    d_safe = jnp.clip(dest, 0, n_dest - 1)

    # one-hot (B, n_dest) via broadcast compare; label = exclusive prefix count
    onehot = (
        d_safe[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, n_dest), 1)
    ).astype(jnp.int32) * active[:, None].astype(jnp.int32)
    label = jnp.cumsum(onehot, axis=0) - onehot
    label = jnp.sum(label * onehot, axis=1)  # pick own column

    kept = active & (label < capacity)
    # buffer image: buf[d, c] = source index i with dest[i]==d, label[i]==c
    src = jax.lax.broadcasted_iota(jnp.int32, (B,), 0)
    lin = jnp.where(kept, d_safe * capacity + label, n_dest * capacity)
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, n_dest * capacity), 1)
    match = (lin[:, None] == slots).astype(jnp.int32)  # (B, n_dest*capacity)
    filled = jnp.max(match * (src[:, None] + 1), axis=0) - 1  # -1 if empty
    buf_ref[...] = filled.reshape(n_dest, capacity)
    count_ref[...] = jnp.minimum(jnp.sum(onehot, axis=0), capacity)
    overflow_ref[...] = (active & ~kept).astype(jnp.int32)


def queue_dispatch_pallas(
    dest: jax.Array,
    n_dest: int,
    capacity: int,
    interpret: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(buffers (n_dest, capacity), counts (n_dest,), overflow (B,) bool)."""
    B = dest.shape[0]
    kernel = functools.partial(
        _queue_dispatch_kernel, n_dest=n_dest, capacity=capacity
    )
    buffers, counts, overflow = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[pl.BlockSpec((B,), lambda i: (0,))],
        out_specs=[
            pl.BlockSpec((n_dest, capacity), lambda i: (0, 0)),
            pl.BlockSpec((n_dest,), lambda i: (0,)),
            pl.BlockSpec((B,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_dest, capacity), jnp.int32),
            jax.ShapeDtypeStruct((n_dest,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
    )(dest)
    return buffers, counts, overflow != 0
