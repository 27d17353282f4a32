"""Mixture-of-Experts FFN with the paper's buffer-mapped dispatch.

The hybrid-partitioning router of the paper and a top-k MoE router solve the
same problem: a chunk of items must be placed into fixed-capacity buffers
keyed by a data-dependent destination, and collisions beyond the port budget
cost throughput.  We expose both of the paper's mappings:

  * ``queue``  (paper's contribution, our default): slot = write_ptr + label
    where the label is the prefix count of earlier same-expert tokens -- the
    dense, FIFO-preserving packing.  Overflow == the paper's frontend stall;
    in a serving system that is a dropped expert contribution for the token.
  * ``direct``: slot = token's position-derived index; cheap, but a token can
    be dropped while the expert buffer still has free slots -- exactly the
    spurious-stall behaviour of Fig. 5, surfaced here as a higher drop rate
    at equal capacity_factor (examples/moe_dispatch.py shows it).

Dispatch/combine are einsum-free gather/scatter on (E, C) buffers, which is
the layout expert-parallel sharding wants: buffer row e lives wherever
expert e lives.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import buffers as buf
from repro.models.config import ModelConfig


def moe_params_shape(cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": (D, E),
        "w_gate": (E, D, F),
        "w_up": (E, D, F),
        "w_down": (E, F, D),
    }


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    fair = n_tokens * cfg.top_k / cfg.n_experts
    return max(1, int(fair * cfg.capacity_factor))


def _ambient_dp_axes() -> Tuple[str, ...]:
    """Mesh DP axes at trace time ('' when tracing without a mesh)."""
    try:
        from jax.interpreters import pxla

        m = pxla.thread_resources.env.physical_mesh
        if m.empty:
            return ()
        return tuple(a for a in ("pod", "data") if a in m.axis_names)
    except Exception:  # pragma: no cover
        return ()


def moe_ffn(
    cfg: ModelConfig, params, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, dropped_fraction).

    Tokens are split into cfg.moe_groups independent dispatch groups along
    the batch dim, carried as an explicit leading G axis that is PINNED to
    the DP mesh axes with sharding constraints.  §Perf iters 1/1r showed
    that without the pin, GSPMD replicates the (E, C, D) dispatch buffers
    and all-reduces them (21.5 GB per layer pass on mixtral-8x7b); with it,
    all dispatch/combine traffic is group-local.
    """
    B, S, D = x.shape
    G = cfg.moe_groups or 1
    if G > 1 and B % G == 0:
        xg = x.reshape(G, (B // G) * S, D)
    elif G > 1 and (B * S) % G == 0:  # decode: batch < G
        xg = x.reshape(G, (B * S) // G, D)
    else:
        xg = x.reshape(1, B * S, D)
    out, dropped = _moe_grouped(cfg, params, xg)
    return out.reshape(B, S, D).astype(x.dtype), dropped


def _moe_grouped(cfg: ModelConfig, params, xg: jax.Array):
    G, Tg, D = xg.shape
    E, K = cfg.n_experts, cfg.top_k
    U = P.UNCONSTRAINED
    dp = _ambient_dp_axes()

    def cst(t, spec):
        return jax.lax.with_sharding_constraint(t, spec) if dp else t

    xg = cst(xg, P(dp, U, U))
    logits = jnp.einsum("gtd,de->gte", xg, params["router"]).astype(jnp.float32)
    gates, experts = jax.lax.top_k(logits, K)  # (G, Tg, K)
    gates = jax.nn.softmax(gates, axis=-1)

    C = expert_capacity(cfg, Tg)
    # K*Tg items per group, k-major so primary choices claim slots first.
    dest = experts.swapaxes(1, 2).reshape(G, K * Tg).astype(jnp.int32)
    plan = jax.vmap(lambda d: buf.dispatch(cfg.moe_dispatch, d, E, C))(dest)

    token_of = plan.buffers % Tg  # (G, E, C)
    token_safe = jnp.clip(token_of, 0, Tg - 1)
    live = plan.buffers >= 0
    xe = jnp.take_along_axis(xg, token_safe.reshape(G, E * C, 1), axis=1)
    xe = jnp.where(live.reshape(G, E * C, 1), xe, 0).reshape(G, E, C, D)
    xe = cst(xe, P(dp, U, U, U))

    g = jnp.einsum("gecd,edf->gecf", xe, params["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", xe, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xg.dtype) * u
    ye = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
    ye = cst(ye, P(dp, U, U, U))

    # combine: pure gather -- each (dest, slot) holds at most one item, so a
    # token reads its k-th expert output at (dest, slot).  (A scatter-add
    # over the token dim forced GSPMD into TB-scale all-reduces of the
    # (T, D) output image; §Perf iter 1 analysis.)
    slot = plan.slot.reshape(G, K, Tg)  # -1 when dropped
    dest_k = dest.reshape(G, K, Tg)
    lin = jnp.clip(dest_k, 0, E - 1) * C + jnp.clip(slot, 0, C - 1)
    flat_ye = ye.reshape(G, E * C, D)
    picked = jnp.take_along_axis(
        flat_ye, lin.reshape(G, K * Tg, 1), axis=1
    ).reshape(G, K, Tg, D)
    w = gates.swapaxes(1, 2).astype(jnp.float32)  # (G, K, Tg)
    w = jnp.where(slot >= 0, w, 0.0)
    out = jnp.sum(picked.astype(jnp.float32) * w[..., None], axis=1)  # (G,Tg,D)
    out = cst(out, P(dp, U, U))
    dropped = 1.0 - plan.kept.sum() / jnp.maximum(dest.size, 1)
    return out, dropped
