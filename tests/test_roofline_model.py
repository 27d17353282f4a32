"""Validate the analytic FLOPs model against HLO-exact counts.

XLA cost_analysis counts while-loop (scan) bodies once, so the roofline uses
an analytic model (benchmarks/analytic_model.py).  Here we cross-validate it
on configurations where the HLO *is* exact: layers unrolled, naive
attention (no kv scan), single logit chunk, single SSD chunk, no remat.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from benchmarks.analytic_model import cell_cost
from repro.configs import smoke_config
from repro.models import model as M
from repro.models.config import SHAPES


def _exact_cfg(arch, B, S):
    cfg = smoke_config(arch)
    return dataclasses.replace(
        cfg,
        n_layers=2,
        d_model=128,
        head_dim=32,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        dtype="float32",
        attention_impl="naive",
        remat=False,
        scan_layers=False,
        logit_chunk=S,
        ssm_chunk=S,
        sliding_window=None,
        frontend_len=0,
        frontend=None if cfg.family == "vlm" else cfg.frontend,
    )


@pytest.mark.parametrize("arch", ["tinyllama_1p1b", "mixtral_8x7b", "mamba2_1p3b"])
def test_analytic_flops_matches_unrolled_hlo(arch):
    B, S = 2, 64
    cfg = _exact_cfg(arch, B, S)
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=S, global_batch=B)

    def fwd_loss(params, tokens, labels):
        loss, _ = M.forward_train(cfg, params, tokens, labels, None)
        return loss

    params = M.init_params(cfg, jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    labels = jax.ShapeDtypeStruct((B, S), jnp.int32)
    grad_fn = jax.jit(jax.grad(fwd_loss))
    compiled = grad_fn.lower(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
        tokens, labels,
    ).compile()
    hlo_flops = compiled.cost_analysis()["flops"]

    cost = cell_cost(cfg, shape)
    # analytic counts fwd+2x bwd matmuls only (remat off); HLO adds
    # elementwise/softmax work -> HLO should be >= analytic and within 2x
    ratio = hlo_flops / cost.flops
    assert 0.6 < ratio < 2.0, (arch, hlo_flops, cost.flops, ratio)


def test_unrolled_matches_scanned_numerics():
    cfg = smoke_config("qwen3_1p7b")
    B, S = 2, 16
    params = M.init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.key(2), (B, S), 0, cfg.vocab_size)
    l1, _ = M.forward_train(cfg, params, tokens, labels, None)
    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    l2, _ = M.forward_train(cfg2, params, tokens, labels, None)
    assert abs(float(l1) - float(l2)) < 1e-5


def test_decode_cost_dominated_by_kv_and_params():
    from repro.configs import get_config

    cfg = get_config("granite_3_8b")
    cost = cell_cost(cfg, SHAPES["decode_32k"])
    # decode arithmetic intensity must be tiny (memory-bound regime)
    intensity = cost.flops / cost.hbm_bytes
    assert intensity < 20.0  # flops per byte far below v5e's ~240 ridge

    # SWA caps the long-context decode cost for mixtral
    mix = get_config("mixtral_8x7b")
    c500 = cell_cost(mix, SHAPES["long_500k"])
    c32 = cell_cost(mix, SHAPES["decode_32k"])
    assert c500.hbm_bytes < c32.hbm_bytes  # batch 1 + windowed cache
