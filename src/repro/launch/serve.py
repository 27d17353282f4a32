"""Serving drivers: LM decoding, and the sharded BST store (DESIGN.md §9).

LM mode -- batched greedy decoding with prefill + KV cache:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 32 --new-tokens 16

BST mode -- the paper's accelerator served through the Pallas forest
kernel over every device JAX sees.  With more than one device,
``BSTServer(mesh=...)`` routes fixed-shape chunks through the strategy's
shard_map-lowered plan in the server's depth-2 chunk loop, with
live writes riding the replicated delta buffer:
  PYTHONPATH=src python -m repro.launch.serve --bst --bst-strategy hyb \
      --requests 100000 --chunk 8192
``--bst-devices N`` simulates N devices on the CPU backend; the flag it
sets counts host-platform devices only, so a TPU host serves over its
real chips either way.
"""

from __future__ import annotations

import argparse
import os
import time


def bst_main(args) -> None:
    """Serve a lookup + mixed write stream through the BSTServer."""
    import jax
    import numpy as np

    from repro.core.distributed import make_serving_mesh
    from repro.core.engine import EngineConfig
    from repro.data.keysets import make_tree_data
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import BSTServer

    enable_compile_cache()
    strategy = args.bst_strategy
    n_devices = len(jax.devices())
    mesh = make_serving_mesh(strategy) if n_devices > 1 else None
    n_trees = 1 if strategy == "hrz" else max(2, n_devices)
    cfg = EngineConfig(
        strategy=strategy,
        n_trees=n_trees,
        mapping="queue",
        use_kernel=True,
        delta_capacity=args.chunk // 2,
    )
    keys, values = make_tree_data((1 << 16) - 1, seed=0)
    srv = BSTServer(keys, values, cfg, chunk_size=args.chunk, mesh=mesh)
    srv.warmup()
    rng = np.random.default_rng(1)
    stream = rng.choice(keys, args.requests).astype(np.int32)

    t0 = time.perf_counter()
    srv.submit(stream)
    srv.drain()
    dt = time.perf_counter() - t0
    s = srv.stats
    print(
        f"{strategy} x {n_devices} {jax.devices()[0].platform} device(s): "
        f"{args.requests} lookups in {dt:.2f}s "
        f"({s.served / dt:.0f} ops/s, {s.found} found, {s.chunks} chunks)"
    )
    print("host ms by phase: " + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in s.phase_s.items())
          + f"; row answers {s.row_answers} of {s.requests} requests, {s.op_runs} op runs")
    if mesh is not None:
        print(f"router: {s.routed_slots} routed slots for {s.lanes} lanes, "
              f"{s.overlapped_chunks} of {s.chunks} chunks overlapped")

    # a mixed tail: writes ride the replicated delta buffer on-device
    wk = rng.integers(1, 2**20, args.chunk).astype(np.int32)
    srv.submit_write(wk, wk * 3)
    srv.submit(wk[: args.chunk // 2])
    srv.drain()
    v, f = srv.lookup(wk[:16])
    print(
        f"write path: {srv.stats.updates} updates absorbed on device, "
        f"{int(np.asarray(f).sum())}/16 fresh keys found, "
        f"{srv.stats.compactions} compaction(s)"
    )
    s = srv.stats
    print("host ms by phase, all drains: " + ", ".join(f"{k} {v * 1e3:.1f}" for k, v in s.phase_s.items())
          + f"; row answers {s.row_answers} of {s.requests} requests, {s.op_runs} op runs")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    # BST sharded serving mode (DESIGN.md §9)
    ap.add_argument("--bst", action="store_true", help="serve the BST store")
    ap.add_argument("--bst-strategy", default="hyb", choices=("hrz", "dup", "hyb"))
    ap.add_argument(
        "--bst-devices", type=int, default=None,
        help="simulate this many devices on the CPU backend",
    )
    ap.add_argument("--requests", type=int, default=100_000)
    ap.add_argument("--chunk", type=int, default=8_192)
    args = ap.parse_args(argv)

    if args.bst_devices is not None:
        # Must precede JAX's start-up; it sizes the host platform only.
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.bst_devices}"
        )
    if args.bst:
        return bst_main(args)
    if args.arch is None:
        ap.error("--arch is required (or pass --bst for the BST store)")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, smoke_config
    from repro.models import model as M
    from repro.serving.serve_loop import make_serve_step

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = M.init_params(cfg, jax.random.key(0))
    B, S = args.batch, args.prompt_len
    prompts = jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size)
    fe = None
    if cfg.frontend is not None:
        flen = S if cfg.family == "encdec" else cfg.frontend_len
        fe = (
            jax.random.normal(jax.random.key(2), (B, flen, cfg.d_model)) * 0.02
        ).astype(cfg.param_dtype)

    t0 = time.time()
    logits, state = M.prefill(cfg, params, prompts, fe, max_len=S + args.new_tokens)
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    t1 = time.time()
    print(f"prefill: {B}x{S} in {t1-t0:.2f}s")

    step = make_serve_step(cfg)
    outs = [tok]
    for i in range(args.new_tokens - 1):
        logits, state = step(params, tok, state)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        outs.append(tok)
    gen = jnp.concatenate(outs, axis=1)
    jax.block_until_ready(gen)
    dt = time.time() - t1
    print(
        f"decode: {args.new_tokens} tokens x {B} seqs in {dt:.2f}s "
        f"({B * args.new_tokens / dt:.1f} tok/s)"
    )
    print("sample token ids:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
