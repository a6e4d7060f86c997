"""Serving launcher.

  * GNN mode (the paper's scenario): batched NAI inference over a stream of
    unseen-node requests through repro.serving.NAIServingEngine, in the
    compiled mode (sample -> pack on the host, one jitted NAP + classify
    program on the device).
  * LM mode: batched decode with KV cache for a (reduced) assigned arch,
    optionally with Adaptive-Depth Inference early exits.

    PYTHONPATH=src python -m repro.launch.serve --gnn pubmed-like --requests 2000
    PYTHONPATH=src python -m repro.launch.serve --arch granite-34b --smoke --tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, smoke
from repro.models import decoder_lm as M
from repro.runtime import enable_compile_cache


def serve_gnn(args) -> None:
    from repro.gnn import (DistillConfig, GNNConfig, NAIConfig, load_dataset,
                           train_nai)
    from repro.serving import EngineConfig, NAIServingEngine
    g = load_dataset(args.gnn, scale=args.scale, seed=args.seed)
    cfg = GNNConfig("sgc", g.features.shape[1], g.num_classes, k=args.k,
                    hidden=64, mlp_layers=2, dropout=0.1)
    dc = DistillConfig(epochs_base=args.epochs, epochs_offline=args.epochs // 2,
                       epochs_online=args.epochs // 2)
    print(f"[serve-gnn] training NAI model on {args.gnn} (n={g.n})...")
    params, _ = train_nai(cfg, g, dc)
    nai = NAIConfig(t_s=args.t_s, t_min=1, t_max=args.k // 2 + 1,
                    batch_size=args.batch)
    engine = NAIServingEngine(
        cfg, nai, params, g,
        config=EngineConfig(mode="compiled", spmm_impl="segment",
                            pipeline_depth=2))

    rng = np.random.default_rng(args.seed)
    n_req = min(args.requests, len(g.test_idx))
    reqs = rng.choice(g.test_idx, size=n_req, replace=False)
    t0 = time.perf_counter()
    engine.submit(reqs)
    stats = engine.run_until_drained()
    dt = time.perf_counter() - t0
    s = stats.summary()
    print(f"[serve-gnn] served={s['served']} batches={s['batches']} "
          f"in {dt:.2f}s ({1e3 * dt / max(s['served'], 1):.2f} ms/req)")
    print(f"[serve-gnn] p50={s['p50_ms']:.1f}ms p95={s['p95_ms']:.1f}ms "
          f"p99={s['p99_ms']:.1f}ms mean_exit_order={s['mean_exit_order']:.2f}")
    print(f"[serve-gnn] exit histogram: {dict(sorted(stats.exit_hist.items()))}")
    if s["failed"] or s["retried"]:
        raise SystemExit(f"[serve-gnn] {s['failed']} failed and "
                         f"{s['retried']} retried requests")


def serve_lm(args) -> None:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    B, L = args.batch, args.tokens + 8
    cache = M.init_cache(cfg, B, L)
    rng = np.random.default_rng(args.seed)
    if cfg.is_encdec or cfg.num_image_tokens:
        n = cfg.encoder_seq if cfg.is_encdec else cfg.num_image_tokens
        fe = jnp.asarray(rng.standard_normal((B, n, cfg.d_model)),
                         jnp.dtype(cfg.dtype))
        cache = M.seed_frontend_cache(cfg, params, cache, fe)

    step = jax.jit(lambda p, c, t, pos: M.decode_step(cfg, p, c, t, pos))
    tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
    t0 = time.perf_counter()
    out_tokens = []
    for t in range(args.tokens):
        logits, cache = step(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out_tokens.append(np.asarray(tok[:, 0]))
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    print(f"[serve-lm] {cfg.name}: {args.tokens} steps, batch {B}: "
          f"{1e3 * dt / args.tokens:.1f} ms/step on {dev.platform} "
          f"({dev.device_kind})")
    print(f"[serve-lm] sample continuation: {np.stack(out_tokens)[:8, 0]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gnn", default=None)
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=1000)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=100)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--t-s", type=float, default=16.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.gnn:
        serve_gnn(args)
    elif args.arch:
        serve_lm(args)
    else:
        ap.error("need --gnn or --arch")


if __name__ == "__main__":
    main()
