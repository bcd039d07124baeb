"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --tiny \
        --steps 50 --ckpt-dir /tmp/ckpt

Single-host execution of the same train_step the dry-run lowers for the
production mesh; the fleet path differs only in mesh/shardings (steps.py)
and per-host data sharding (data/pipeline.py).  Fault tolerance is live:
checkpoint/restart via CheckpointManager, straggler + heartbeat via
TrainSupervisor.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.launch.steps import make_train_step
from repro.models import build_model, get_config
from repro.optim import AdamWConfig, cosine_with_warmup, init_state
from repro.runtime.ft import TrainSupervisor


def tiny(cfg):
    return cfg.replace(
        n_layers=len(cfg.pattern) * 2 if not cfg.shared_attn_period
        else cfg.shared_attn_period,
        d_model=128, n_heads=4,
        n_kv_heads=min(4, cfg.n_kv_heads), head_dim=32,
        d_ff=0 if cfg.d_ff == 0 else 256, vocab=512,
        moe_experts=8 if cfg.moe_experts else 0,
        moe_top_k=min(2, cfg.moe_top_k) if cfg.moe_top_k else 0,
        moe_shared_dff=64 if cfg.moe_shared_dff else 0,
        moe_group_size=64, ssm_chunk=32, ssm_head_dim=16,
        encoder_layers=2 if cfg.is_encdec else 0,
        encoder_len=32 if cfg.is_encdec else cfg.encoder_len,
        n_img_tokens=4 if cfg.n_img_tokens else 0,
        window=16 if cfg.window else None,
        query_pre_attn_scalar=32.0 if cfg.query_pre_attn_scalar else None,
        remat=False, q_chunk=64, loss_seq_chunk=None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.tiny:
        cfg = tiny(cfg)
    model = build_model(cfg)
    print(f"arch={cfg.name} params={sum(x.size for x in jax.tree.leaves(model.abstract_params())):,}")

    data = SyntheticLM(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        encoder_len=cfg.encoder_len if cfg.is_encdec else 0,
        n_img_tokens=cfg.n_img_tokens, d_model=cfg.d_model))
    adamw = AdamWConfig(lr=cosine_with_warmup(args.lr, 10, args.steps))
    step_fn = jax.jit(make_train_step(model, adamw, None, None),
                      donate_argnums=(0, 1))

    params = model.init(jax.random.PRNGKey(0))
    opt = init_state(params)
    start = 0
    sup = None
    if args.ckpt_dir:
        sup = TrainSupervisor(CheckpointManager(args.ckpt_dir, keep=2),
                              ckpt_every=args.ckpt_every)
        (state := {"p": params, "o": opt})
        state, start = sup.resume_or_init(lambda: state, like=state)
        params, opt = state["p"], state["o"]
        if start:
            print(f"resumed at step {start}")

    losses = []
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 data.global_batch_at(step).items()}
        if cfg.n_img_tokens and "patch_embeds" in batch:
            batch["patch_embeds"] = batch["patch_embeds"].astype(jnp.bfloat16)
        if cfg.is_encdec and "frames" in batch:
            batch["frames"] = batch["frames"].astype(jnp.bfloat16)
        t0 = time.perf_counter()
        params, opt, metrics = step_fn(params, opt, batch)
        wall = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        if sup:
            sup.after_step(step, {"p": params, "o": opt}, wall)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={losses[-1]:.4f} "
                  f"{wall * 1e3:.0f}ms")
    if sup:
        sup.ckpt.wait()
    first = sum(losses[:5]) / max(len(losses[:5]), 1)
    last = sum(losses[-5:]) / max(len(losses[-5:]), 1)
    print(f"loss {first:.3f} -> {last:.3f} "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
