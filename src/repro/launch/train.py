"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 200 --batch 4 --seq 128 \
        --progressive-ckpt out/ckpt --ckpt-every 25 --grad-compress 8

Runs the real train loop on the local device(s): model from configs/,
AdamW/Adafactor, gradient clipping, optional bitplane gradient compression
(error feedback), async progressive checkpointing, fault-tolerant restart
(--resume), and deterministic synthetic data. On a TPU cluster the same
driver runs under the production mesh (launch/mesh.py); flags documented
for latency hiding on real backends:
  LIBTPU_INIT_ARGS="$LIBTPU_INIT_ARGS --xla_tpu_enable_async_collective_fusion=true
  --xla_tpu_enable_async_collective_fusion_fuse_all_gather=true"
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data.batches import make_train_batch
from repro.models import transformer as T
from repro.train.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.train.grad_compress import compress_decompress, zeros_like_feedback
from repro.train.optimizer import clip_by_global_norm, make_optimizer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-compress", type=int, default=0,
                    help="bitplanes for gradient compression (0 = off)")
    ap.add_argument("--progressive-ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--restore-tau", type=float, default=0.0,
                    help="QoI-bounded warm restore tolerance (0 = exact)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    opt_init, opt_update = make_optimizer(cfg.optimizer)
    opt_state = opt_init(params)
    fb = None
    start_step = 0

    ckpt = AsyncCheckpointer(args.progressive_ckpt) \
        if args.progressive_ckpt else None
    if args.resume and ckpt and latest_step(args.progressive_ckpt) is not None:
        restored, report = restore_checkpoint(args.progressive_ckpt,
                                              tau_rel=args.restore_tau)
        params = jax.tree.map(
            lambda a, b: jnp.asarray(np.asarray(a), np.asarray(b).dtype),
            restored, params)
        start_step = report.step + 1
        print(f"[restore] step={report.step} moved="
              f"{report.bytes_moved / 2**20:.1f}MiB "
              f"({report.bytes_moved / max(report.bytes_full, 1):.0%} of full)")

    @jax.jit
    def step_fn(params, opt_state, fb, batch):
        (loss, metrics), grads = jax.value_and_grad(
            T.loss_fn, has_aux=True)(params, cfg, batch)
        if args.grad_compress:
            grads, fb = compress_decompress(grads, fb, args.grad_compress)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        params, opt_state = opt_update(params, grads, opt_state, lr=args.lr)
        return params, opt_state, fb, loss, gnorm

    if args.grad_compress:
        fb = zeros_like_feedback(params)

    t0 = time.time()
    tokens_per_step = args.batch * args.seq
    for step in range(start_step, args.steps):
        batch = make_train_batch(cfg, args.batch, args.seq, seed=step)
        params, opt_state, fb, loss, gnorm = step_fn(params, opt_state, fb,
                                                     batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            done = step - start_step + 1
            print(f"step={step} loss={float(loss):.4f} "
                  f"gnorm={float(gnorm):.3f} "
                  f"tok/s={tokens_per_step * done / max(dt, 1e-9):.0f}")
        if ckpt and step % args.ckpt_every == 0:
            ckpt.save(params, step)
    if ckpt:
        ckpt.close()
    print(f"done: {args.steps - start_step} steps in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
