"""Centralized LM training driver (the end-to-end example backbone).

Trains any ``--arch`` (reduced by default on CPU; pass --full on a real
mesh) on synthetic Markov-chain LM data with AdamW + warmup-cosine,
checkpointing and metric logging.  The jitted step comes from the SAME
builder the dry-run lowers — what we measure is what we run.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 200 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint.ckpt import latest_step, load_checkpoint, save_checkpoint
from repro.configs.base import ARCH_IDS, ShapeConfig, get_config, reduced
from repro.data.synthetic import make_synthetic_lm
from repro.data.pipeline import lm_batch_iterator
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import Knobs, build_train_step
from repro.models import build_model
from repro.optim.optimizers import warmup_cosine
from repro.utils.compile_cache import use_compile_cache
from repro.utils.metrics import MetricLogger


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--full", action="store_true", help="full config (TPU mesh)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    if cfg.is_encoder_decoder:
        print("(enc-dec arch: tokens drive the decoder; src embeds are synthetic)")

    mesh = make_test_mesh()
    shape = ShapeConfig("custom_train", "train", args.seq, args.batch)
    sched = warmup_cosine(args.lr, args.warmup, args.steps)
    knobs = Knobs(remat="none", param_dtype="float32", learning_rate=sched)
    bundle = build_train_step(cfg, shape, mesh, knobs)
    step_fn = jax.jit(bundle.fn, donate_argnums=(0, 1))

    model = build_model(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    from repro.optim.optimizers import adamw

    opt = adamw(sched)
    opt_state = opt.init(params)

    start_step = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        tree, meta = load_checkpoint(args.ckpt_dir, None, {"params": params, "opt": opt_state})
        params, opt_state = tree["params"], tree["opt"]
        start_step = int(meta.get("step", 0))
        print(f"resumed from step {start_step}")

    toks = make_synthetic_lm(
        vocab_size=cfg.vocab_size, seq_len=args.seq + 1, n_seqs=2048, seed=args.seed
    )
    it = lm_batch_iterator(toks, args.batch, seed=args.seed)

    log = MetricLogger(["step", "loss", "grad_norm", "tok_per_s"], echo_every=1)
    t_last, toks_since = time.time(), 0
    for step in range(start_step, args.steps):
        batch = next(it)
        if cfg.is_encoder_decoder:
            batch = {
                "src_embeds": jax.random.normal(
                    jax.random.fold_in(rng, step), (args.batch, args.seq, cfg.d_model)
                ),
                "tgt_tokens": batch["tokens"],
                "labels": batch["labels"],
            }
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        toks_since += args.batch * args.seq
        if (step + 1) % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t_last
            log.log(step=step + 1, loss=float(metrics["loss"]),
                    grad_norm=float(metrics["grad_norm"]),
                    tok_per_s=round(toks_since / max(dt, 1e-9)))
            t_last, toks_since = time.time(), 0
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, step + 1, {"params": params, "opt": opt_state})
    final_loss = float(metrics["loss"])
    print(f"final loss: {final_loss:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
