"""Training launcher: ``python -m repro.launch.train --arch smollm-360m
--steps 50 --batch 8 --seq 256`` — runs a real training loop on the local
devices (CPU smoke scale or a real TPU slice; the same code path the
multi-pod dry-run lowers at 16×16/2×16×16)."""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.config import get_config
from repro.data.pipeline import DataPipeline, SyntheticLMDataset
from repro.distributed.sharding import ShardingRules
from repro.launch import runtime
from repro.launch.mesh import make_smoke_mesh
from repro.models.api import build_model
from repro.optim import adamw, cosine_warmup
from repro.training.train_step import init_state, jit_train_step
from repro.training.trainer import Trainer


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--reduced", action="store_true",
                   help="use the smoke-scale config (CPU-friendly)")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--dtype", default="float32")
    args = p.parse_args()

    runtime.enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(dtype=args.dtype)
    else:
        cfg = dataclasses.replace(cfg, dtype=args.dtype)
    model = build_model(cfg)
    mesh = make_smoke_mesh()
    attn = getattr(cfg, "attention", None)
    rules = ShardingRules(mesh, head_dim=attn.head_dim if attn else None)
    opt = adamw()
    lr_fn = cosine_warmup(args.lr, max(args.steps // 10, 1), args.steps)

    state = init_state(model, jax.random.PRNGKey(0), opt)
    batch_shape = {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq),
                                                  jnp.int32)}
    step = jit_train_step(model, opt, lr_fn, mesh, rules,
                          jax.eval_shape(lambda: state), batch_shape)

    ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch)
    pipe = DataPipeline(ds)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    trainer = Trainer(step_fn=lambda s, b: step(s, b), state=state,
                      pipeline=pipe, ckpt=ckpt,
                      metrics_hook=lambda i, r: print(
                          f"step {i:5d}  loss {r['loss']:.4f}  "
                          f"{r['dt']*1e3:.0f} ms"))
    if args.resume:
        start = trainer.maybe_restore()
        print(f"resumed from step {start}")
    t0 = time.time()
    with mesh:
        summary = trainer.run(args.steps)
    pipe.close()
    print(f"done in {time.time()-t0:.1f}s: {summary}")


if __name__ == "__main__":
    main()
