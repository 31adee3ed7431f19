"""Distributed training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --tiny \
        --steps 50 --batch 8 --seq 64

On a real TPU pod each host runs this same script (jax.distributed
initializes from the TPU environment); on CPU it runs single-process. The
pjit path shards params FSDP x tensor via launch.sharding; the dry-run
(launch.dryrun) proves the full-scale mesh lowers.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.launch.sharding import param_shardings
from repro.launch.steps import build_train_step
from repro.training.checkpoint import save_checkpoint
from repro.training.data import DataConfig, SyntheticLM
from repro.training.optimizer import AdamWConfig, adamw_init


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x4 to use a data x model mesh")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch, tiny=args.tiny)
    model, step_fn = build_train_step(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=args.dtype)
    opt = adamw_init(params)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          total_steps=args.steps)
    from repro.training.train_loop import make_train_step
    step_fn = make_train_step(model, opt_cfg)

    if args.mesh:
        d, m = map(int, args.mesh.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"))
        pshard = param_shardings(mesh, jax.eval_shape(lambda: params),
                                 fsdp=True)
        oshard = param_shardings(mesh, jax.eval_shape(lambda: opt),
                                 fsdp=True)
        dsh = NamedSharding(mesh, P("data"))
        with jax.set_mesh(mesh):
            jstep = jax.jit(step_fn,
                            in_shardings=(pshard, oshard, dsh, dsh, dsh),
                            out_shardings=(pshard, oshard, None))
    else:
        jstep = jax.jit(step_fn)

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq,
                                  global_batch=args.batch,
                                  n_codebooks=cfg.n_codebooks, seed=0))
    it = data.batches()
    for step in range(args.steps):
        tokens, labels, mask = next(it)
        params, opt, metrics = jstep(params, opt, jnp.asarray(tokens),
                                     jnp.asarray(labels), jnp.asarray(mask))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"ce {float(metrics['ce']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
    if args.ckpt:
        path = save_checkpoint(args.ckpt, args.steps, params)
        print("checkpoint:", path)


if __name__ == "__main__":
    main()
