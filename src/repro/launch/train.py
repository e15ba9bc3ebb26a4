"""End-to-end fault-tolerant LM training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b --smoke \
      --steps 30 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On TPU pods the same driver runs the full config on the production mesh; on
this CPU container use --smoke (reduced config, 1 device). --cim noisy turns
on NeuRRAM noise-resilient training for every linear layer (the paper's
technique as a training-time feature).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import configs
from ..models import transformer as T
from ..data import lm_tokens
from ..obs.clock import now as clock_now
from ..distributed.sharding import (param_pspecs, batch_pspecs, fit_pspecs,
                                    opt_pspecs)
from ..distributed.fault import FaultTolerantTrainer
from .steps import make_train_step, adamw_init_f32
from .mesh import make_production_mesh, data_axes


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--cim", default="off", choices=["off", "noisy"])
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args(argv)
    from .env import enable_compile_cache
    enable_compile_cache()

    cfg = configs.get(args.arch, smoke=args.smoke)
    cfg = cfg.replace(cim_mode=args.cim,
                      dtype=jnp.float32 if args.smoke else cfg.dtype)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    opt = adamw_init_f32(params)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M cim={cfg.cim_mode}")

    step_fn_raw = make_train_step(cfg, lr=args.lr)
    if args.production_mesh:
        mesh = make_production_mesh()
        pspec = fit_pspecs(jax.eval_shape(lambda: params), param_pspecs(params),
                           mesh)
        ns = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        # out_shardings pinned to the same specs as the inputs: the step
        # returns (params, opt, loss, gnorm) and an unpinned result would
        # hand back fresh GSPMDSharding objects each call (pjit call-cache
        # miss per step — lint R001)
        jit_step = jax.jit(step_fn_raw, in_shardings=(
            ns(pspec), ns(opt_pspecs(pspec)), None),
            out_shardings=(ns(pspec), ns(opt_pspecs(pspec)), None, None),
            donate_argnums=(0, 1))
    else:
        # single-device path: `mesh` is only bound in the branch above
        jit_step = jax.jit(step_fn_raw, donate_argnums=(0, 1))  # lint: disable=R001

    def wrapped(state, batch):
        params, opt = state
        params, opt, loss, gnorm = jit_step(params, opt, batch)
        wrapped.last_loss = float(loss)
        return (params, opt)

    def data_iter():
        i = 0
        while True:
            k = jax.random.PRNGKey(1000 + i)
            toks = lm_tokens(k, args.batch, args.seq + 1, cfg.vocab)
            batch = {"tokens": toks}
            if cfg.vis_patches > 0:
                batch["vis_embeds"] = 0.02 * jax.random.normal(
                    jax.random.fold_in(k, 1),
                    (args.batch, cfg.vis_patches, cfg.d_model), cfg.dtype)
            if cfg.enc_layers > 0:
                batch["src_embeds"] = 0.02 * jax.random.normal(
                    jax.random.fold_in(k, 2),
                    (args.batch, args.seq, cfg.d_model), cfg.dtype)
            yield batch
            i += 1

    trainer = FaultTolerantTrainer(wrapped, args.ckpt_dir,
                                   ckpt_every=args.ckpt_every)
    state, start = trainer.resume((params, opt))
    print(f"starting at step {start}")
    it = data_iter()
    t0 = clock_now()
    losses = []
    for s in range(start, args.steps):
        state = wrapped(state, next(it))
        losses.append(wrapped.last_loss)
        if s % 5 == 0 or s == args.steps - 1:
            print(f"step {s} loss {wrapped.last_loss:.4f} "
                  f"({(clock_now()-t0)/(s-start+1):.2f}s/step)")
        if (s + 1) % args.ckpt_every == 0:
            trainer.ckpt.save(s + 1, state)
    trainer.ckpt.wait()
    print(f"done. loss {losses[0]:.3f} -> {losses[-1]:.3f}")
    return losses


if __name__ == "__main__":
    main()
