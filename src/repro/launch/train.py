"""Training launcher: --arch <id> end-to-end driver with fault tolerance.

Smoke run by default: the reduced config over every device present (see
``repro.launch.mesh.device_mesh``); pass --full to use the full
architecture config (requires the production mesh environment).

Example:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \\
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --steps 50
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.configs.registry import reduced
from repro.data.pipeline import DataConfig, make_batch
from repro.launch.mesh import device_mesh, make_production_mesh, \
    production_plan
from repro.models import params as pm
from repro.optim.adamw import AdamWConfig, init_state
from repro.partition import DATA
from repro.runtime.fault_tolerance import FaultConfig, TrainController
from repro.train.step import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCH_NAMES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--strategy", default="cannon_opt",
                    choices=["cannon", "cannon_opt", "allgather", "summa"])
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="full config (production mesh) instead of smoke")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
        mesh, plan = device_mesh()
    else:
        mesh = make_production_mesh()
        plan = production_plan(mesh)

    extra = ()
    dkw = dict(vocab_size=min(cfg.vocab_size, 256) if not args.full
               else cfg.vocab_size, seq_len=args.seq_len,
               global_batch=args.global_batch)
    if cfg.enc_layers:
        dkw.update(frames=cfg.enc_seq, frame_dim=cfg.d_model)
        extra = ("frames",)
    if cfg.vis_patches:
        dkw.update(patches=cfg.vis_patches, patch_dim=cfg.d_model,
                   seq_len=args.seq_len - cfg.vis_patches)
        extra = ("patches",)
    dc = DataConfig(**dkw)

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=10,
                          decay_steps=max(args.steps, 100))
    step_fn, specs, pctx = make_train_step(
        cfg, mesh, plan, opt_cfg=opt_cfg, tp_strategy=args.strategy,
        remat=True, grad_compress=args.grad_compress, extra_batch_keys=extra)

    params = pm.init_params(specs, seed=0)
    pspecs = pm.param_pspecs(specs)
    params = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), params, pspecs)
    opt_state = init_state(params, opt_cfg)
    if args.grad_compress:
        opt_state["resid"] = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.bfloat16), params)

    def make_device_batch(step):
        b = make_batch(dc, step, 0, 1)
        return {k: jax.device_put(jnp.asarray(v),
                                  NamedSharding(mesh, P(DATA)))
                for k, v in b.items()}

    fcfg = FaultConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    ctrl = TrainController(step_fn, make_device_batch, fcfg)
    start, params, opt_state = ctrl.resume_or_init(params, opt_state)

    t0 = time.time()
    last = start

    class _Logger:
        pass

    def logged_step(p, o, b):
        nonlocal last
        p, o, m = step_fn(p, o, b)
        step = len(ctrl.metrics_log) + start
        if step % args.log_every == 0:
            dt = (time.time() - t0) / max(step - start + 1, 1)
            print(f"step {step:5d} loss {float(m['loss']):.4f} "
                  f"gnorm {float(m['grad_norm']):.2f} "
                  f"lr {float(m['lr']):.2e} ({dt:.2f}s/step)", flush=True)
        return p, o, m

    ctrl.step_fn = logged_step
    params, opt_state = ctrl.run(params, opt_state, args.steps, start)
    print(f"done: {len(ctrl.metrics_log)} steps, retries={ctrl.retries}, "
          f"skipped={ctrl.skipped}")


if __name__ == "__main__":
    main()
