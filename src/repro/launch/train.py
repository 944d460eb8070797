"""Production training launcher.

Single-controller JAX: builds the mesh from the runtime topology, the
datastore from the file manifest, shards the train state per the
logical rules, and runs the (optionally LTFB-wrapped) training loop with
checkpoint/restart.  On a CPU it runs the reduced configs; on a TPU
pod slice the same script runs the full configs.

  python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 50
  python -m repro.launch.train --arch icf-cyclegan --steps 300
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.base import MeshConfig, OptimizerConfig
from repro.configs.registry import ARCHS, get_config
from repro.data.tokens import train_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.parallel.sharding import tree_shardings, use_sharding
from repro.train.steps import (init_lm_state, make_lm_eval_metric,
                               make_lm_train_step)


def build_mesh(args):
    n = len(jax.devices())
    if n == 1:
        return None
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    if n >= 512 and args.multi_pod:
        return make_production_mesh(multi_pod=True)
    if n >= 256:
        return make_production_mesh(multi_pod=False)
    return make_host_mesh(("data",))


def train_lm(args):
    cfg = get_config(args.arch, smoke=args.smoke)
    opt = OptimizerConfig(name=args.optimizer, lr=args.lr,
                          warmup_steps=min(100, args.steps // 10 + 1))
    mesh_cfg = MeshConfig(remat=args.remat)
    mesh = build_mesh(args)
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"devices={len(jax.devices())} mesh={'none' if mesh is None else mesh.shape}")

    step_fn = make_lm_train_step(cfg, opt, mesh_cfg)
    metric = jax.jit(make_lm_eval_metric(cfg))

    with use_sharding(mesh):
        state, axes = init_lm_state(cfg, opt, jax.random.PRNGKey(args.seed))
        if mesh is not None:
            shardings = tree_shardings(mesh, axes, state)
            state = jax.device_put(state, shardings)
            step = jax.jit(step_fn, donate_argnums=(0,),
                           in_shardings=(shardings, None),
                           out_shardings=(shardings, None))
        else:
            step = jax.jit(step_fn, donate_argnums=(0,))

        # restart support
        start = 0
        latest = ckpt.latest_step_path(args.ckpt_dir)
        if latest and not args.no_resume:
            state, meta = ckpt.restore(latest, state)
            start = meta.get("step", 0)
            print(f"[train] resumed from {latest} at step {start}")

        saver = ckpt.AsyncCheckpointer()
        val = {k: jnp.asarray(v) for k, v in
               train_batch(cfg, args.batch, args.seq, seed=987654).items()}
        t0 = time.time()
        for i in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     train_batch(cfg, args.batch, args.seq, seed=i).items()}
            state, m = step(state, batch)
            if i % args.log_every == 0:
                print(f"step {i:5d} loss={float(m['loss']):.4f} "
                      f"lr={float(m['lr']):.2e} "
                      f"({(time.time()-t0):.1f}s)")
            if args.ckpt_every and i and i % args.ckpt_every == 0:
                saver.save(os.path.join(args.ckpt_dir, f"step_{i}.ckpt"),
                           state, {"step": i})
        saver.wait()
        print(f"[train] done: val={float(metric(state['params'], val)):.4f}")


def train_cyclegan(args):
    """The paper's model: delegates to the quickstart pipeline."""
    from repro.configs.base import OptimizerConfig
    from repro.configs.icf_cyclegan import CycleGANConfig
    from repro.data import jag
    from repro.train.steps import make_gan_steps

    ccfg = CycleGANConfig(image_size=16 if args.smoke else 64,
                          enc_hidden=(256, 64), dec_hidden=(64, 256))
    init, train_step, metric = make_gan_steps(
        ccfg, OptimizerConfig(name="adam", lr=args.lr))
    params, opt_state, hparams = init(args.seed)
    xs = jag.sample_inputs(args.samples + 512, seed=0)
    sim = jag.jag_simulate(xs, ccfg.image_size)
    x, y = sim["x"], jag.flatten_outputs(sim)
    val = {"x": jnp.asarray(x[args.samples:]),
           "y": jnp.asarray(y[args.samples:])}
    import numpy as np
    rng = np.random.default_rng(args.seed)
    for i in range(args.steps):
        idx = rng.integers(0, args.samples, 128)
        batch = {"x": jnp.asarray(x[idx]), "y": jnp.asarray(y[idx])}
        params, opt_state, m = train_step(params, opt_state, batch, hparams)
        if i % args.log_every == 0:
            print(f"step {i:5d} g={float(m['g_loss']):.4f} "
                  f"d={float(m['d_loss']):.4f} "
                  f"val={float(metric(params, val)):.4f}")
    print(f"[train] done: val={float(metric(params, val)):.4f}")


def build_parser():
    """The train CLI's argument parser (separate from :func:`main` so
    ``docs/flags.md`` can be checked against it)."""
    ap = argparse.ArgumentParser(prog="repro.launch.train")
    ap.add_argument("--arch", default="icf-cyclegan",
                    choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--samples", type=int, default=8000)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def main(argv=None):
    """CLI entry point: train the selected arch."""
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    if args.arch == "icf-cyclegan":
        train_cyclegan(args)
    else:
        train_lm(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
