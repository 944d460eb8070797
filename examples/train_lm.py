"""Train an assigned-architecture LM on the synthetic token pipeline.

Any of the 10 architectures is selectable; reduced (smoke) configs keep
this runnable on CPU, and the identical code path runs the full configs
on a device mesh.  Optional --ltfb K runs the
tournament algorithm over K trainers (full-model exchange).

  PYTHONPATH=src python examples/train_lm.py --arch xlstm-125m --steps 60
  PYTHONPATH=src python examples/train_lm.py --arch qwen2.5-3b --ltfb 2
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.configs.base import MeshConfig, OptimizerConfig
from repro.configs.registry import LM_ARCH_IDS, get_config
from repro.core.population import Population, TrainerFns
from repro.data.tokens import train_batch
from repro.train.steps import (init_lm_state, make_lm_eval_metric,
                               make_lm_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=LM_ARCH_IDS)
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ltfb", type=int, default=0,
                    help="number of LTFB trainers (0 = single trainer)")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    opt = OptimizerConfig(lr=3e-3, warmup_steps=10)
    print(f"arch={cfg.name}: {cfg.param_count()/1e6:.1f}M params "
          f"({cfg.param_count(True)/1e6:.1f}M active), family={cfg.family}")

    raw_step = make_lm_train_step(cfg, opt, MeshConfig(remat="none"))
    step = jax.jit(raw_step)
    metric = jax.jit(make_lm_eval_metric(cfg))
    val = {k: jnp.asarray(v)
           for k, v in train_batch(cfg, args.batch, args.seq, 9999).items()}

    if args.ltfb:
        K = args.ltfb

        def init(seed):
            st, _ = init_lm_state(cfg, opt, jax.random.PRNGKey(seed))
            return st["params"], st["opt_state"], {"lr": opt.lr}

        def tstep(params, opt_state, batch, hparams):
            st, m = step({"params": params, "opt_state": opt_state}, batch)
            return st["params"], st["opt_state"], m

        def loader_for(k):
            c = [0]
            def loader():
                c[0] += 1
                b = train_batch(cfg, args.batch, args.seq,
                                seed=k * 100000 + c[0])
                return {kk: jnp.asarray(v) for kk, v in b.items()}
            return loader

        fns = TrainerFns(init, tstep, metric)
        tourn = [[{k: jnp.asarray(v) for k, v in
                   train_batch(cfg, args.batch, args.seq, 7_000 + k).items()}]
                 for k in range(K)]
        pop = Population(fns, [loader_for(k) for k in range(K)], tourn,
                         scope="full", seed=0)
        rounds = max(1, args.steps // 20)
        for r in range(rounds):
            pop.train_round(20)
            log = pop.tournament()
            print(f"round {r}: exchanged={log['exchanged']} "
                  f"best_val={pop.best_metric(val):.4f}")
        return

    state, _ = init_lm_state(cfg, opt, jax.random.PRNGKey(0))
    t0 = time.time()
    for i in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in
                 train_batch(cfg, args.batch, args.seq, seed=i).items()}
        state, m = step(state, batch)
        if i % 10 == 0:
            print(f"step {i:4d} loss={float(m['loss']):.4f} "
                  f"val={float(metric(state['params'], val)):.4f}")
    print(f"{args.steps} steps in {time.time()-t0:.1f}s; "
          f"final val={float(metric(state['params'], val)):.4f}")


if __name__ == "__main__":
    main()
