"""Train step builders — the functions the launchers jit.

``make_lm_train_step``   -> LM loss + grads + optimizer
``make_gan_steps``       -> the paper's CycleGAN (generator+discriminator)
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.configs.base import MeshConfig, ModelConfig, OptimizerConfig
from repro.configs.icf_cyclegan import CycleGANConfig
from repro.models import icf_cyclegan as cg
from repro.models import lm
from repro.optim import optimizers as opt_lib

Params = Any


# ---------------------------------------------------------------------------
# LM steps
# ---------------------------------------------------------------------------


def make_lm_train_step(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                       mesh_cfg: Optional[MeshConfig] = None) -> Callable:
    mesh_cfg = mesh_cfg or MeshConfig()
    optimizer = opt_lib.make_optimizer(opt_cfg)

    def train_step(state: Dict[str, Any], batch: Dict[str, jax.Array]):
        params = state["params"]

        def loss_fn(p):
            return lm.lm_loss(p, cfg, batch, remat=mesh_cfg.remat)

        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if opt_cfg.grad_clip_norm:
            grads, gnorm = opt_lib.clip_by_global_norm(
                grads, opt_cfg.grad_clip_norm)
            metrics = {**metrics, "grad_norm": gnorm}
        lr = opt_lib.lr_schedule(opt_cfg, state["opt_state"]["step"])
        new_params, new_opt = optimizer.update(grads, state["opt_state"],
                                               params, lr)
        new_state = {"params": new_params, "opt_state": new_opt}
        return new_state, {**metrics, "loss": loss, "lr": lr}

    return train_step


def make_lm_eval_metric(cfg: ModelConfig) -> Callable:
    """Tournament metric for LM archs: held-out CE (lower better)."""

    def metric(params, batch):
        loss, _ = lm.lm_loss(params, cfg, batch)
        return loss

    return metric


def make_lm_population_fns(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                           mesh_cfg: Optional[MeshConfig] = None):
    """(init, train_step, metric) adapter so LM architectures plug into
    the LTFB population/tournament orchestrator exactly like the GAN.

    The LM step drives its own lr schedule from the optimizer step
    count; the hparams dict carries the base lr for PBT bookkeeping but
    perturbations do not rewire the compiled schedule.
    """
    step_fn = jax.jit(make_lm_train_step(cfg, opt_cfg, mesh_cfg))
    metric = jax.jit(make_lm_eval_metric(cfg))

    def init(seed: int):
        state, _ = init_lm_state(cfg, opt_cfg, jax.random.PRNGKey(seed))
        return state["params"], state["opt_state"], {"lr": opt_cfg.lr}

    def train_step(params, opt_state, batch, hparams):
        new_state, metrics = step_fn(
            {"params": params, "opt_state": opt_state}, batch)
        return new_state["params"], new_state["opt_state"], metrics

    return init, train_step, metric


def init_lm_state(cfg: ModelConfig, opt_cfg: OptimizerConfig,
                  key: jax.Array) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (state, axes) where axes mirrors state for sharding."""
    params, p_axes = lm.init_lm(cfg, key)
    optimizer = opt_lib.make_optimizer(opt_cfg)
    opt_state = optimizer.init(params)
    o_axes = opt_state_axes(opt_cfg, p_axes)
    return ({"params": params, "opt_state": opt_state},
            {"params": p_axes, "opt_state": o_axes})


def opt_state_axes(opt_cfg: OptimizerConfig, p_axes):
    """Optimizer-state logical axes (ZeRO: moments inherit param axes)."""
    is_axes = lambda t: isinstance(t, tuple) and all(
        x is None or isinstance(x, str) for x in t)
    if opt_cfg.name in ("adam", "adamw"):
        return {"m": p_axes, "v": p_axes, "step": ()}
    if opt_cfg.name == "adafactor":
        vr = jax.tree.map(lambda a: a[:-1] if len(a) >= 2 else a,
                          p_axes, is_leaf=is_axes)
        vc = jax.tree.map(lambda a: a[:-2] + a[-1:] if len(a) >= 2
                          else (None,), p_axes, is_leaf=is_axes)
        return {"vr": vr, "vc": vc, "step": ()}
    if opt_cfg.name == "sgd":
        return {"mom": p_axes, "step": ()}
    raise ValueError(opt_cfg.name)


# ---------------------------------------------------------------------------
# CycleGAN steps (the paper's model)
# ---------------------------------------------------------------------------


def make_gan_steps(ccfg: CycleGANConfig, opt_cfg: OptimizerConfig):
    """Returns (init, train_step, metric) suitable for
    repro.core.population.TrainerFns.  One train_step = one discriminator
    update + one generator update (standard simultaneous GAN schedule).
    """
    optimizer = opt_lib.make_optimizer(opt_cfg)

    def init(seed: int):
        params, _ = cg.init_cyclegan(ccfg, jax.random.PRNGKey(seed))
        opt_state = {"gen": optimizer.init(params["gen"]),
                     "disc": optimizer.init(params["disc"])}
        return params, opt_state, {"lr": opt_cfg.lr}

    @jax.jit
    def train_step(params, opt_state, batch, hparams):
        lr = hparams["lr"]
        # --- discriminator ---
        (d_loss, d_metrics), d_grads = jax.value_and_grad(
            cg.discriminator_loss, has_aux=True)(
                params["disc"], params["gen"], ccfg, batch)
        new_disc, new_dopt = optimizer.update(
            d_grads, opt_state["disc"], params["disc"], lr)
        # --- generator ---
        (g_loss, g_metrics), g_grads = jax.value_and_grad(
            cg.generator_loss, has_aux=True)(
                params["gen"], new_disc, ccfg, batch)
        new_gen, new_gopt = optimizer.update(
            g_grads, opt_state["gen"], params["gen"], lr)
        params = {"gen": new_gen, "disc": new_disc}
        opt_state = {"gen": new_gopt, "disc": new_dopt}
        metrics = {"g_loss": g_loss, "d_loss": d_loss,
                   **d_metrics, **g_metrics}
        return params, opt_state, metrics

    @jax.jit
    def metric(params, batch):
        return cg.validation_metric(params, ccfg, batch)

    return init, train_step, metric


def make_gan_disc_metric(ccfg: CycleGANConfig):
    """The paper's GAN tournament metric (Fig. 6b): score a (possibly
    foreign) generator against the LOCAL discriminator."""

    @jax.jit
    def metric(params, batch):
        return cg.discriminator_metric(params, ccfg, batch)

    return metric
