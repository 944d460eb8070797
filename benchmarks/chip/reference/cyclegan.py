"""Plain float32 reference of the ICF CycleGAN train step and tournament
metric, and the benchmark's weights for it.

The paper's surrogate (arXiv:1910.02270 section II-D): fully connected
stacks with leaky ReLU (slope 0.2) between layers; forward model
F: x -> z, inverse G: z -> x, autoencoder E: y -> z and Dec: z -> y over
the output bundle, and a latent discriminator D: z -> logit.  One step
updates D on real E(y) against fake F(x) latents, then the generator
(F, G, E, Dec) on mean absolute errors (reconstruction, forward,
latent, cycle) plus a non-saturating adversarial term against the
updated D, each with Adam.  The tournament metric is the forward plus
inverse error on held-out samples.  Everything runs in float32 at
``HIGHEST`` precision; it imports nothing of the program.

``dtype`` and ``share`` plant the control and a fault: the same
step computed in bfloat16 (parameters, activations and the Adam state),
or with the second half of each batch left out of the means.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
STACKS = ("fwd", "inv", "enc", "dec")


def dims(cfg: dict) -> Dict[str, tuple]:
    out = cfg["num_scalars"] + cfg["num_images"] * cfg["image_size"] ** 2
    x, z = cfg["input_dim"], cfg["latent_dim"]
    return {"fwd": (x, *cfg["fwd_hidden"], z),
            "inv": (z, *cfg["inv_hidden"], x),
            "enc": (out, *cfg["enc_hidden"], z),
            "dec": (z, *cfg["dec_hidden"], out),
            "disc": (z, *cfg["disc_hidden"], 1)}


def _key_dims(cfg):
    d = dims(cfg)
    return tuple((k, d[k]) for k in ("fwd", "inv", "enc", "dec", "disc"))


@partial(jax.jit, static_argnums=(0,))
def _make(kd, key):
    keys = jax.random.split(key, len(kd))

    def stack(k, ds):
        ks = jax.random.split(k, len(ds) - 1)
        return {"w": tuple(jax.random.normal(ks[i], (ds[i], ds[i + 1]),
                                             jnp.float32) / jnp.sqrt(ds[i])
                           for i in range(len(ds) - 1)),
                "b": tuple(jnp.zeros((ds[i + 1],), jnp.float32)
                           for i in range(len(ds) - 1))}

    p = {name: stack(k, ds) for k, (name, ds) in zip(keys, kd)}
    return {"gen": {k: p[k] for k in STACKS}, "disc": p["disc"]}


def make_params(cfg: dict, key):
    """Weights N(0, 1/fan_in), zero biases, made on the device in one
    call."""
    return _make(_key_dims(cfg), key)


# ---------------------------------------------------------------------------
# forward and losses
# ---------------------------------------------------------------------------


def _mlp(p, x):
    n = len(p["w"])
    for i, (w, b) in enumerate(zip(p["w"], p["b"])):
        x = jnp.dot(x, w, precision=HI) + b
        if i < n - 1:
            x = jnp.where(x >= 0, x, 0.2 * x)
    return x


def _mae(a, b):
    return jnp.mean(jnp.abs(a - b))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _take(batch, share: float):
    n = batch["x"].shape[0]
    k = max(1, int(round(n * share)))
    return {"x": batch["x"][:k], "y": batch["y"][:k]}


def disc_loss(disc, gen, batch, cfg):
    z_fake = jax.lax.stop_gradient(_mlp(gen["fwd"], batch["x"]))
    z_real = jax.lax.stop_gradient(_mlp(gen["enc"], batch["y"]))
    lr = _mlp(disc, z_real)[..., 0]
    lf = _mlp(disc, z_fake)[..., 0]
    return jnp.mean(_softplus(-lr)) + jnp.mean(_softplus(lf))


def gen_loss(gen, disc, batch, cfg):
    x, y = batch["x"], batch["y"]
    z_fake = _mlp(gen["fwd"], x)
    z_real = _mlp(gen["enc"], y)
    l_recon = _mae(_mlp(gen["dec"], z_real), y)
    l_fwd = _mae(_mlp(gen["dec"], z_fake), y)
    l_lat = _mae(z_fake, jax.lax.stop_gradient(z_real))
    l_cyc = _mae(_mlp(gen["inv"], z_fake), x)
    logit = _mlp(jax.lax.stop_gradient(disc), z_fake)[..., 0]
    l_adv = jnp.mean(_softplus(-logit))
    return (cfg["w_recon"] * l_recon + cfg["w_forward"] * (l_fwd + l_lat)
            + cfg["w_cycle"] * l_cyc + cfg["w_adv"] * l_adv)


def metric(gen, batch):
    """Tournament metric: forward plus inverse error (lower is better)."""
    z = _mlp(gen["fwd"], batch["x"])
    return _mae(_mlp(gen["dec"], z), batch["y"]) \
        + _mae(_mlp(gen["inv"], z), batch["x"])


# ---------------------------------------------------------------------------
# Adam and the step
# ---------------------------------------------------------------------------


def adam_init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32)}


def adam(grads, state, params, opt):
    t = state["t"] + 1
    tf = t.astype(jnp.float32)
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"],
                     grads)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf
    new = jax.tree.map(
        lambda p, m, v: p - opt["lr"] * (m / bc1)
        / (jnp.sqrt(v / bc2) + opt["eps"]), params, m, v)
    return new, {"m": m, "v": v, "t": t}


HP = ("w_recon", "w_forward", "w_cycle", "w_adv")
OPT = ("lr", "b1", "b2", "eps")


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _step(params, state, batch, hp, opt, dtype, share):
    cfg, opt = dict(zip(HP, hp)), dict(zip(OPT, opt))
    cast = lambda t: jax.tree.map(                               # noqa
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, t)
    params, state, batch = cast(params), cast(state), cast(batch)
    batch = _take(batch, share)
    d_loss, d_grads = jax.value_and_grad(disc_loss)(
        params["disc"], params["gen"], batch, cfg)
    new_disc, d_state = adam(d_grads, state["disc"], params["disc"], opt)
    g_loss, g_grads = jax.value_and_grad(gen_loss)(
        params["gen"], new_disc, batch, cfg)
    new_gen, g_state = adam(g_grads, state["gen"], params["gen"], opt)
    up = lambda t: jax.tree.map(                                 # noqa
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, t)
    return (up({"gen": new_gen, "disc": new_disc}),
            {"gen": up(g_state), "disc": up(d_state)},
            {"g_loss": g_loss.astype(jnp.float32),
             "d_loss": d_loss.astype(jnp.float32)},
            up({"gen": g_grads, "disc": d_grads}))


def init_state(params):
    return {"gen": adam_init(params["gen"]), "disc": adam_init(params["disc"])}


def step(params, state, batch, cfg: dict, dtype=jnp.float32,
         share: float = 1.0) -> Tuple:
    """One train step: (params, state, losses, gradients)."""
    return _step(params, state, batch, tuple(float(cfg[k]) for k in HP),
                 tuple(float(cfg["optimizer"][k]) for k in OPT),
                 jnp.dtype(dtype), share)


@partial(jax.jit, static_argnums=(2,))
def _metric(gen, batch, dtype):
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa
    return metric(cast(gen), cast(batch)).astype(jnp.float32)


def tournament_metric(gen, batch, dtype=jnp.float32) -> float:
    return float(_metric(gen, batch, jnp.dtype(dtype)))
