"""JAG ICF samples for the training cells, made on the device from a seed.

The same structure as the JAG semi-analytic simulator's output the paper
trains on: 5 drive and shape inputs in [0, 1] (scrambled Halton points
shifted by the seed), 15 scalar observables and 12 hyperspectral X-ray
images (3 lines of sight x 4 channels).  The formulas follow the
program's synthetic simulator, written out here so that the data the
benchmark feeds cannot move with the program.  One jitted call makes the
whole set; it then moves to the host once, where the datastore keeps it.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict

import numpy as np

NUM_INPUTS, NUM_SCALARS, NUM_VIEWS, NUM_CHANNELS = 5, 15, 3, 4
NUM_IMAGES = NUM_VIEWS * NUM_CHANNELS


def halton(n: int, seed: int) -> np.ndarray:
    """(n, 5) points of the Halton sequence in bases 2..11, each
    coordinate shifted by a draw from ``seed`` (mod 1)."""
    shift = np.random.default_rng([seed, 0x4A47]).random(NUM_INPUTS)
    idx = np.arange(1, n + 1)
    cols = []
    for p in (2, 3, 5, 7, 11):
        x, denom, i = np.zeros(n), p, idx.copy()
        while i.max() > 0:
            x += (i % p) / denom
            i //= p
            denom *= p
        cols.append(x)
    return ((np.stack(cols, 1) + shift) % 1.0).astype(np.float32)


def _simulate(x, size: int):
    import jax.numpy as jnp

    d, fill, s = x[:, 0], x[:, 1], x[:, 2:5]
    asym = jnp.linalg.norm(s - 0.5, axis=1)
    yld = jnp.exp(4.0 * d) * (1.0 - 0.8 * asym ** 2) * (0.3 + fill)
    tion = 1.0 + 3.0 * d ** 2 - asym
    rho_r = (0.4 + d) * (1.0 - 0.5 * asym) * (0.5 + 0.5 * fill)
    scalars = jnp.stack([
        yld, jnp.log1p(yld), tion, tion ** 2 / 4.0,
        0.5 + 0.5 * jnp.tanh(6.0 * (d - 0.55)), rho_r,
        jnp.sin(math.pi * d) * jnp.cos(2 * math.pi * s[:, 0]),
        s[:, 0] * s[:, 1] - s[:, 2] ** 2, jnp.exp(-8.0 * asym ** 2),
        d * fill, jnp.sqrt(jnp.maximum(yld, 0)) * 0.1,
        jnp.cos(3 * math.pi * (d - asym)), (1 - d) * asym,
        jnp.maximum(0.0, d - 2 * asym),
        0.2 + 0.6 * fill + 0.2 * jnp.sin(2 * math.pi * s[:, 1])], axis=1)
    lin = jnp.linspace(-1, 1, size)
    yy, xx = jnp.meshgrid(lin, lin, indexing="ij")
    r = jnp.sqrt(xx ** 2 + yy ** 2) + 1e-6
    th = jnp.arctan2(yy, xx)
    dd = d[:, None, None]
    p2 = (s[:, 0] - 0.5)[:, None, None]
    p3 = (s[:, 1] - 0.5)[:, None, None]
    rot = (s[:, 2] - 0.5)[:, None, None] * math.pi
    views = []
    for v in range(NUM_VIEWS):
        ph = 2.0 * math.pi * v / NUM_VIEWS
        radius = 0.55 * (1.0 + 0.35 * p2 * jnp.cos(2 * (th + rot + ph))
                         + 0.25 * p3 * jnp.cos(3 * (th + rot + ph)))
        radius = jnp.maximum(radius, 0.05)
        shell = jnp.exp(-0.5 * ((r - radius) / (0.08 + 0.05 * (1 - dd))) ** 2)
        core = jnp.exp(-0.5 * (r / (0.15 + 0.1 * dd)) ** 2) * dd
        base = shell + 1.5 * core
        for c in range(NUM_CHANNELS):
            views.append(base * jnp.exp(-c * (1.2 - dd)))
    images = jnp.stack(views, axis=1).astype(jnp.float32)
    return scalars.astype(jnp.float32), images


def generate(n: int, image_size: int, seed: int) -> Dict[str, np.ndarray]:
    """``n`` samples as host arrays: ``x`` (n, 5), ``scalars`` (n, 15),
    ``images`` (n, 12, size, size)."""
    import jax

    x = halton(n, seed)
    fn = jax.jit(partial(_simulate, size=image_size))
    scalars, images = jax.device_get(fn(x))
    return {"x": x, "scalars": np.asarray(scalars),
            "images": np.asarray(images)}
