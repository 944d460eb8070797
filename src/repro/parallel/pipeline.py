"""Pipeline parallelism (PP): GPipe-style circular pipeline over a
``stage`` mesh axis (the MaxText pipelining pattern, JAX-native).

The layer stack is split into S stages; stage s's parameters live on the
mesh slice ``stage=s`` (sharded via shard_map).  M >= S microbatches
flow through the pipeline in M + S - 1 ticks; at each tick every stage
applies its layers to its current activation and the activations rotate
one stage forward via ``lax.ppermute`` (HLO collective-permute over the
ICI ring — the natural TPU topology for PP).

Autodiff goes straight through (transpose of ppermute is the reverse
permute), so ``jax.grad`` of a pipelined loss is GPipe backward; wrap
``stage_fn`` in ``jax.checkpoint`` for the standard activation-memory
profile.  Bubble fraction = (S-1)/(M+S-1), reported by
:func:`bubble_fraction`.

Composes with the other axes: the mesh can be ('stage','data','model'),
with DP/TP rules applying inside each stage as usual.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

Params = Any


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_apply(stage_params: Params, x_mb: jax.Array,
                   stage_fn: Callable[[Params, jax.Array], jax.Array],
                   num_stages: int, axis: str = "stage") -> jax.Array:
    """Run the circular pipeline; call INSIDE shard_map over `axis`.

    stage_params: this stage's parameter shard (leading dim already
        consumed by shard_map).
    x_mb: (M, mb, ...) microbatches — identical on every stage; stage 0
        feeds them in, stage S-1 produces outputs.
    Returns (M, mb, ...) outputs (valid on every stage; they are
        broadcast back through the rotation).
    """
    S = num_stages
    M = x_mb.shape[0]
    ticks = M + S - 1
    stage_id = jax.lax.axis_index(axis)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    mb_shape = x_mb.shape[1:]
    state = jnp.zeros(mb_shape, x_mb.dtype)          # current activation
    outputs = jnp.zeros_like(x_mb)                   # collected at exit

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (if in range)
        feed = x_mb[jnp.clip(t, 0, M - 1)]
        state = jnp.where((stage_id == 0) & (t < M), feed, state)
        # every stage applies its layers
        state = stage_fn(stage_params, state)
        # stage S-1 has finished microbatch (t - (S-1)) at the END of tick t
        out_idx = t - (S - 1)
        is_exit = (stage_id == S - 1) & (out_idx >= 0)
        outputs = jax.lax.dynamic_update_slice_in_dim(
            outputs,
            jnp.where(is_exit, state, outputs[jnp.clip(out_idx, 0, M - 1)])
            [None],
            jnp.clip(out_idx, 0, M - 1), axis=0)
        # rotate activations to the next stage
        state = jax.lax.ppermute(state, axis, fwd_perm)
        return (state, outputs), None

    (state, outputs), _ = jax.lax.scan(
        tick, (state, outputs), jnp.arange(ticks))
    # outputs live on stage S-1; broadcast to all stages (masked psum)
    # so every shard returns the same value and shard_map's out_spec is
    # replicated over the stage axis
    mask = (stage_id == S - 1).astype(outputs.dtype)
    outputs = jax.lax.psum(outputs * mask, axis)
    return outputs


def make_pipelined_forward(stage_fn: Callable, mesh: Mesh,
                           num_stages: int, axis: str = "stage",
                           param_spec=None, x_spec=None):
    """Build f(stacked_stage_params, microbatches) -> outputs.

    stacked_stage_params: leading dim = num_stages (sharded over `axis`);
    microbatches: (M, mb, ...) replicated over `axis`.
    """
    p_spec = param_spec if param_spec is not None else P(axis)

    def body(params, x_mb):
        params = jax.tree.map(lambda a: a[0], params)
        out = pipeline_apply(params, x_mb, stage_fn, num_stages, axis)
        return out

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_spec, x_spec if x_spec is not None else P()),
        out_specs=P(), check_vma=False)
