"""jit'd public wrappers for the Pallas kernels.

On TPU the kernels run compiled; everywhere else (this CPU container)
they run in ``interpret=True`` mode, which executes the kernel body in
Python per grid point — bit-comparable against the ``ref.py`` oracles.

``paged_attention`` additionally carries the SERVING-MESH dispatch:
when model code is traced under a sharding context whose mesh has a
``data`` axis of size > 1 (see :mod:`repro.serve.mesh`), the gather
runs inside a ``shard_map`` over the mesh — each data shard gathers
ONLY from its own slice of the page pool (block-table entries are
global page ids; the shard subtracts its pool offset), so a decode
step never moves KV pages across the ``data`` axis.  The dispatch
happens at trace time, outside any jit cache, so mesh and single-
device callers can never alias each other's lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as fa
from repro.kernels import latent_attention as la
from repro.kernels import paged_attention as pa
from repro.kernels import ref
from repro.kernels import rmsnorm as rn
from repro.parallel import sharding as _sharding


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = None):
    """q: (B, H, S, D); k/v: (B, Hkv, S, D) -> (B, H, S, D)."""
    if interpret is None:
        interpret = not _on_tpu()
    return fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, interpret=interpret)


def _paged_attention_local(q, k_pages, v_pages, layer, tables, lengths,
                           interpret=None):
    """Single-shard gather: Pallas kernel on TPU (or when interpret
    mode is explicitly requested), jnp oracle everywhere else —
    interpret mode executes the grid in Python and is far too slow for
    a decode loop."""
    if interpret is None:
        if not _on_tpu():
            return ref.paged_attention_ref(q, k_pages, v_pages, layer,
                                           tables, lengths)
        interpret = False
    return pa.paged_attention(q, k_pages, v_pages, layer, tables, lengths,
                              interpret=interpret)


def paged_attention_sharded(mesh, q, k_pages, v_pages, layer, tables,
                            lengths, interpret: bool = None):
    """Gather-decode over a stacked page pool sharded on the mesh
    ``data`` axis.

    The pool's page dim (dim 1; dim 0 is the layer, replicated, and
    ``layer`` passes through to every shard) is split into
    ``data``-many private sub-pools
    (each with its own trailing null page); ``tables`` holds GLOBAL
    page ids, and every row's pages live in that row's shard — the
    invariant :class:`repro.serve.mesh.MeshPagedLayout` maintains.
    Inside the shard_map each shard rebases its table slice to local
    ids and runs the ordinary single-shard kernel/oracle, so no KV
    page ever crosses the ``data`` axis.  Heads additionally split
    over ``model`` when both q and kv head counts divide it (GQA
    grouping preserved); otherwise heads stay replicated.
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data = axes.get("data", 1)
    model = axes.get("model", 1)
    B = q.shape[0]
    H, Hkv = q.shape[-2], k_pages.shape[3]
    if B % data != 0 or k_pages.shape[1] % data != 0:
        raise ValueError(
            f"paged_attention_sharded: batch {B} and pool pages "
            f"{k_pages.shape[1]} must be divisible by the data axis "
            f"({data})")
    shard_heads = model > 1 and H % model == 0 and Hkv % model == 0
    mspec = "model" if shard_heads else None
    q_spec = P("data", None, mspec, None) if q.ndim == 4 \
        else P("data", mspec, None)
    kv_spec = P(None, "data", None, mspec, None)
    pages_per_shard = k_pages.shape[1] // data

    def local(qs, ks, vs, ly, ts, ls):
        shard = jax.lax.axis_index("data")
        local_t = jnp.clip(ts - shard * pages_per_shard, 0,
                           pages_per_shard - 1).astype(jnp.int32)
        return _paged_attention_local(qs, ks, vs, ly, local_t, ls,
                                      interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(), P("data", None),
                  P("data")),
        out_specs=q_spec, check_vma=False)(
            q, k_pages, v_pages, jnp.asarray(layer, jnp.int32), tables,
            lengths)


def paged_attention(q, k_pages, v_pages, layer, tables, lengths,
                    interpret: bool = None):
    """Decode/verify attention over scattered KV pages of one layer of
    a stacked pool.

    q: (B, H, D), or (B, K, H, D) for a K-token speculative-verify
    step; k_pages/v_pages: (layers, P, bs, Hkv, D); layer: int32
    scalar; tables: (B, W); lengths:
    (B,) valid KV tokens for the FIRST query of each row (query t sees
    ``lengths + t``; 0 marks an idle row, whose output is zeros) ->
    same rank as q.  The kernel streams each row's live pages a block
    at a time and reads nothing for an idle row.

    Dispatch (decided at trace time — deliberately NOT a jit boundary,
    so a mesh trace can never reuse a single-device lowering):

    * a sharding context with a ``data`` axis of size > 1 active ->
      :func:`paged_attention_sharded` (shard_map; pages stay on-shard);
    * TPU -> the compiled Pallas kernel; explicit ``interpret=True``
      runs it in interpret mode (tests);
    * otherwise -> the jnp oracle ``ref.paged_attention_ref``.
    """
    mesh = _sharding.current_mesh()
    if mesh is not None and "data" in mesh.axis_names \
            and dict(zip(mesh.axis_names, mesh.devices.shape))["data"] > 1:
        return paged_attention_sharded(mesh, q, k_pages, v_pages, layer,
                                       tables, lengths, interpret=interpret)
    return _paged_attention_local(q, k_pages, v_pages, layer, tables,
                                  lengths, interpret=interpret)


def latent_paged_attention(q, pool, layer, tables, lengths, scale: float,
                           value_dim: int, interpret: bool = None):
    """Absorbed latent-attention decode over a stacked paged latent pool.

    q: (B, H, D) or (B, K, H, D); pool: (layers, P+1, bs, D); layer:
    int32 scalar; tables: (B, W); lengths: (B,) (0: idle row) -> float32
    (.., H, value_dim).  The compiled Pallas kernel on TPU (or in
    interpret mode when asked), the jnp oracle everywhere else.
    Single-device only: no serving mesh holds a latent pool.
    """
    if interpret is None:
        if not _on_tpu():
            return ref.latent_paged_attention_ref(q, pool, layer, tables,
                                                  lengths, scale, value_dim)
        interpret = False
    return la.latent_paged_attention(q, pool, layer, tables, lengths,
                                     scale=scale, value_dim=value_dim,
                                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x, scale, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = None):
    if interpret is None:
        interpret = not _on_tpu()
    return rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                      interpret=interpret)
