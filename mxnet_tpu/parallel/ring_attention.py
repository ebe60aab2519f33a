"""Blockwise + ring attention: long-context sequence/context parallelism.

The reference has NO sequence parallelism (SURVEY.md §5.7 — MXNet predates
it; its long-sequence story is bucketing).  This module is the capability
the TPU build adds to meet the BERT-pod config: attention over sequences
sharded across the ICI mesh.

* ``blockwise_attention`` — single-device flash-style attention: O(T) memory
  via running max / normaliser accumulation over KV blocks (`lax.scan`).
  This is the XLA-fusable fallback; a Pallas kernel can swap in later
  behind the same signature.
* ``ring_attention`` — KV shards rotate around the ICI ring
  (``lax.ppermute``) while every device keeps its local Q shard; each hop
  contributes a partial softmax accumulated flash-style, so the full T×T
  score matrix never materialises on any chip.  Communication is
  neighbour-only → rides ICI at full bandwidth, overlapping with the local
  block matmuls (MXU).

Layout convention: (batch, heads, seq, head_dim), seq sharded over the
named mesh axis (default ``"sp"``) for the ring variant.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["blockwise_attention", "ring_attention", "ring_attention_sharded"]


def _block_scores(q, k, scale):
    # q: (B, H, Tq, D), k: (B, H, Tk, D) → (B, H, Tq, Tk); bf16-in fp32-acc
    return jnp.einsum("bhqd,bhkd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale


def _flash_update(acc, scores, v_blk, mask=None):
    """One flash-attention accumulation step.

    acc = (m, l, o): running max (B,H,Tq), normaliser (B,H,Tq),
    unnormalised output (B,H,Tq,D) — the standard online-softmax update.
    """
    m, l, o = acc
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32)
    return (m_new, l_new, o_new)


def blockwise_attention(q, k, v, block_size: int = 512, causal: bool = False,
                        scale: Optional[float] = None):
    """Memory-linear attention on one device (flash-style).

    Equivalent math to the reference's contrib transformer attention ops
    (``src/operator/contrib/transformer.cc`` interleaved matmuls + softmax),
    restructured so peak memory is O(T·block) instead of O(T²).
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    block_size = min(block_size, Tk)
    n_blocks = -(-Tk // block_size)
    pad = n_blocks * block_size - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(B, H, n_blocks, block_size, D).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(B, H, n_blocks, block_size, D).transpose(2, 0, 1, 3, 4)

    q_pos = jnp.arange(Tq)
    m0 = jnp.full((B, H, Tq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    o0 = jnp.zeros((B, H, Tq, D), jnp.float32)

    def body(acc, inputs):
        blk_idx, k_blk, v_blk = inputs
        scores = _block_scores(q, k_blk, scale)
        kv_pos = blk_idx * block_size + jnp.arange(block_size)
        valid = kv_pos < Tk
        mask = jnp.broadcast_to(valid[None, None, None, :], scores.shape)
        if causal:
            cmask = q_pos[:, None] >= kv_pos[None, :]
            mask = mask & cmask[None, None]
        return _flash_update(acc, scores, v_blk, mask), None

    (m, l, o), _ = lax.scan(body, (m0, l0, o0),
                            (jnp.arange(n_blocks), kb, vb))
    return (o / l[..., None]).astype(q.dtype)


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: Optional[float] = None, block_size: int = 512):
    """Ring attention over a named mesh axis (call inside shard_map).

    Each device owns the Q/K/V shard of its sequence chunk; K/V rotate
    around the ring so after ``axis_size`` hops every Q block has attended
    to the full sequence.  Based on the blockwise-parallel-transformer /
    ring-attention construction (public technique; see PAPERS.md).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, T_local, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    perm = [(i, (i + 1) % n) for i in range(n)]

    q_pos = idx * T_local + jnp.arange(T_local)

    m0 = jnp.full((B, H, T_local), -1e30, jnp.float32)
    l0 = jnp.zeros((B, H, T_local), jnp.float32)
    o0 = jnp.zeros((B, H, T_local, D), jnp.float32)
    # mark accumulators as device-varying along the ring axis so the scan
    # carry type matches after the flash update (jax vma type system);
    # pvary is deprecated in favour of pcast(..., to='varying')
    _pcast = getattr(lax, "pcast", None)
    if _pcast is not None:
        m0, l0, o0 = (_pcast(a, (axis_name,), to="varying")
                      for a in (m0, l0, o0))
    elif hasattr(lax, "pvary"):
        m0, l0, o0 = (lax.pvary(a, (axis_name,)) for a in (m0, l0, o0))

    def body(carry, _):
        m, l, o, k_cur, v_cur, src = carry
        scores = _block_scores(q, k_cur, scale)
        if causal:
            kv_pos = src * T_local + jnp.arange(T_local)
            cmask = q_pos[:, None] >= kv_pos[None, :]
            mask = jnp.broadcast_to(cmask[None, None], scores.shape)
        else:
            mask = None
        acc = _flash_update((m, l, o), scores, v_cur, mask)
        # rotate KV to the next ring neighbour (overlaps with next matmul)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        src_nxt = (src - 1) % n
        return (*acc, k_nxt, v_nxt, src_nxt), None

    (m, l, o, _, _, _), _ = lax.scan(body, (m0, l0, o0, k, v, idx),
                                     None, length=n)
    return (o / l[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh=None, axis: str = "sp",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Convenience wrapper: shard_map ``ring_attention`` over ``mesh[axis]``
    with Q/K/V sequence-sharded — the user-facing CP entry point."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from .mesh import default_mesh
    from ..ndarray import NDArray
    from ..ndarray.ndarray import _wrap

    mesh = mesh or default_mesh()
    unwrap = lambda x: x._data if isinstance(x, NDArray) else x
    qv, kv_, vv = unwrap(q), unwrap(k), unwrap(v)
    spec = P(None, None, axis, None)

    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = fn(qv, kv_, vv)
    return _wrap(out, q.context) if isinstance(q, NDArray) else out
