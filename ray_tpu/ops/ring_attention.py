"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Net-new TPU work (SURVEY.md §2.4: SP/context parallelism is absent from the
reference). Each device holds one sequence shard of Q and a rotating shard
of K/V; K/V blocks travel around the `sp` ring via jax.lax.ppermute while
each hop's partial attention is merged with an online-softmax (log-sum-exp)
accumulator, so the full sequence is never materialized on one chip and
communication overlaps compute (XLA schedules the ppermute ahead of the
block math).

Use inside shard_map over a mesh with an `sp` axis; `ring_attention_sharded`
wraps that for callers holding globally-sharded arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .attention import NEG_INF, _repeat_kv
from ..parallel.mesh import AXIS_SP, BATCH_AXES


def _block_attend(q, k, v, scale, causal_mode, q_offset, kv_offset):
    """One block pair: returns (numerator, row max, row denominator).

    causal_mode: 0 = fully visible (kv chunk strictly before q chunk),
                 1 = diagonal (same chunk: in-chunk causal mask),
                 2 = fully masked (kv chunk after q chunk).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(sq)[:, None]
    k_pos = kv_offset + jnp.arange(sk)[None, :]
    visible = jnp.where(causal_mode == 0, True,
                        jnp.where(causal_mode == 1, q_pos >= k_pos, False))
    logits = jnp.where(visible[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)                  # (b,h,q,1)
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0).
    m_safe = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(logits - m_safe)
    p = jnp.where(visible[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)                       # (b,h,q,1)
    num = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return num.astype(jnp.float32), m_safe, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = AXIS_SP,
                   causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Per-shard ring attention. Call inside shard_map.

    q: (B, Sq_local, H, D); k/v: (B, Sk_local, KVH, D) — the local shards.
    """
    b, sq, h, d = q.shape
    scale_val = scale if scale is not None else d ** -0.5
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    sk = k.shape[1]

    acc = jnp.zeros((b, sq, h, d), jnp.float32)
    m_run = jnp.full((b, h, sq, 1), NEG_INF / 2, jnp.float32)
    l_run = jnp.zeros((b, h, sq, 1), jnp.float32)

    def step(carry, s):
        acc, m_run, l_run, k_cur, v_cur = carry
        # At hop s this device holds the kv chunk originally on (my - s).
        src = (my - s) % sp
        if causal:
            mode = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
        else:
            mode = jnp.int32(0)
        num, m_blk, l_blk = _block_attend(
            q, k_cur, v_cur, scale_val, mode,
            q_offset=my * sq, kv_offset=src * sk)
        m_new = jnp.maximum(m_run, m_blk)
        c_run = jnp.exp(m_run - m_new)
        c_blk = jnp.exp(m_blk - m_new)
        l_new = l_run * c_run + l_blk * c_blk
        # (b,h,q,1) -> (b,q,h,1) to scale the (b,q,h,d) accumulators.
        acc = (acc * c_run.transpose(0, 2, 1, 3)
               + num * c_blk.transpose(0, 2, 1, 3))
        # Rotate kv to the next device (skip after the final hop).
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, m_new, l_new, k_nxt, v_nxt), None

    (acc, m_run, l_run, _, _), _ = jax.lax.scan(
        step, (acc, m_run, l_run, k, v), jnp.arange(sp))
    out = acc / jnp.maximum(l_run.transpose(0, 2, 1, 3), 1e-30)
    return out.astype(q.dtype)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, *, causal: bool = True) -> jax.Array:
    """Ring attention on globally-sharded (B, S, H, D) arrays: shard_map
    over (batch -> dp/fsdp, seq -> sp)."""
    spec = PartitionSpec(BATCH_AXES, AXIS_SP, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
