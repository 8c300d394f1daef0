"""Attention ops: XLA reference impl + Pallas TPU flash-attention kernel.

This is net-new TPU work: the reference has no in-repo attention (vLLM is
external; SURVEY.md §2.4 marks SP/long-context absent). Shapes follow
(batch, seq, heads, head_dim) with GQA (kv_heads divides heads).

The flash kernel uses the online-softmax accumulation pattern with a
3-D grid (batch*heads, q_blocks, kv_blocks): the kv grid dimension is
innermost and sequential on TPU, so the running max / denominator / output
accumulator live in VMEM scratch across kv steps. The forward also emits
the per-row logsumexp; backward is two Pallas kernels (FlashAttention-2
style): a dq kernel accumulating over kv blocks and a dk/dv kernel
accumulating over (grouped-query head, q block) pairs, with
delta = rowsum(dO * O) precomputed in XLA.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from ..parallel.mesh import AXIS_TP, BATCH_AXES

# 512-blocks measured ~1.7x faster than 128 end-to-end on v5e (the
# (512, 512) f32 logits tile still fits VMEM comfortably); _resolve_blocks
# clamps to the sequence length for short inputs.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _repeat_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B, S, KVH, D) -> (B, S, H, D) by repeating each kv head.

    broadcast+reshape, NOT jnp.repeat: repeat lowers to a gather whose
    sharding the SPMD partitioner can't propagate through a
    head-sharded (tp) mesh — it falls back to full rematerialization
    (replicate + repartition) of K/V. The broadcast form stays an
    elementwise/layout op and partitions cleanly."""
    b, s, kvh, d = k.shape
    if kvh == num_heads:
        return k
    reps = num_heads // kvh
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, s, kvh, reps, d)
    ).reshape(b, s, num_heads, d)


def _out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """pallas_call out_shape that varies over the same manual mesh
    axes as the kernel's inputs — shard_map's varying-axes check needs
    to be told (a pallas_call cannot infer it)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        q_offset: int | jax.Array = 0,
                        kv_offset: int | jax.Array = 0,
                        scale: Optional[float] = None) -> jax.Array:
    """Plain XLA attention. q: (B, Sq, H, D); k/v: (B, Sk, KVH, D).

    q_offset/kv_offset are the global positions of the first query/key —
    used by ring attention where each device holds a rotating kv chunk.
    """
    b, sq, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + jnp.arange(sq)[:, None]
        k_pos = kv_offset + jnp.arange(k.shape[1])[None, :]
        mask = q_pos >= k_pos
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# --------------------------------------------------------------------------
# Pallas flash attention (forward)
# --------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr,
                  *, causal: bool, scale: float,
                  block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: the kv block is live iff its first key position can be seen
    # by the last query of this q block.
    if causal:
        live = ki * block_k <= qi * block_q + (block_q - 1)
    else:
        live = ki >= 0

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0].astype(jnp.float32)          # (block_k, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        m_prev = m_scr[:]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                     # (bq, bk)
        corr = jnp.exp(m_prev - m_new)             # (bq, 1)
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ki == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _flash_forward(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool, scale: float,
                   block_q: int, block_k: int,
                   interpret: bool = False):
    """q: (BH, Sq, D); k/v: (BKVH, Sk, D); grouped via index maps.

    Returns (out (BH, Sq, D), lse (BH, Sq, 1) float32)."""
    bh, sq, d = q.shape
    bkvh, sk, _ = k.shape
    group = bh // bkvh
    grid = (bh, pl.cdiv(sq, block_q), pl.cdiv(sk, block_k))

    return pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k),
        out_shape=(
            _out_struct((bh, sq, d), q.dtype, q, k, v),
            _out_struct((bh, sq, 1), jnp.float32, q, k, v),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)


# --------------------------------------------------------------------------
# Pallas flash attention (backward)
# --------------------------------------------------------------------------

def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              qi, ki, causal, scale, block_q, block_k):
    """Shared per-tile recompute: returns (p, ds, q, k, do) in f32.

    p = softmax probabilities from the saved logsumexp; ds = the softmax
    backward dS = P o (dP - delta)."""
    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)        # (bq, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        row = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        col = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(row >= col, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0])               # masked entries underflow to 0
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # (bq, bk)
    ds = p * (dp - delta_ref[0])
    return p, ds, q, k, do


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_scr,
                     *, causal: bool, scale: float,
                     block_q: int, block_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = (qi + 1) * block_q > ki * block_k if causal else ki >= 0

    @pl.when(live)
    def _step():
        _, ds, _, k, _ = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                   delta_ref, qi, ki, causal, scale,
                                   block_q, block_k)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr,
                      *, causal: bool, scale: float,
                      block_q: int, block_k: int, n_qb: int):
    kj = pl.program_id(1)
    t = pl.program_id(2)          # (group, q_block) pairs, q innermost
    n_t = pl.num_programs(2)
    qi = t % n_qb

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = (qi + 1) * block_q > kj * block_k if causal else t >= 0

    @pl.when(live)
    def _step():
        p, ds, q, _, do = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, qi, kj, causal, scale,
                                    block_q, block_k)
        # contract the bq dim: p^T @ dO and ds^T @ q, both (bk, d)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, causal, scale,
                    block_q, block_k, interpret=False):
    """All flat: q/o/do (BH, Sq, D); k/v (BKVH, Sk, D); lse (BH, Sq, 1)."""
    bh, sq, d = q.shape
    bkvh, sk, _ = k.shape
    group = bh // bkvh
    n_qb = pl.cdiv(sq, block_q)
    n_kb = pl.cdiv(sk, block_k)

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)            # (BH, Sq, 1)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k),
        out_shape=_out_struct((bh, sq, d), q.dtype, q, k, v, do),
        grid=(bh, n_qb, n_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv: one pass per kv head; the innermost grid dim walks every
    # (q head in the GQA group, q block) pair so the accumulators cover
    # the whole group.
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, n_qb=n_qb),
        out_shape=(
            _out_struct((bkvh, sk, d), k.dtype, q, k, v, do),
            _out_struct((bkvh, sk, d), v.dtype, q, k, v, do),
        ),
        grid=(bkvh, n_kb, group * n_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, t: (b * group + t // n_qb, t % n_qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, t: (b * group + t // n_qb, t % n_qb, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, t: (b * group + t // n_qb,
                                          t % n_qb, 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, t: (b * group + t // n_qb,
                                          t % n_qb, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """Flash attention. q: (B, Sq, H, D); k/v: (B, Sk, KVH, D)."""
    out, _ = _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k,
                             interpret)
    return out


def _pick_block(limit: int, s: int) -> Optional[int]:
    """Largest block <= limit that divides s and is a multiple of 8."""
    b = min(limit, s)
    b -= b % 8
    while b >= 8:
        if s % b == 0:
            return b
        b -= 8
    return None


def _resolve_blocks(sq, sk, block_q, block_k):
    bq = _pick_block(block_q, sq)
    bk = _pick_block(block_k, sk)
    if bq is None or bk is None:
        raise ValueError(
            f"flash_attention needs seq lengths with a divisor that is a "
            f"multiple of 8 (sq={sq}, sk={sk}); pad inputs or use "
            f"impl='xla'.")
    return bq, bk


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    _, sk, kvh, _ = k.shape
    scale_val = scale if scale is not None else d ** -0.5
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
    of, lse = _flash_forward(qf, kf, vf, causal, scale_val, bq, bk, interpret)
    out = of.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out, (qf, kf, vf, of, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret,
                    residuals, g):
    qf, kf, vf, of, lse = residuals
    bh, sq, d = qf.shape
    bkvh, sk, _ = kf.shape
    b, _, h, _ = g.shape
    kvh = bkvh // b
    scale_val = scale if scale is not None else d ** -0.5
    bq, bk = _resolve_blocks(sq, sk, block_q, block_k)
    gf = g.transpose(0, 2, 1, 3).reshape(bh, sq, d)
    dqf, dkf, dvf = _flash_backward(
        qf, kf, vf, of, lse, gf, causal, scale_val, bq, bk, interpret)
    dq = dqf.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    dk = dkf.reshape(b, kvh, sk, d).transpose(0, 2, 1, 3)
    dv = dvf.reshape(b, kvh, sk, d).transpose(0, 2, 1, 3)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# --------------------------------------------------------------------------
# Dispatcher
# --------------------------------------------------------------------------

def _mesh_specs(mesh: Mesh, head_axes):
    """(shard_map mesh, manual axis names, activation spec, lse spec,
    head split) for the flash kernel on a sharded program: batch
    over dp/fsdp, heads over `head_axes`, full sequence per shard.
    Axes an enclosing shard_map already made manual (the pipeline's
    pp) are left alone, and a nested shard_map takes the enclosing
    context's mesh (mesh=None) — only then does the Mosaic lowering
    see the outer manual axes too."""
    ctx = jax.sharding.get_abstract_mesh()
    manual = set(ctx.manual_axes)
    names = frozenset(mesh.axis_names) - manual
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch = tuple(a for a in BATCH_AXES if a in names) or None
    heads = tuple(a for a in head_axes if a in names) or None
    n_split = 1
    for a in heads or ():
        n_split *= sizes[a]
    return (None if manual else mesh, names,
            P(batch, None, heads, None), P(batch, heads, None, None),
            n_split)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_on_mesh(q, k, v, causal: bool, interpret: bool, mesh: Mesh,
                   head_axes):
    """The flash kernel under shard_map over the batch and head axes.

    A Mosaic kernel cannot be auto-partitioned ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a
    shard_map"), so wherever the caller's program is GSPMD-sharded the
    kernel runs per shard. Attention is independent per (batch row,
    head): no collectives inside. The custom_vjp sits OUTSIDE the
    shard_maps — forward and backward are each one shard_map with
    stated specs — because differentiating through a shard_map nested
    in the pipeline's pp shard_map hands the residuals back with pp
    ahead of the manual axes, which the partitioner refuses."""
    return _flash_on_mesh_fwd(q, k, v, causal, interpret, mesh,
                              head_axes)[0]


def _flash_on_mesh_fwd(q, k, v, causal, interpret, mesh, head_axes):
    sm_mesh, names, spec, lse_spec, _ = _mesh_specs(mesh, head_axes)

    def local(q_, k_, v_):
        out, (_, _, _, _, lse) = _flash_fwd_rule(
            q_, k_, v_, causal, None, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
            interpret)
        b, sq, hl, _ = q_.shape
        return out, lse.reshape(b, hl, sq, 1)

    out, lse = jax.shard_map(
        local, mesh=sm_mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, lse_spec), axis_names=names)(q, k, v)
    return out, (q, k, v, out, lse)


def _flash_on_mesh_bwd(causal, interpret, mesh, head_axes, res, g):
    q, k, v, out, lse = res
    sm_mesh, names, spec, lse_spec, _ = _mesh_specs(mesh, head_axes)

    def local(q_, k_, v_, o_, lse_, g_):
        b, sq, hl, d = q_.shape
        kvh, sk = k_.shape[2], k_.shape[1]
        flat = lambda x, n, s_: x.transpose(0, 2, 1, 3).reshape(
            b * n, s_, d)
        res_l = (flat(q_, hl, sq), flat(k_, kvh, sk), flat(v_, kvh, sk),
                 flat(o_, hl, sq), lse_.reshape(b * hl, sq, 1))
        return _flash_bwd_rule(causal, None, DEFAULT_BLOCK_Q,
                               DEFAULT_BLOCK_K, interpret, res_l, g_)

    dq, dk, dv = jax.shard_map(
        local, mesh=sm_mesh,
        in_specs=(spec, spec, spec, spec, lse_spec, spec),
        out_specs=(spec, spec, spec), axis_names=names)(
            q, k, v, out, lse, g)
    return dq, dk, dv


_flash_on_mesh.defvjp(_flash_on_mesh_fwd, _flash_on_mesh_bwd)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, impl: str = "auto",
              mesh: Optional[Mesh] = None,
              head_axes=(AXIS_TP,)) -> jax.Array:
    """Pick the best attention implementation for the current backend.

    mesh: the mesh the surrounding program is sharded over, if any —
    the flash kernel then runs under shard_map (`_flash_on_mesh`);
    the XLA reference partitions through GSPMD as is."""
    if impl == "auto":
        on_tpu = jax.devices()[0].platform == "tpu"
        sq, sk = q.shape[1], k.shape[1]
        bq = _pick_block(DEFAULT_BLOCK_Q, sq)
        bk = _pick_block(DEFAULT_BLOCK_K, sk)
        # tiny resolved blocks mean awkward seq lengths — XLA does better
        ok_shapes = (bq is not None and bk is not None and bq >= 128
                     and bk >= 128 and q.shape[-1] >= 64)
        impl = "pallas" if (on_tpu and ok_shapes) else "xla"
    if impl in ("pallas", "pallas_interpret"):
        interpret = impl == "pallas_interpret"
        if mesh is not None and mesh.devices.size > 1:
            if k.shape[2] % _mesh_specs(mesh, head_axes)[-1]:
                # too few kv heads to split: one per q head instead
                k, v = _repeat_kv(k, q.shape[2]), _repeat_kv(v, q.shape[2])
            return _flash_on_mesh(q, k, v, causal, interpret, mesh,
                                  tuple(head_axes))
        return flash_attention(q, k, v, causal, None, DEFAULT_BLOCK_Q,
                               DEFAULT_BLOCK_K, interpret)
    return reference_attention(q, k, v, causal=causal)
