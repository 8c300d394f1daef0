"""What more than one model family's serving forwards use: rotate-half
rope, SwiGLU, the window layers' and the recurrent layers' dispatch-span
counts, the refusal of
the dense family's arguments, attention over a tick's page groups, the
Mamba-2 mixer of the two families that have one, the decode step as the
ragged tick of one token a slot, the write of a
tick's rows into a group's pool (one function a pool layout), and what
`stats()` shows of held experts' counts.

Imports `ops/` and `cache_row`'s neighbours only, never a family's
module: a family module imports from here (and `llama` for `rms_norm`),
never from another family (`tests/test_model_imports.py`).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops import ragged_paged_attention as rpa
from ..ops import selective_scan as ssm
from ..ops import ssd_scan
from ..ops.paged_attention import _fit_lanes

# a layer's kind in a stack of sliding-window and full-attention layers
SLIDING, FULL = "sliding_attention", "full_attention"


def refuse(label: str, **given) -> None:
    """The engine hands every family's forwards the dense family's
    arguments; one that is set is refused by name."""
    for name, value in given.items():
        if value is not None and value != "f32":
            raise ValueError(f"the {label} forwards take no {name}")


def rope_cos_sin(cfg, positions: jax.Array):
    """positions [T] -> cos, sin [T, head_dim / 2] float32, from
    `cfg.head_dim` and `cfg.rope_theta` alone."""
    d = cfg.head_dim
    inv = 1.0 / cfg.rope_theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [T, ..., d] rotate-half; cos/sin: [T, d/2]."""
    d = x.shape[-1]
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    x1 = x[..., :d // 2].astype(jnp.float32)
    x2 = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def swiglu(w, y):
    return (jax.nn.silu(y @ w["wg"]) * (y @ w["wi"])) @ w["wd"]


def window_span_counts(cfg, segs, decode) -> Dict[str, int]:
    """What the dispatch span carries of a tick's window layers, from
    the plan and `cfg.sliding_window` alone: `segs` = [(cached tokens,
    tokens this tick)] a row, `decode` = which rows are decode rows.
    `win_kv_tokens`: the keys inside their windows that the rows read
    (each row's union over its queries); `win_attn_pairs`: the (query,
    key) pairs a window layer keeps; `win_decode_pairs`: the decode
    rows' part of them."""
    w = cfg.sliding_window
    kv = pairs = dec = 0
    for (pos0, n), is_dec in zip(segs, decode):
        kv += min(pos0 + n, n + w - 1)
        # query i of the row keeps min(pos0 + i + 1, w) keys
        full = max(min(w - pos0, n), 0)          # queries not yet cut
        kept = (full * pos0 + full * (full + 1) // 2) + (n - full) * w
        pairs += kept
        if is_dec:
            dec += kept
    return {"win_kv_tokens": kv, "win_attn_pairs": pairs,
            "win_decode_pairs": dec}


def attend_fn(impl: str, pools, tables, slot_ids, positions, valid, start,
              ctx_pages: int, *, merged_rows: bool):
    """attend(q [T, heads, d], k, v [T, kv heads, d], group, index in
    the group, window or None) -> o for one set of queries against group
    `group`'s pools (`pools[group]` = (K pool, V pool), `tables[group]`
    its page table) and the queries' own k and v: the work-list kernels
    (`ragged_paged_attention`, `ragged_window_attention` with a window),
    or the dense gather, as `impl` says. The work list is built once for
    every layer; a kernel gets a group's pools WHOLE, flattened over
    layers, and the table shifted to the layer's pages (a pool sliced by
    layer is copied first). `merged_rows`: the pools' layout,
    `CacheRow.layout` "rows"."""
    if impl in ("pallas", "pallas_interpret"):
        work = rpa.ragged_work_list(slot_ids, valid, start,
                                    rpa.ragged_q_block(slot_ids.shape[0]))

        def attend(q, k, v, g, gi, window):
            kp, vp = pools[g]
            flat = lambda pool: pool.reshape((-1,) + pool.shape[2:])
            return rpa.ragged_paged_attention_pallas(
                q, flat(kp), flat(vp), tables[g] + gi * kp.shape[1],
                slot_ids, positions, valid, start, k, v,
                ctx_pages=ctx_pages, work=work, window=window,
                interpret=(impl == "pallas_interpret"),
                merged_rows=merged_rows)
    else:
        def attend(q, k, v, g, gi, window):
            kp, vp = pools[g]
            tab = tables[g] if ctx_pages < 0 else tables[g][:, :ctx_pages]
            return rpa.ragged_gather_paged_blocked(
                q, kp, vp, gi, tab, slot_ids, positions, valid, start,
                k, v, window=window, merged_rows=merged_rows)
    return attend


def gated_group_norm(cfg, y: jax.Array, z: jax.Array,
                     weight: jax.Array) -> jax.Array:
    """y, z: [T, d_inner] -> the gate FIRST (y silu(z)), then RMSNorm
    over each of the `cfg.n_groups` groups of channels separately (one
    group: over them all), times the weight; float32 inside, `cfg.dtype`
    out."""
    f32 = jnp.float32
    t = y.shape[0]
    g = (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(
        t, cfg.n_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + cfg.norm_eps)
    return (g.reshape(t, -1) * weight).astype(cfg.dtype)


def mamba2_mixer(cfg, layer, u: jax.Array, marks, tick,
                 conv_all: jax.Array, ssm_all: jax.Array, gi, impl: str,
                 conv_scope: str = "conv"):
    """A Mamba-2 (SSD) mixer on a ragged tick. u: [T, H] normalised ->
    (the mixer's output [T, H], the conv inputs and the scan state with
    layer `gi`'s rows of this tick's slots replaced). `cfg` names the
    sizes as `NemotronHConfig` does (`d_inner`, `conv_dim`, `d_conv`,
    `mamba_heads`, `mamba_head_dim`, `n_groups`, `ssm_state`); `layer`
    holds in_proj, conv_w, conv_b, dt_bias, a_log, d_skip, norm,
    out_proj. Delta is not clamped."""
    slot_ids, valid, last_idx = tick
    t, b = u.shape[0], conv_all.shape[1]
    e, k, hm = cfg.d_inner, cfg.d_conv, cfg.mamba_heads
    gn = cfg.n_groups * cfg.ssm_state
    z, xbc, dt = jnp.split(u @ layer["in_proj"], [e, e + cfg.conv_dim],
                           axis=-1)
    with jax.named_scope(conv_scope):
        stored = jax.lax.dynamic_index_in_dim(conv_all, gi, 0, False)
        xc, conv_new = ssm.causal_conv_ragged(
            xbc, layer["conv_w"], layer["conv_b"], slot_ids, last_idx,
            marks, stored.reshape(b, k - 1, cfg.conv_dim))
        conv_all = jax.lax.dynamic_update_index_in_dim(
            conv_all, conv_new.reshape(b, -1), gi, 0)
        xbc = jax.nn.silu(xc).astype(cfg.dtype)
    x, bm, cm = jnp.split(xbc, [e, e + gn], axis=-1)
    delta = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
    with jax.named_scope("ssd_scan"):
        y, ssm_all = ssd_scan.ssd_ragged_scan(
            x.reshape(t, hm, cfg.mamba_head_dim), delta,
            -jnp.exp(layer["a_log"]),
            bm.reshape(t, cfg.n_groups, cfg.ssm_state),
            cm.reshape(t, cfg.n_groups, cfg.ssm_state), layer["d_skip"],
            marks, slot_ids, valid, last_idx, ssm_all, gi, impl=impl)
    y = gated_group_norm(cfg, y.reshape(t, e), z, layer["norm"])
    return y @ layer["out_proj"], conv_all, ssm_all


def state_span_counts(cfg, segs, decode) -> Dict[str, int]:
    """What the dispatch span carries of a tick's recurrent layers, from
    the plan (`segs` = [(cached tokens, tokens this tick)] a row):
    `ssm_tokens`, the tokens through each such layer's scan, and
    `ssm_rows`, the rows whose state a layer reads and writes."""
    del cfg, decode
    return {"ssm_tokens": sum(n for _, n in segs), "ssm_rows": len(segs)}


def one_token_tick(ragged_forward):
    """The `decode_step` of every family: its decode tick IS its ragged
    tick of one token a slot (slot b's token at positions[b], inactive
    slots invalid: they write nothing and their state is left alone),
    through the same attention, experts and scans, so that ONE kernel
    knows a window and a decode row costs the keys it may see. Keyword
    arguments (impl, mesh, lora, lora_idx, the quantized pools' kv_kind
    and scales, the dense family's explicit-tp pair) go to
    `ragged_forward` as they come; returns what it returns."""
    def decode_step(cfg, params: Dict[str, Any], tokens: jax.Array,
                    positions: jax.Array, k_pages, v_pages, page_tables,
                    active: jax.Array, **kw):
        slots = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        return ragged_forward(
            cfg, params, tokens, slots, positions, active, positions, slots,
            k_pages, v_pages, page_tables, ctx_pages=-1, **kw)
    return decode_step


def scatter_token_rows(pool: jax.Array, rows: jax.Array,
                       page_tables: jax.Array, positions: jax.Array,
                       valid: jax.Array) -> jax.Array:
    """Write a tick's rows into one group's TOKEN-layout pool. pool: [L,
    P, page, kv heads, Dp]; rows: [L, N, kv heads, d]; each token's OWN
    table in page_tables [N, max_pages]; invalid rows go to the scratch
    page. One scatter with one index dim over the pool flattened to
    [L * P * page, kv heads, Dp] (`mla_attention.scatter_latent`)."""
    l, num_pages, page, kvh, w = pool.shape
    page_idx = jnp.take_along_axis(
        page_tables, (positions // page)[:, None], axis=1)[:, 0]
    page_idx = jnp.where(valid, page_idx, num_pages - 1)
    at = page_idx * page + positions % page                       # [N]
    at = (jnp.arange(l, dtype=at.dtype)[:, None] * (num_pages * page)
          + at[None, :]).reshape(-1)                              # [L*N]
    new = _fit_lanes(rows, w).reshape(-1, kvh, w).astype(pool.dtype)
    return pool.reshape(-1, kvh, w).at[at].set(new).reshape(pool.shape)


def scatter_merged_rows(pool: jax.Array, rows: jax.Array,
                        page_tables: jax.Array, positions: jax.Array,
                        valid: jax.Array) -> jax.Array:
    """Write a tick's rows into one group's MERGED-ROWS pool. pool: [L,
    P, page * heads, Dp]; rows: [L, N, heads, d]; each token's OWN table
    in page_tables [N, max_pages]; invalid rows go to the scratch page
    (`num_pages - 1`), where they may collide.

    ONE scatter of SINGLE Dp-lane rows, L * N * heads of them, into the
    pool flattened to [L * P * page * heads, Dp], under scope
    `kv_write`: XLA:TPU runs it as one native `scatter` on the donated
    pool. Single rows and not a token's [heads, Dp] WINDOW: a scatter
    of windows it runs as a serial `while` of one
    `dynamic-update-slice` a (layer, token), 3.7 us a trip, 17 ms for
    the K rows of a 512-token tick in `smallthinker-assist`'s window
    group (PERF.md section 6, PR 46). The rows a token (10, 4, 2) and
    the layers a call are the arguments' shapes: one path for every
    merged-rows family. A valid token's page comes from its own table
    and its row from `positions % page`, so no index is past the end;
    `mode="clip"` only keeps the compiler from assuming it."""
    l, num_pages, per_page, w = pool.shape
    kvh = rows.shape[2]
    page = per_page // kvh
    with jax.named_scope("kv_write"):
        page_idx = jnp.take_along_axis(
            page_tables, (positions // page)[:, None], axis=1)[:, 0]
        page_idx = jnp.where(valid, page_idx, num_pages - 1)
        at = (page_idx * page + positions % page) * kvh           # [N]
        at = (jnp.arange(l, dtype=at.dtype)[:, None, None]
              * (num_pages * per_page) + at[None, :, None]
              + jnp.arange(kvh, dtype=at.dtype))             # [L, N, heads]
        new = _fit_lanes(rows, w).reshape(-1, w).astype(pool.dtype)
        return pool.reshape(-1, w).at[at.reshape(-1)].set(
            new, mode="clip").reshape(pool.shape)


def held_rider_len(cfg) -> int:
    """Ints a tick's program appends to its token readback in a family
    with held experts: the assignments landed, [n_moe_layers, n_held]."""
    return cfg.n_moe_layers * cfg.n_held


def routing_summary(cfg, landed, tokens_routed: int) -> Dict[str, Any]:
    """What `stats()["moe"]` shows of the forwards' expert counts summed
    since start-up (`landed`: n_moe_layers * n_held ints): tokens routed
    (each through every expert layer), assignments that landed on the
    experts held here by layer and expert, how many held experts
    received any, and the busiest one's load over the mean load of a
    held expert."""
    landed = landed.reshape(cfg.n_moe_layers, cfg.n_held)
    total = int(landed.sum())
    mean = total / max(landed.size, 1)
    return {
        "experts_held": list(cfg.held),
        "expert_layers": cfg.n_moe_layers,
        "tokens_routed": tokens_routed,
        "assignments_landed": total,
        "experts_with_tokens": int((landed != 0).sum()),
        "busiest_over_mean": (round(float(landed.max()) / mean, 4)
                              if total else 0.0),
        "landed": landed.tolist(),
    }


def latent_attend_fn(impl: str, pool: jax.Array, page_tables: jax.Array,
                     slot_ids: jax.Array, positions: jax.Array,
                     valid: jax.Array, start: jax.Array, ctx_pages: int, *,
                     heads: int, width: int, dv: int, scale: float):
    """attend(q, rows, index in the group) -> o_lat [T, heads, dv] for
    one tick of a LATENT group (`ops/mla_attention.py`): absorbed
    queries [T, `heads`, width] against the pool's cached rows of that
    layer of the group and the tick's own `rows` [T, width], by the
    kernel or by the dense gather as `impl` says. The kernel's work list
    is built once here, for every layer; the pool goes in whole and the
    layer as an index (a pool sliced by layer is copied first)."""
    from ..ops import mla_attention as mla_ops
    if impl in ("pallas", "pallas_interpret"):
        work = mla_ops.mla_work_list(slot_ids, valid, start, heads)

        def attend(q, rows, gi):
            return mla_ops.mla_ragged_attention_pallas(
                q, pool, gi, page_tables, slot_ids, positions, valid,
                start, rows, dv=dv, scale=scale, ctx_pages=ctx_pages,
                work=work, interpret=(impl == "pallas_interpret"))
    else:
        tables = (page_tables if ctx_pages < 0
                  else page_tables[:, :ctx_pages])

        def attend(q, rows, gi):
            return mla_ops.mla_attention_gather_paged(
                q, pool, gi, tables, rows, slot_ids, positions, valid,
                start, width=width, dv=dv, scale=scale)
    return attend
