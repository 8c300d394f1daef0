"""The seam between the serving engine and a model family.

The engine asks a family, not a model file, for what it needs: the
parameter tree and its initialiser from a seed, the ragged forward and
the decode forward its two programs call, the cache row a token writes
(`cache_row.CacheRow`), the attention kernel's host-side work count for
the dispatch span, what a tick's readback carries besides tokens and
how its totals read in `stats()`, and the engine options the family
does not compose with (refused at construction with the reason, never
half-run). Nothing here imports the engine: the engine imports this.

Two families: `llama` (dense RoPE/GQA/SwiGLU decoders, `LlamaConfig`,
programs unchanged) and `deepseek_v3` (latent attention over a latent
cache, expert layers with the experts held here, `DeepseekV3Config`).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

from .cache_row import CacheRow


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    init_params: Callable[..., Dict[str, Any]]
    ragged_forward: Callable[..., tuple]
    decode_step: Callable[..., tuple]
    # (cfg, impl, kv_kind) -> CacheRow
    cache_row: Callable[..., CacheRow]
    # (segs, T, page_size, n_ctx_pages, geometry) -> (live items, KV
    # blocks they visit): the attention kernel's grid for a tick whose
    # rows hold `segs` = [(cached tokens, tokens this tick)]; geometry
    # is the engine's (local kv heads, pool row width, itemsize)
    work_counts: Callable[..., Tuple[int, int]]
    # (cfg) -> ints a tick's program appends to its token readback
    # (0: the readback is the sampled tokens alone)
    rider_len: Callable[[Any], int] = lambda cfg: 0
    # (cfg, the riders summed since start-up, tokens the ticks carried)
    # -> what stats() shows of them; None for a family with no rider
    rider_summary: Optional[Callable[..., Dict[str, Any]]] = None
    # engine options this family does not compose with: name -> reason
    refuses: Dict[str, str] = dataclasses.field(default_factory=dict)


def _llama_cache_row(cfg, impl: str, kv_kind: str = "f32") -> CacheRow:
    from ..ops import kv_quant
    from ..ops.paged_attention import pool_head_dim
    quant = kv_kind != "f32"
    return CacheRow(
        kind="kv", pools=2, heads=cfg.n_kv_heads, width=cfg.head_dim,
        padded_width=pool_head_dim(cfg.head_dim, impl),
        dtype=kv_quant.storage_dtype(kv_kind) if quant else cfg.dtype,
        scale_bytes=kv_quant.SCALE_BYTES if quant else 0)


def _deepseek_cache_row(cfg, impl: str, kv_kind: str = "f32") -> CacheRow:
    from ..ops.mla_attention import latent_row_width
    if kv_kind != "f32":
        raise ValueError(DEEPSEEK_REFUSES["kv_dtype"])
    return CacheRow(
        kind="latent", pools=1, heads=1, width=cfg.latent_width,
        padded_width=latent_row_width(cfg.kv_lora_rank,
                                      cfg.qk_rope_head_dim, impl),
        dtype=cfg.dtype, value_width=cfg.kv_lora_rank)


def _llama_work_counts(segs, t, page_size, n_ctx_pages, geometry):
    from ..ops.ragged_paged_attention import ragged_work_counts
    return ragged_work_counts(segs, t, page_size, n_ctx_pages, *geometry)


def _deepseek_work_counts(segs, t, page_size, n_ctx_pages, geometry):
    from ..ops.mla_attention import mla_work_counts
    return mla_work_counts(segs, t, page_size, n_ctx_pages)


DEEPSEEK_REFUSES = {
    "lora": "LoRA adapters hook the dense family's wq/wk/wv/wo "
            "projections; latent attention has none of them",
    "kv_dtype": "int8/fp8 KV pages keep per-(row, kv head) scales for a "
                "K pool and a V pool; the latent pool has one row that "
                "is both, and no quantized write or read path",
    "enable_kv_offload": "the host KV tier spills and restores K and V "
                         "pages; the latent pool is one pool",
    "mesh": "GSPMD tensor parallelism shards heads and kv heads; the "
            "latent cache has one head, and the expert layer has no "
            "exchange across chips",
    "mesh_shape": "the explicit-tp shard_map programs are the dense "
                  "family's (Megatron layout of wq/wk/wv/wo)",
    "checkpoint": "no checkpoint loader for this family's tree yet",
    "session_shipping": "session and prefix export/import move K and V "
                        "pages; the latent pool is one pool",
}


@functools.lru_cache(maxsize=None)
def _families() -> Dict[type, ModelFamily]:
    """Configuration type -> its family (built on first use: the model
    modules import jax)."""
    from . import deepseek_v3, llama, llama_infer
    return {
        llama.LlamaConfig: ModelFamily(
            name="llama", init_params=llama.init_params,
            ragged_forward=llama_infer.ragged_forward,
            decode_step=llama_infer.decode_step,
            cache_row=_llama_cache_row, work_counts=_llama_work_counts),
        deepseek_v3.DeepseekV3Config: ModelFamily(
            name="deepseek_v3", init_params=deepseek_v3.init_params,
            ragged_forward=deepseek_v3.ragged_forward,
            decode_step=deepseek_v3.decode_step,
            cache_row=_deepseek_cache_row,
            work_counts=_deepseek_work_counts,
            rider_len=lambda c: c.n_moe_layers * c.n_held,
            rider_summary=deepseek_v3.routing_summary,
            refuses=DEEPSEEK_REFUSES),
    }


def family_of(cfg) -> ModelFamily:
    """The family that serves `cfg` (a LlamaConfig or a
    DeepseekV3Config)."""
    for kind, family in _families().items():
        if isinstance(cfg, kind):
            return family
    raise TypeError(f"no model family serves a {type(cfg).__name__}")


def resolve_config(model):
    """A preset name or a family's configuration -> the configuration.
    Names are the dense family's presets, or `deepseek_v3:<preset>`."""
    from . import deepseek_v3, llama
    if isinstance(model, deepseek_v3.DeepseekV3Config):
        return model
    if isinstance(model, str) and model.startswith("deepseek_v3:"):
        return deepseek_v3.config(model.split(":", 1)[1])
    return llama.config(model)
