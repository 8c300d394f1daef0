"""The seam between the serving engine and a model family.

The engine asks a family, not a model file, for what it needs: the
parameter tree, its initialiser from a seed and the type each leaf is
stored in (`store_params`), the ragged forward and
the decode forward its two programs call, the cache row a token writes
(`cache_row.CacheRow`), the attention kernel's host-side work count for
the dispatch span, what a tick's readback carries besides tokens and
how its totals read in `stats()`, and the engine options the family
does not compose with (refused at construction with the reason, never
half-run). Nothing here imports the engine: the engine imports this.

The families are the entries of `FAMILIES`; each module's own docstring
describes its model.

A family with a STATE group (`cache_row.CacheGroup.state`) gets that
group's arrays in `k_pages` / `v_pages` behind its page groups' pools
(its first part / its second) and returns them in the same places; the
forwards of a family without one take and return what they always did.
A ONE-pool page group (a latent row) among several groups has None as
its `v_pages` entry, as a one-group latent family has None for the whole.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

from .cache_row import CacheGroup, CacheRow


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    init_params: Callable[..., Dict[str, Any]]
    ragged_forward: Callable[..., tuple]
    decode_step: Callable[..., tuple]
    # (cfg, impl, kv_kind) -> the cache groups, in the order the
    # forwards take their pools and page tables. One group with every
    # layer and no window: the forwards take one pool (or a K and a V
    # pool) and one table, as arrays; more: tuples of them, a group each
    cache_groups: Callable[..., Tuple[CacheGroup, ...]]
    # (segs, T, page_size, n_ctx_pages, geometry) -> (live items, KV
    # blocks they visit): the attention kernel's grid for a tick whose
    # rows hold `segs` = [(cached tokens, tokens this tick)]; geometry
    # is the engine's (local kv heads, pool row width, itemsize)
    work_counts: Callable[..., Tuple[int, int]]
    # (cfg, segs) -> ints the dispatch span carries besides the usual
    # ones, counted on the host from the plan (a family with window
    # layers: the keys and pairs inside their windows)
    span_counts: Optional[Callable[..., Dict[str, int]]] = None
    # (cfg) -> ints a tick's program appends to its token readback
    # (0: the readback is the sampled tokens alone)
    rider_len: Callable[[Any], int] = lambda cfg: 0
    # (cfg, the riders summed since start-up, tokens the ticks carried)
    # -> what stats() shows of them; None for a family with no rider
    rider_summary: Optional[Callable[..., Dict[str, Any]]] = None
    # (cfg) -> the type each leaf is STORED in for serving, a tree of
    # dtypes shaped like init_params': the type the tick's programs use
    # the leaf in, so that they convert no weight. None: kept as given
    storage_dtypes: Optional[Callable[[Any], Dict[str, Any]]] = None
    # engine options this family does not compose with: name -> reason
    refuses: Dict[str, str] = dataclasses.field(default_factory=dict)

    def cache_row(self, cfg, impl: str, kv_kind: str = "f32") -> CacheRow:
        """The FIRST group's row, kept for its readers; `cache_groups`
        is the description since PR 31."""
        return self.cache_groups(cfg, impl, kv_kind)[0].row


def one_group(row: CacheRow, n_layers: int) -> Tuple[CacheGroup, ...]:
    """Every layer writes `row` and sees its whole context."""
    return (CacheGroup("all", row, tuple(range(n_layers))),)


def _llama_cache_groups(cfg, impl: str, kv_kind: str = "f32"):
    return one_group(_llama_cache_row(cfg, impl, kv_kind), cfg.n_layers)


def _deepseek_cache_groups(cfg, impl: str, kv_kind: str = "f32"):
    return one_group(_deepseek_cache_row(cfg, impl, kv_kind),
                     cfg.n_layers)


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


# family name -> (its module under `models/`, its configuration type
# there): the ONE place the families are listed. `family_of`,
# `resolve_config` and `_families` read it, so a new family is an entry
# here and its `ModelFamily` in `_families`
FAMILIES: Dict[str, Tuple[str, str]] = {
    "llama": ("llama", "LlamaConfig"),
    "deepseek_v3": ("deepseek_v3", "DeepseekV3Config"),
    "trinity": ("trinity", "TrinityConfig"),
    "phi4flash": ("phi4flash", "Phi4FlashConfig"),
    "nemotron_h": ("nemotron_h", "NemotronHConfig"),
    "smallthinker": ("smallthinker", "SmallThinkerConfig"),
    "kimi_linear": ("kimi_linear", "KimiLinearConfig"),
    "granite_hybrid": ("granite_hybrid", "GraniteHybridConfig"),
}


@functools.lru_cache(maxsize=None)
def _modules() -> Dict[str, Any]:
    """Family name -> its module (imported on first use: the model
    modules import jax)."""
    return {name: importlib.import_module(f".{module}", __package__)
            for name, (module, _) in FAMILIES.items()}


def _from_module(name: str, module, **parts) -> ModelFamily:
    """The family of a module that names its parts as the later families'
    modules do; `parts` are the ones it names otherwise or has besides.
    `work_counts` is the dense family's unless given: the full layers'
    (or the one attention kind's) kernel is the dense family's."""
    for part in ("init_params", "ragged_forward", "decode_step",
                 "cache_groups"):
        if part not in parts:
            parts[part] = getattr(module, part)
    parts.setdefault("work_counts", _llama_work_counts)
    return ModelFamily(name=name, **parts)


@functools.lru_cache(maxsize=None)
def _families() -> Dict[type, ModelFamily]:
    """Configuration type -> its family."""
    from . import llama_infer, paged_common
    m = _modules()
    trinity, phi4flash = m["trinity"], m["phi4flash"]
    nemotron_h, smallthinker = m["nemotron_h"], m["smallthinker"]
    kimi_linear, granite_hybrid = m["kimi_linear"], m["granite_hybrid"]
    # a family with held experts: the counts of the assignments landed
    # ride the readback, [n_moe_layers, n_held], and are summed alike
    held = dict(rider_len=paged_common.held_rider_len,
                rider_summary=paged_common.routing_summary)
    families = (
        _from_module(
            "llama", m["llama"],
            ragged_forward=llama_infer.ragged_forward,
            decode_step=llama_infer.decode_step,
            cache_groups=_llama_cache_groups,
            storage_dtypes=llama_infer.storage_dtypes),
        _from_module(
            "deepseek_v3", m["deepseek_v3"],
            cache_groups=_deepseek_cache_groups,
            work_counts=_deepseek_work_counts,
            refuses=DEEPSEEK_REFUSES, **held),
        _from_module(
            "trinity", trinity, span_counts=trinity.span_counts,
            refuses=trinity.TRINITY_REFUSES, **held),
        _from_module(
            "phi4flash", phi4flash, init_params=phi4flash.init_stacked,
            span_counts=phi4flash.span_counts,
            storage_dtypes=phi4flash.storage_dtypes,
            refuses=phi4flash.PHI4FLASH_REFUSES),
        _from_module(
            "nemotron_h", nemotron_h, span_counts=nemotron_h.span_counts,
            storage_dtypes=nemotron_h.storage_dtypes,
            refuses=nemotron_h.NEMOTRON_H_REFUSES, **held),
        _from_module(
            "smallthinker", smallthinker,
            span_counts=smallthinker.span_counts,
            refuses=smallthinker.SMALLTHINKER_REFUSES, **held),
        _from_module(
            "kimi_linear", kimi_linear,
            work_counts=kimi_linear.work_counts,
            span_counts=kimi_linear.span_counts,
            storage_dtypes=kimi_linear.storage_dtypes,
            refuses=kimi_linear.KIMI_LINEAR_REFUSES, **held),
        _from_module(
            "granite_hybrid", granite_hybrid,
            span_counts=granite_hybrid.span_counts,
            storage_dtypes=granite_hybrid.storage_dtypes,
            refuses=granite_hybrid.GRANITE_HYBRID_REFUSES),
    )
    return {getattr(m[f.name], FAMILIES[f.name][1]): f for f in families}


def store_params(family: ModelFamily, cfg, params, shardings=None,
                 release: bool = False):
    """`params` as the engine keeps them: each leaf placed (under its
    entry of `shardings`, else on the default device) and in the
    family's storage type, cast ONCE here and not in every tick. Leaves
    go one at a time, smallest first, so what is held while loading is
    the tree that came in plus one leaf, never a second whole tree.
    release: the caller hands its buffers over (the engine's own draw
    from the seed): each wide leaf is deleted as its narrow copy exists.
    A leaf already placed and in its type is kept as it is. Also runs
    under jit (the born-sharded init), where it is the casts alone."""
    import jax
    leaves, treedef = jax.tree.flatten(params)
    dtypes = (treedef.flatten_up_to(family.storage_dtypes(cfg))
              if family.storage_dtypes is not None
              else [leaf.dtype for leaf in leaves])
    places = (treedef.flatten_up_to(shardings) if shardings is not None
              else [None] * len(leaves))
    for i in sorted(range(len(leaves)), key=lambda i: leaves[i].size):
        wide = jax.device_put(leaves[i], places[i])  # jaxlint: disable=JL006 -- load time, once: a leaf is placed, cast and let go before the next so that no second whole tree is ever held
        if wide.dtype == dtypes[i]:
            leaves[i] = wide
            continue
        leaves[i] = wide.astype(dtypes[i])
        if release:
            leaves[i].block_until_ready()
            wide.delete()
        # a placed copy made here goes before the next leaf's is made
        del wide
    return treedef.unflatten(leaves)


def family_of(cfg) -> ModelFamily:
    """The family that serves `cfg`, a configuration of one of
    `FAMILIES`' types."""
    for kind, family in _families().items():
        if isinstance(cfg, kind):
            return family
    raise TypeError(f"no model family serves a {type(cfg).__name__}")


def resolve_config(model):
    """A family's configuration, `<family>:<preset>` or a preset name of
    the dense family's -> the configuration."""
    if isinstance(model, tuple(_families())):
        return model
    if isinstance(model, str) and ":" in model:
        family, preset = model.split(":", 1)
        if family in FAMILIES:
            return _modules()[family].config(preset)
    return _modules()["llama"].config(model)
