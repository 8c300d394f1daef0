"""What one token writes to the cache in one layer, and which layers
write alike: the description a model family hands the serving engine
(`models/family.py`). It lives on the model's side of the seam and
imports nothing of the engine."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class CacheRow:
    """What ONE token writes to the cache in ONE layer, and how the pool
    lays it out. A model's cache is one or more `CacheGroup`s of layers,
    each with a row: since PR 31 the groups, not one row for every
    layer, are the description that the engine's pool construction, its
    per-page byte reckoning, `stats()` and `perfmodel.CostModel` read.
    A model family gives them (`models/family.py`); nothing else knows a
    row's shape.

    kind "kv": a K pool and a V pool, `heads` kv heads of `width`
    values each (a dense GQA decoder). kind "latent": ONE pool of one
    head whose row is `[c_kv | k_pe]`; its first `value_width` lanes
    are also the values (multi-head latent attention).

    `padded_width` is the pool's minor dim: `width` as the model writes
    it for the gather impl, padded to whole 128-lane vectors for the
    kernels. `scale_bytes` is a quantized pool's per-(row, head)
    sidecar."""
    kind: str
    pools: int
    heads: int
    width: int
    padded_width: int
    dtype: Any
    scale_bytes: int = 0
    value_width: Optional[int] = None

    @property
    def bytes_per_token_layer(self) -> int:
        """Device bytes one token holds in one layer, every pool."""
        return self.pools * self.heads * (
            self.padded_width * int(np.dtype(self.dtype).itemsize)
            + self.scale_bytes)

    def pool_shape(self, layers: int, num_pages: int,
                   page_size: int) -> Tuple[int, ...]:
        """Shape of EACH of the `pools` pools."""
        return (layers, num_pages, page_size, self.heads,
                self.padded_width)

    def describe(self) -> Dict[str, Any]:
        return {"kind": self.kind, "pools": self.pools,
                "heads": self.heads, "width": self.width,
                "padded_width": self.padded_width,
                "dtype": np.dtype(self.dtype).name,
                "bytes_per_token_layer": self.bytes_per_token_layer}


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The layers of a model that write the same row under the same
    rule of what a query may still see. Each group has pools of its own
    `[its layers, its pages, page, heads, width]`, an allocator and a
    page table a slot (`kv_cache.CacheManager`).

    window None: a query sees its whole context and a sequence holds
    every page it wrote. window w: query i sees keys j with
    i - w < j <= i, and pages wholly behind the window of every query
    still to come go back to the group's allocator."""
    name: str
    row: CacheRow
    layers: Tuple[int, ...]
    window: Optional[int] = None

    @property
    def bytes_per_token(self) -> int:
        """Device bytes one token holds in this group's layers."""
        return len(self.layers) * self.row.bytes_per_token_layer

    def describe(self) -> Dict[str, Any]:
        return {"name": self.name, "row": self.row.describe(),
                "layers": list(self.layers), "window": self.window}
