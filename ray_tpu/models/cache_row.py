"""What one token writes to the cache in one layer: the description a
model family hands the serving engine (`models/family.py`). It lives on
the model's side of the seam and imports nothing of the engine."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class CacheRow:
    """What ONE token writes to the cache in ONE layer, and how the pool
    lays it out: the one description that the engine's pool
    construction, its per-page byte reckoning, `stats()` and
    `perfmodel.CostModel` read. A model family gives it
    (`models/family.py`); nothing else knows a row's shape.

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
