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
    # "token": a page is [page, heads, width]. "rows": the same bytes
    # in the same order as [page * heads, width], one axis: for a number
    # of heads that is no multiple of the 8-row tile (10), which the
    # token layout pads to the next one in device memory (16)
    layout: str = "token"

    @property
    def bytes_per_token_layer(self) -> int:
        """Device bytes one token holds in one layer, every pool."""
        return self.pools * self.heads * (
            self.padded_width * int(np.dtype(self.dtype).itemsize)
            + self.scale_bytes)

    def pool_shape(self, layers: int, num_pages: int,
                   page_size: int) -> Tuple[int, ...]:
        """Shape of EACH of the `pools` pools."""
        if self.layout == "rows":
            return (layers, num_pages, page_size * self.heads,
                    self.padded_width)
        return (layers, num_pages, page_size, self.heads,
                self.padded_width)

    def describe(self) -> Dict[str, Any]:
        out = {"kind": self.kind, "pools": self.pools,
               "heads": self.heads, "width": self.width,
               "padded_width": self.padded_width,
               "dtype": np.dtype(self.dtype).name,
               "bytes_per_token_layer": self.bytes_per_token_layer}
        if self.layout != "token":
            out["layout"] = self.layout
        return out


@dataclasses.dataclass(frozen=True)
class StateRow:
    """What ONE SLOT holds in ONE layer of a state group: arrays of a
    fixed size that a recurrent layer reads at a row's first token of a
    tick and writes at its last, whatever the sequence's length. No row
    a token, no pages. `parts`: (name, shape a slot a layer, dtype)."""
    kind: str
    parts: Tuple[Tuple[str, Tuple[int, ...], Any], ...]

    @property
    def bytes_per_slot_layer(self) -> int:
        return sum(int(np.prod(shape)) * int(np.dtype(dt).itemsize)
                   for _, shape, dt in self.parts)

    def describe(self) -> Dict[str, Any]:
        return {"kind": self.kind,
                "parts": {name: {"shape": list(shape),
                                 "dtype": np.dtype(dt).name}
                          for name, shape, dt in self.parts},
                "bytes_per_slot_layer": self.bytes_per_slot_layer}


@dataclasses.dataclass(frozen=True)
class CacheGroup:
    """The layers of a model that write the same row under the same
    rule of what a query may still see. Each group has pools of its own
    `[its layers, its pages, page, heads, width]`, an allocator and a
    page table a slot (`kv_cache.CacheManager`).

    window None: a query sees its whole context and a sequence holds
    every page it wrote. window w: query i sees keys j with
    i - w < j <= i, and pages wholly behind the window of every query
    still to come go back to the group's allocator.

    readers: layers that write nothing and READ this group's pages (a
    cross-decoder over one layer's K and V): they hold no bytes and
    count in what a query's attention reads.

    state: a STATE group (`row` None): its layers keep `state` a SLOT,
    held from admission to vacate; it has arrays `[its layers, slots,
    ...]`, no allocator and no page table."""
    name: str
    row: Optional[CacheRow]
    layers: Tuple[int, ...]
    window: Optional[int] = None
    readers: Tuple[int, ...] = ()
    state: Optional[StateRow] = None

    def __post_init__(self):
        if (self.row is None) == (self.state is None):
            raise ValueError("a cache group has a row a token or a state "
                             "a slot, one of the two")
        if self.state is not None and (self.window is not None
                                       or self.readers):
            raise ValueError("a state group has no window and no readers")

    @property
    def kind(self) -> str:
        return "pages" if self.state is None else "state"

    @property
    def bytes_per_token(self) -> int:
        """Device bytes one token holds in this group's layers (a state
        group: none)."""
        if self.state is not None:
            return 0
        return len(self.layers) * self.row.bytes_per_token_layer

    @property
    def bytes_per_slot(self) -> int:
        """Device bytes one slot holds of a state group, whatever its
        sequence's length (a page group: none)."""
        if self.state is None:
            return 0
        return len(self.layers) * self.state.bytes_per_slot_layer

    @property
    def read_bytes_per_token(self) -> int:
        """Bytes a query's attention reads of one cached token: the
        writing layers' rows and the same row again for each reader."""
        if self.state is not None:
            return 0
        return ((len(self.layers) + len(self.readers))
                * self.row.bytes_per_token_layer)

    def array_shapes(self, num_pages: int, page_size: int, n_slots: int
                     ) -> Tuple[Tuple[Tuple[int, ...], Any], ...]:
        """(shape, dtype) of each device array the engine keeps for this
        group: a page group's pools, a state group's parts."""
        if self.state is not None:
            return tuple(((len(self.layers), n_slots) + tuple(shape), dt)
                         for _, shape, dt in self.state.parts)
        shape = self.row.pool_shape(len(self.layers), num_pages, page_size)
        return ((shape, self.row.dtype),) * self.row.pools

    def describe(self) -> Dict[str, Any]:
        if self.state is not None:
            return {"name": self.name, "kind": "state",
                    "state": self.state.describe(),
                    "layers": list(self.layers), "window": None,
                    "bytes_per_slot": self.bytes_per_slot}
        out = {"name": self.name, "row": self.row.describe(),
               "layers": list(self.layers), "window": self.window}
        if self.readers:
            out["readers"] = list(self.readers)
        return out
