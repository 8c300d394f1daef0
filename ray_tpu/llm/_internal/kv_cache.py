"""Host-side page allocator for the paged KV cache.

Reference parity: vLLM's BlockManager role (external to the reference —
net-new here; SURVEY.md §7 step 10). Pages are allocated worst-case at
admission (prompt + max_new_tokens) so a running sequence can never hit
cache OOM mid-decode — admission control is the backpressure point.

Prefix caching (SURVEY §7 hard part 1): full prompt pages are
hash-consed — a page's key is the chain (parent_key, its page_size
tokens), so two requests sharing a prompt prefix share the KV pages and
the second prefill starts where the match ends. Shared pages are
refcounted; only FULL pages are ever shared, so the write path (decode
scatters, partial-page prefill) always lands in private pages and no
copy-on-write is needed. Cached-but-unreferenced pages stay resident
and are evicted LRU only under allocation pressure.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# what a token writes to the cache in a layer, and which layers write
# alike: the model's side of the seam describes it, the engine's pools
# are built from it
from ...models.cache_row import CacheGroup, CacheRow  # noqa: F401


class PageAllocator:
    def __init__(self, num_pages: int, page_size: int,
                 enable_prefix_caching: bool = True):
        # last page is the scratch page scatter_kv() uses for masked rows
        self.page_size = page_size
        self.num_usable = num_pages - 1
        self.enable_prefix_caching = enable_prefix_caching
        # next tier down the memory hierarchy (ISSUE 10): the engine
        # attaches its HostKVTier here so one stats() call reports the
        # whole hierarchy — device pages AND parked host pages
        self.host_tier = None
        self._free: List[int] = list(range(self.num_usable))
        self._rc: Dict[int, int] = {}
        # prefix cache: chain key -> page id, LRU-ordered (move_to_end on
        # hit). The cache itself holds one reference on its pages.
        self._cache: "OrderedDict[Tuple, int]" = OrderedDict()
        self._key_by_page: Dict[int, Tuple] = {}
        # cache entries whose page only the cache holds (rc == 1), kept
        # as a count: `free_pages` is read a dozen times a tick, and a
        # walk of a 16k-entry cache each time was most of a tick's host
        # time (PERF.md section 6, PR 27)
        self._evictable = 0
        self.cache_hit_tokens = 0
        self.cache_query_tokens = 0

    # ------------------------------------------------------------ basics
    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    @property
    def free_pages(self) -> int:
        """Pages allocatable right now (free list + evictable cache)."""
        return len(self._free) + self._evictable

    def can_allocate(self, num_tokens: int) -> bool:
        return self.pages_needed(num_tokens) <= self.free_pages

    def allocate(self, num_tokens: int) -> List[int]:
        return self.allocate_pages(self.pages_needed(num_tokens))

    def allocate_pages(self, n: int) -> List[int]:
        if n > self.free_pages:
            raise MemoryError(
                f"KV cache exhausted: need {n} pages, {self.free_pages} "
                f"free")
        while len(self._free) < n:
            self._evict_one()
        pages, self._free = self._free[:n], self._free[n:]
        for p in pages:
            self._rc[p] = 1
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            rc = self._rc.get(p, 0) - 1
            if rc <= 0:
                self._rc.pop(p, None)
                self._free.append(p)
            else:
                self._rc[p] = rc
                if rc == 1 and p in self._key_by_page:
                    self._evictable += 1      # only the cache holds it

    # ----------------------------------------------------- prefix cache
    def _chain_keys(self, tokens: Sequence[int]) -> List[Tuple]:
        """One key per FULL page of `tokens`, each chaining its parent."""
        keys: List[Tuple] = []
        parent: Tuple = ()
        for i in range(len(tokens) // self.page_size):
            page_toks = tuple(
                tokens[i * self.page_size:(i + 1) * self.page_size])
            parent = (parent, page_toks)
            keys.append(parent)
        return keys

    def match_prefix(self, prompt_tokens: Sequence[int]
                     ) -> Tuple[List[int], int]:
        """Longest cached chain of full prompt pages.

        Returns (shared page ids with a reference taken, matched token
        count). Matching is capped one token short of the full prompt so
        the final prompt token is always recomputed — its logits seed
        the first sampled token (vLLM does the same)."""
        if not self.enable_prefix_caching:
            return [], 0
        matchable = prompt_tokens[:max(len(prompt_tokens) - 1, 0)]
        pages: List[int] = []
        for key in self._chain_keys(matchable):
            page = self._cache.get(key)
            if page is None:
                break
            self._cache.move_to_end(key)
            held = self._rc.get(page, 0)
            if held == 1:
                self._evictable -= 1          # a sequence holds it too now
            self._rc[page] = held + 1
            pages.append(page)
        return pages, len(pages) * self.page_size

    def cached_prefix_pages(self, tokens: Sequence[int]) -> List[int]:
        """Longest cached chain of FULL pages for `tokens`, in chain
        order, WITHOUT taking references or touching LRU order — the
        KV-transport export/import paths (ISSUE 12) inspect the cache
        under the engine step lock, where nothing can free or evict
        concurrently. Unlike match_prefix this is NOT capped one
        token short: the fleet prefix store ships every cached page
        of the shared prompt."""
        pages: List[int] = []
        for key in self._chain_keys(tokens):
            page = self._cache.get(key)
            if page is None:
                break
            pages.append(page)
        return pages

    def record_match(self, matched: int, prompt_len: int) -> None:
        """Hit-rate accounting, called ONCE per ADMITTED request (a
        blocked head-of-line request re-matches every scheduler tick and
        must not inflate the telemetry)."""
        self.cache_hit_tokens += matched
        self.cache_query_tokens += prompt_len

    def register_prefix(self, prompt_tokens: Sequence[int],
                        pages: Sequence[int]) -> None:
        """Offer a prefilled prompt's full pages to the cache. Pages
        already cached under the same chain are skipped (the earlier
        copy wins); newly cached pages gain the cache's reference."""
        if not self.enable_prefix_caching:
            return
        keys = self._chain_keys(prompt_tokens)
        for key, page in zip(keys, pages):
            if key in self._cache:
                self._cache.move_to_end(key)
                continue
            if page in self._key_by_page:
                continue   # page already caches a different chain
            self._cache[key] = page
            self._key_by_page[page] = key
            self._rc[page] = self._rc.get(page, 0) + 1
            if self._rc[page] == 1:
                self._evictable += 1

    def _evict_one(self) -> None:
        """Drop the least-recently-used cache entry whose page has no
        other owner (rc == 1: only the cache holds it)."""
        for key, page in self._cache.items():
            if self._rc.get(page, 0) == 1:
                del self._cache[key]
                del self._key_by_page[page]
                self._rc.pop(page, None)
                self._free.append(page)
                self._evictable -= 1
                return
        raise MemoryError("no evictable KV cache page")

    def clear_cache(self) -> None:
        """Drop every cache entry whose page has no other owner (bench /
        test hook; entries still referenced by live sequences stay)."""
        for key in list(self._cache):
            page = self._cache[key]
            if self._rc.get(page, 0) == 1:
                del self._cache[key]
                del self._key_by_page[page]
                self._rc.pop(page, None)
                self._free.append(page)
                self._evictable -= 1

    # ------------------------------------------------------------- stats
    @property
    def cached_pages(self) -> int:
        return len(self._cache)

    @property
    def used_pages(self) -> int:
        """Pages NOT allocatable right now — referenced by live
        sequences or pinned by multiply-owned cache entries (the
        complement of free_pages, which counts evictable cached pages
        as free)."""
        return self.num_usable - self.free_pages

    @property
    def cache_hit_rate(self) -> float:
        """Cumulative prefix-cache hit rate: matched prompt tokens /
        queried prompt tokens over every ADMITTED request (the
        occupancy signal paged-attention serving is judged on)."""
        return (self.cache_hit_tokens / self.cache_query_tokens
                if self.cache_query_tokens else 0.0)

    def stats(self) -> Dict[str, float]:
        out = {
            "free_pages": self.free_pages,
            "used_pages": self.used_pages,
            "occupancy": (self.used_pages / self.num_usable
                          if self.num_usable else 0.0),
            "cached_pages": self.cached_pages,
            "cache_hit_tokens": self.cache_hit_tokens,
            "cache_query_tokens": self.cache_query_tokens,
            "cache_hit_rate": self.cache_hit_rate,
        }
        if self.host_tier is not None:
            out.update(self.host_tier.stats())
        return out



PREFIX_CACHE_OFF_FOR_WINDOWS = (
    "off: a window group hands pages back as a sequence moves past "
    "them, so a cached chain of the full group has no window pages to "
    "resume on; a family with a window group matches nothing")
PREFIX_CACHE_OFF_FOR_STATE = (
    "off: a resume at token m needs the recurrent state as it stood at "
    "m, and a state group keeps one state a slot, the newest; no "
    "snapshot is taken at page boundaries, so a family with a state "
    "group matches nothing")


class _SlotState:
    """A state group at run time: which slots hold it. It has arrays
    `[layers, slots, ...]` on the device (the engine's), no allocator
    and no table: a slot's state is its own from admission to vacate,
    and a sequence that starts at token 0 starts from zeros INSIDE the
    tick's program, so nothing is cleared here or on the device."""

    def __init__(self, spec: CacheGroup, n_slots: int):
        self.spec = spec
        self.n_slots = n_slots
        self.held = [False] * n_slots
        self.peak_held = 0
        self.held_at_peak = 0

    @property
    def n_held(self) -> int:
        return sum(self.held)

    def note_peak(self) -> None:
        self.peak_held = max(self.peak_held, self.n_held)


class _GroupState:
    """One cache group at run time: its allocator, its page table a
    slot and, for a window group, what each slot holds of it."""

    def __init__(self, spec: CacheGroup, num_pages: int, page_size: int,
                 n_slots: int, table_width: int, prefix_caching: bool):
        self.spec = spec
        self.num_pages = num_pages
        self.allocator = PageAllocator(
            num_pages, page_size, enable_prefix_caching=prefix_caching)
        self.tables = np.zeros((n_slots, table_width), np.int32)
        # a window group's slots hold table columns [lo, hi); `reserve`
        # pages were set aside at admission, `final` columns is all the
        # request will ever write
        self.lo = [0] * n_slots
        self.hi = [0] * n_slots
        self.reserve = [0] * n_slots
        self.final = [0] * n_slots
        self.returned = 0            # pages handed back behind windows
        self.peak_used = 0
        # pages in use when the whole cache's bytes in use last peaked
        self.used_at_peak = 0

    @property
    def outstanding(self) -> int:
        """Pages reserved at admission that their slots do not hold
        right now: free, but spoken for."""
        return sum(min(r - (h - l), f - h) for l, h, r, f in zip(
            self.lo, self.hi, self.reserve, self.final) if r)

    @property
    def admittable(self) -> int:
        """Pages a new admission may reserve."""
        return self.allocator.free_pages - (
            self.outstanding if self.spec.window else 0)

    def note_peak(self) -> None:
        self.peak_used = max(self.peak_used, self.allocator.used_pages)


class CacheManager:
    """The engine's cache: one `PageAllocator`, one page table a slot
    and (the engine's) one set of pools for each `CacheGroup` of the
    model's family. A family with one group (every layer, no window)
    gets today's allocator and table and nothing else; `first` is that
    allocator, and the engine's `slot.pages` is its page list.

    A window group (`CacheGroup.window` = w tokens) holds, for a live
    sequence whose next query sits at position p, the pages that cover
    (p - w, p + what the next tick may write] and no others: `advance`
    hands the pages wholly behind every query still to come back to the
    group's allocator (their table entries point at the scratch page;
    the kernel's sweep starts at the window's first block and never
    reads them) and claims the pages ahead. Hand-back, not a ring of
    pages a slot: position -> table column stays one rule for every
    group, and a page behind one sequence's window serves another's
    front. Admission reserves each group's worst case, prompt +
    max_new_tokens in a full group and min(that, w + a tick's tokens +
    2 pages) in a window group, and counts what is reserved but not
    held against later admissions, so a running sequence cannot meet an
    empty pool mid-decode.

    Prefix cache: a resume at token m needs the window group's pages
    over (m - w, m] too, and those are gone; with a window group the
    cache is off in every group (`PREFIX_CACHE_OFF_FOR_WINDOWS`).

    A STATE group (`CacheGroup.state`: a recurrent layer's fixed bytes a
    slot) comes after the page groups; `states` holds them. It is held
    from `admit` to `vacate`, counts in `can_admit`, `bytes_used`, the
    peaks and `stats()["cache_groups"]`, and has no pages: `groups`,
    `tables` and the top-level page counts are the PAGE groups' alone.
    With one, the prefix cache is off too
    (`PREFIX_CACHE_OFF_FOR_STATE`)."""

    def __init__(self, groups: Sequence[CacheGroup],
                 num_pages: Sequence[int], page_size: int, n_slots: int,
                 table_width: int, tick_tokens: int,
                 enable_prefix_caching: bool = True):
        states = [g for g in groups if g.state is not None]
        n_paged = len(groups) - len(states)
        if any(g.state is not None for g in groups[:n_paged]) \
                or not n_paged:
            raise ValueError(
                "state groups come after the page groups, and a family "
                "has at least one page group")
        # (a state group's entry of `num_pages` counts nothing)
        groups, num_pages = groups[:n_paged], list(num_pages)[:n_paged]
        if groups[0].window is not None or any(
                g.window is None for g in groups[1:]):
            raise ValueError(
                "the first cache group holds whole contexts (the "
                "engine's slot.pages is its page list) and the groups "
                "after it are window groups: no family has asked for "
                "another layout")
        self.page_size = page_size
        self.tick_tokens = int(tick_tokens)
        self.windowed = len(groups) > 1
        self.states = [_SlotState(g, n_slots) for g in states]
        self.prefix_cache = (
            PREFIX_CACHE_OFF_FOR_STATE if self.states
            else PREFIX_CACHE_OFF_FOR_WINDOWS if self.windowed
            else "on" if enable_prefix_caching else "off")
        matching = (enable_prefix_caching and not self.windowed
                    and not self.states)
        self.groups = [
            _GroupState(g, n, page_size, n_slots, table_width, matching)
            for g, n in zip(groups, num_pages)]
        self.first = self.groups[0].allocator
        self._rest = self.groups[1:]
        self._peak_bytes = 0

    # ------------------------------------------------------- admission
    def reserve_pages(self, g: _GroupState, tokens: int) -> int:
        """Pages group `g` sets aside for a request of `tokens` in all
        (prompt + max_new_tokens, or what optimistic admission
        reserves of it)."""
        w = g.spec.window
        if w is not None:
            tokens = min(tokens, w + self.tick_tokens + 2 * self.page_size)
        return g.allocator.pages_needed(tokens)

    def fits(self, tokens: int) -> Optional[str]:
        """None if a request of `tokens` could ever be admitted, else
        which group cannot hold it."""
        for g in self.groups:
            need = self.reserve_pages(g, tokens)
            if need > g.allocator.num_usable:
                return (f"needs {need} KV pages of group "
                        f"{g.spec.name!r} but the pool only has "
                        f"{g.allocator.num_usable}")
        return None

    def can_admit(self, tokens: int, shared: int = 0) -> bool:
        """Does EVERY group have the pages a request of `tokens` in all
        reserves? `shared`: pages of the first group a prefix match
        already holds."""
        if self.reserve_pages(self.groups[0], tokens) - shared \
                > self.first.free_pages:
            return False
        if any(st.n_held >= st.n_slots for st in self.states):
            return False
        return all(self.reserve_pages(g, tokens) <= g.admittable
                   for g in self._rest)

    def admit(self, slot: int, tokens: int, shared: Sequence[int] = (),
              pos: int = 0) -> List[int]:
        """Claim every group's reservation for `slot`. Returns the first
        group's pages (`shared` first), which the engine keeps as
        `slot.pages`; the other groups' pages live here. `pos`: where
        the sequence's prefill starts."""
        head = self.groups[0]
        pages = list(shared) + self.first.allocate_pages(
            self.reserve_pages(head, tokens) - len(shared))
        head.tables[slot] = 0
        head.tables[slot, :len(pages)] = pages
        head.note_peak()
        for g in self._rest:
            g.reserve[slot] = self.reserve_pages(g, tokens)
            g.final[slot] = g.allocator.pages_needed(tokens)
            g.tables[slot] = g.num_pages - 1
            g.lo[slot] = g.hi[slot] = max(
                (pos - g.spec.window + 1) // self.page_size, 0)
        for st in self.states:
            st.held[slot] = True
            st.note_peak()
        self.advance([(slot, pos)])
        return pages

    def reset_peaks(self) -> None:
        """Forget the peaks so far: what `pages_peak` and
        `pages_at_peak` say from here on is of what comes after (a
        benchmark's window, after its checks and warm-up)."""
        self._peak_bytes = 0
        for g in self.groups:
            g.peak_used = g.used_at_peak = g.allocator.used_pages
        for st in self.states:
            st.peak_held = st.held_at_peak = st.n_held

    def _note_bytes_peak(self) -> None:
        """Remember what every group held when the bytes in use, all
        groups together, were at their most: what a family with a window
        group saves is read there (`pages_at_peak`)."""
        now = self.bytes_used()
        if now > self._peak_bytes:
            self._peak_bytes = now
            for g in self.groups:
                g.used_at_peak = g.allocator.used_pages
            for st in self.states:
                st.held_at_peak = st.n_held

    def vacate(self, slot: int) -> None:
        """`slot` is empty: whatever it holds beyond the first group
        goes back, and its table rows are cleared. (The first group's
        pages are the engine's `slot.pages`, freed by whoever clears
        the slot.)"""
        self.groups[0].tables[slot] = 0
        for g in self._rest:
            lo, hi = g.lo[slot], g.hi[slot]
            if hi > lo:
                g.allocator.free(g.tables[slot, lo:hi].tolist())
            g.lo[slot] = g.hi[slot] = g.reserve[slot] = g.final[slot] = 0
            g.tables[slot] = 0
        for st in self.states:
            st.held[slot] = False

    # ---------------------------------------------------- window groups
    def advance(self, live: Iterable[Tuple[int, int]]) -> Tuple[int, int]:
        """A tick boundary. `live`: (slot, position of the earliest
        query still to come) for each sequence: a prefilling slot's next
        chunk starts there, a decoding slot's in-flight or next token
        sits there. Window groups first hand back every page wholly
        behind that query's window, then claim the pages the next tick
        may write (a tick's tokens, and one more for the token a
        pipelined decode tick writes before the host has folded its
        predecessor). Returns (pages handed back, pages claimed).
        Nothing to do, and nothing done, for a family without a window
        group."""
        if not self.windowed:
            return 0, 0
        live = list(live)
        page, handed, claimed = self.page_size, 0, 0
        for g in self._rest:
            w = g.spec.window
            scratch, returned = g.num_pages - 1, 0
            for slot, pos in live:
                lo = min(max((pos - w + 1) // page, g.lo[slot]),
                         g.hi[slot])
                if lo > g.lo[slot]:
                    row = g.tables[slot]
                    g.allocator.free(row[g.lo[slot]:lo].tolist())
                    row[g.lo[slot]:lo] = scratch
                    returned += lo - g.lo[slot]
                    g.lo[slot] = lo
            for slot, pos in live:
                hi = min(g.final[slot], g.allocator.pages_needed(
                    pos + self.tick_tokens + 2))
                if hi > g.hi[slot]:
                    g.tables[slot, g.hi[slot]:hi] = (
                        g.allocator.allocate_pages(hi - g.hi[slot]))
                    claimed += hi - g.hi[slot]
                    g.hi[slot] = hi
            g.returned += returned
            handed += returned
            g.note_peak()
        self._note_bytes_peak()
        return handed, claimed

    # ------------------------------------------------------------ stats
    @property
    def tables(self) -> List[np.ndarray]:
        return [g.tables for g in self.groups]

    def bytes_used(self) -> int:
        """Device bytes the pages in use hold, every group, and what
        the slots that hold a state group's state hold of it."""
        return (sum(g.allocator.used_pages * self.page_size
                    * g.spec.bytes_per_token for g in self.groups)
                + sum(st.n_held * st.spec.bytes_per_slot
                      for st in self.states))

    def page_bytes(self) -> int:
        """Device bytes of one page in every group (one group: a page
        across the whole stack)."""
        return sum(self.page_size * g.spec.bytes_per_token
                   for g in self.groups)

    def stats(self) -> Dict[str, Any]:
        """The allocator's stats of the FULLEST group, the one that
        gates admission (a window group's free pages are those a new
        admission may reserve), `total_pages`, and `cache_groups`: each
        group's row, layers, window and pages total / used / peak /
        reserved / at the peak of the whole cache's bytes in use, and
        for a window group the pages handed back behind the window
        since start-up."""
        def occupancy(g):
            total = g.allocator.num_usable
            return 1.0 - g.admittable / total if total else 0.0

        full = max(self.groups, key=occupancy)
        out = full.allocator.stats()
        if full.spec.window is not None:
            out["free_pages"] = full.admittable
            out["used_pages"] = full.allocator.num_usable - full.admittable
            out["occupancy"] = occupancy(full)
        out["total_pages"] = full.allocator.num_usable
        out["prefix_cache"] = self.prefix_cache
        groups = []
        for g in self.groups:
            used = g.allocator.used_pages
            g.note_peak()
            d = {**g.spec.describe(),
                 "pages_total": g.allocator.num_usable,
                 "pages_used": used, "pages_peak": g.peak_used,
                 "pages_reserved": used + (
                     g.outstanding if g.spec.window else 0),
                 "pages_at_peak": (g.used_at_peak if self.windowed
                                   else g.peak_used)}
            if g.spec.window is not None:
                d["pages_returned"] = g.returned
            groups.append(d)
        for st in self.states:
            st.note_peak()
            groups.append({**st.spec.describe(),
                           "slots_total": st.n_slots,
                           "slots_held": st.n_held,
                           "slots_peak": st.peak_held,
                           "slots_at_peak": st.held_at_peak})
        out["cache_groups"] = groups
        return out
