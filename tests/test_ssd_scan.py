"""`ops/ssd_scan.ssd_ragged_scan` (Mamba-2's recurrence over a ragged
tick) against the recurrence stepped one token at a time in numpy: the
plain `jax.numpy` path and the interpreted kernel, on runs that straddle
chunks, one-token runs, a run that continues stored state, one that
starts from zeros over a slot that held something, and padding; with
groups that are one head tile each and with ONE group cut into tiles."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssd_scan
from ray_tpu.ops.selective_scan import segment_marks

H, P, G, N, B = 4, 8, 2, 16, 6
# (heads, groups): two groups of two heads (a tile a group, under 8 heads);
# ONE group of 16 heads (two tiles of 8 that read the same B and C:
# granite-4.0-h-micro's layout at a quarter of its heads); eight groups of
# 8 (a tile a group: Nemotron's layout)
GEOMETRIES = {"2 groups of 2": (4, 2), "1 group of 16": (16, 1),
              "8 groups of 8": (64, 8)}


@pytest.fixture
def geometry(request, monkeypatch):
    """The module's H and G for one test."""
    h, g = GEOMETRIES[request.param]
    monkeypatch.setattr(request.module, "H", h)
    monkeypatch.setattr(request.module, "G", g)
    return h, g


def _inputs(seed, t):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    return dict(
        x=f(t, H, P), dt=np.log1p(np.exp(f(t, H))).astype(np.float32),
        a=-np.exp(rng.uniform(-1, 2, H)).astype(np.float32),
        b=f(t, G, N), c=f(t, G, N), d=f(H),
        state=f(2, B, H, P, N))


def _tick(runs, t):
    """runs: [(slot, cached tokens before the tick, tokens)] packed from
    row 0 -> slot_ids, positions, valid [T]; start, last_idx [B]."""
    slot_ids = np.zeros(t, np.int32)
    positions = np.zeros(t, np.int32)
    valid = np.zeros(t, bool)
    start = np.zeros(B, np.int32)
    last_idx = np.zeros(B, np.int32)
    cur = 0
    for s, pos0, n in runs:
        slot_ids[cur:cur + n] = s
        positions[cur:cur + n] = np.arange(pos0, pos0 + n)
        valid[cur:cur + n] = True
        start[s], last_idx[s] = pos0, cur + n - 1
        cur += n
    return slot_ids, positions, valid, start, last_idx


def _stepped(inp, runs, layer):
    """The recurrence itself, a token at a time."""
    t = inp["x"].shape[0]
    y = np.zeros((t, H, P), np.float32)
    state = inp["state"].copy()
    cur = 0
    for s, pos0, n in runs:
        st = (state[layer, s].astype(np.float64) if pos0
              else np.zeros((H, P, N)))
        for tok in range(cur, cur + n):
            for h in range(H):
                g = h // (H // G)
                st[h] = (np.exp(inp["dt"][tok, h] * inp["a"][h]) * st[h]
                         + inp["dt"][tok, h] * np.outer(
                             inp["x"][tok, h], inp["b"][tok, g]))
                y[tok, h] = (st[h] @ inp["c"][tok, g]
                             + inp["d"][h] * inp["x"][tok, h])
        state[layer, s] = st
        cur += n
    return y, state


def _run(inp, tick, layer, impl):
    slot_ids, positions, valid, start, last_idx = (jnp.array(a)
                                                   for a in tick)
    marks = segment_marks(slot_ids, positions, valid, start, last_idx)
    y, state = ssd_scan.ssd_ragged_scan(
        jnp.array(inp["x"]), jnp.array(inp["dt"]), jnp.array(inp["a"]),
        jnp.array(inp["b"]), jnp.array(inp["c"]), jnp.array(inp["d"]),
        marks, slot_ids, valid, last_idx, jnp.array(inp["state"]), layer,
        impl=impl)
    return np.asarray(y), np.asarray(state)


CASES = {
    # a chunk of a prompt that continues, decode rows, a prompt that
    # starts in a slot that held something, padding behind
    "mixed": ([(2, 7, 11), (0, 30, 1), (4, 3, 1), (5, 0, 9), (1, 12, 1)],
              32),
    "decode_only": ([(0, 5, 1), (3, 9, 1), (5, 1, 1)], 8),
    "one_long_fresh": ([(1, 0, 24)], 24),
    # over 128 tokens: runs that straddle the kernel's chunks
    "straddle": ([(3, 4, 1), (0, 9, 140), (2, 0, 100), (4, 50, 1),
                  (5, 6, 7)], 256),
}


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
@pytest.mark.parametrize("case,geometry", [
    (case, "2 groups of 2") for case in sorted(CASES)] + [
    # ragged ticks that mix chunks and one-token rows, at head tiles
    (case, name) for case in ("mixed", "straddle")
    for name in ("1 group of 16", "8 groups of 8")], indirect=["geometry"])
def test_ragged_scan_matches_the_stepped_recurrence(case, geometry, impl):
    runs, t = CASES[case]
    inp = _inputs(3, t)
    layer = 1
    want_y, want_state = _stepped(inp, runs, layer)
    y, state = _run(inp, _tick(runs, t), layer, impl)
    n_valid = sum(n for _, _, n in runs)
    np.testing.assert_allclose(y[:n_valid], want_y[:n_valid], rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-4)
    # the other layer's rows, and the slots without a run, are as given
    np.testing.assert_array_equal(state[0], inp["state"][0])
    idle = sorted(set(range(B)) - {s for s, _, _ in runs})
    np.testing.assert_array_equal(state[layer][idle],
                                  inp["state"][layer][idle])


def test_inactive_rows_between_active_ones_are_passed_by():
    """A decode tick's layout: one token a slot, some slots inactive."""
    t = B
    inp = _inputs(5, t)
    slot_ids = np.arange(B, dtype=np.int32)
    positions = np.array([4, 0, 7, 2, 0, 9], np.int32)
    valid = np.array([True, False, True, True, False, True])
    tick = (slot_ids, positions, valid, positions, slot_ids)
    want = {}
    for impl in ("gather", "pallas_interpret"):
        y, state = _run(inp, tick, 0, impl)
        for s in range(B):
            runs = [(s, int(positions[s]), 1)]
            one = {k: (v[s:s + 1] if k in ("x", "dt", "b", "c") else v)
                   for k, v in inp.items()}
            wy, ws = want.setdefault(s, _stepped(one, runs, 0))
            if valid[s]:
                np.testing.assert_allclose(y[s], wy[0], rtol=2e-4,
                                           atol=2e-4)
                np.testing.assert_allclose(state[0, s], ws[0, s],
                                           rtol=2e-4, atol=2e-4)
            else:
                np.testing.assert_array_equal(state[0, s],
                                              inp["state"][0, s])


def test_segments_cut_runs_at_chunk_boundaries():
    runs, t = CASES["straddle"]
    slot_ids, positions, valid, start, last_idx = (
        jnp.array(a) for a in _tick(runs, t))
    marks = segment_marks(slot_ids, positions, valid, start, last_idx)
    chunk, row, length, slot, first, n = (
        np.asarray(a) for a in ssd_scan.segments(
            marks, slot_ids, valid, 128, 2 + B))
    n = int(n)
    # (3: 1) (0: 127 | 13) (2: 100) (4: 1) (5: 7)
    assert n == 6
    assert length[:n].tolist() == [1, 127, 13, 100, 1, 7]
    assert chunk[:n].tolist() == [0, 0, 1, 1, 1, 1]
    assert row[:n].tolist() == [0, 1, 0, 13, 113, 114]
    assert slot[:n].tolist() == [3, 0, 0, 2, 4, 5]
    assert first[:n].tolist() == [1, 1, 0, 2, 1, 1]


def _lowered_call(h, g, t=16):
    """The kernel's `pallas_call` as `_ssd_call` traces it for `h` heads
    in `g` groups: its grid, its block shapes, and the primitives of each
    operand's index map."""
    f32 = jnp.float32
    seg = tuple(jnp.zeros((4,), jnp.int32) for _ in range(5)) + (
        jnp.int32(2),)
    jaxpr = jax.make_jaxpr(
        lambda *a: ssd_scan._ssd_call(seg, 0, *a, interpret=True))(
        jnp.zeros((h,), f32), jnp.zeros((t, h * P), f32),
        jnp.zeros((t, h), f32), jnp.zeros((t, h), f32),
        jnp.zeros((g, t, N), f32), jnp.zeros((g, t, N), f32),
        jnp.zeros((2, B, h, P, N), f32))

    def find(jp):
        for e in jp.eqns:
            if e.primitive.name == "pallas_call":
                return e
            for sub in jax.core.jaxprs_in_params(e.params):
                got = find(sub)
                if got is not None:
                    return got
    def names(jp):
        out = set()
        for e in jp.eqns:
            out.add(e.primitive.name)
            for sub in jax.core.jaxprs_in_params(e.params):
                out |= names(sub)
        return out
    mapping = find(jaxpr.jaxpr).params["grid_mapping"]
    blocks = [tuple(bm.block_shape) for bm in mapping.block_mappings]
    maps = [names(bm.index_map_jaxpr.jaxpr)
            for bm in mapping.block_mappings]
    return mapping.grid, blocks, maps


def test_eight_groups_of_eight_lower_to_the_grid_they_had():
    """G = 8 over 64 heads (`nemotron-agent`): the first grid axis is the
    8 groups, a step's state block a group's 8 heads, and no index map
    divides (a tile IS its group), as before head tiles. G = 1 over 64
    heads: the same grid and the same blocks, B and C picked by tile //
    8."""
    grid8, blocks8, maps8 = _lowered_call(64, 8)
    grid1, blocks1, maps1 = _lowered_call(64, 1)
    assert grid8[0] == grid1[0] == 8
    assert blocks8 == blocks1
    state = [b for b in blocks8 if len(b) == 5]
    assert len(state) == 2 and all(b[2].block_size == 8 for b in state)
    divides = lambda maps: [m for m in maps if m - {"get"}]
    assert not divides(maps8)                  # a scalar read, no more
    assert len(divides(maps1)) == 2            # B and C
    assert ssd_scan.head_tile(64, 8) == ssd_scan.head_tile(64, 1) == 8
    assert ssd_scan.head_tile(4, 2) == 2
    with pytest.raises(ValueError, match="whole number of tiles"):
        ssd_scan.head_tile(12, 1)
