"""`ops/kda_scan.kda_ragged_scan` (the gated delta rule with a decay a
channel over a ragged tick) against the recurrence stepped one token at
a time in numpy float64: the plain `jax.numpy` path and the interpreted
kernel, on runs that start mid-chunk and cross chunk boundaries, decode
rows among chunks, new and resumed sequences sharing a tick, an empty
tick, and decays at both ends of the range.

The tolerance: float32 products at HIGHEST precision against float64,
through a triangular solve of 64 rows: 2e-4 relative and absolute on
outputs and states of order 1 (the stepped float32 recurrence itself
reads 1e-5; the blocked solve and the levels' references add a few
times that). A wrong FORM reads 1e-2 and more (`test_wrong_forms...`)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import kda_scan
from ray_tpu.ops.selective_scan import segment_marks

H, K, V, B = 2, 16, 16, 6
TOL = dict(rtol=2e-4, atol=2e-4)


def _inputs(seed, t, decay="mid"):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.standard_normal(s).astype(np.float32)
    unit = lambda m: m / np.linalg.norm(m, axis=-1, keepdims=True)
    g = -np.exp(rng.uniform(-4, 1, (t, H, K))).astype(np.float32)
    if decay == "none":
        g = np.full((t, H, K), -1e-6, np.float32)
    elif decay == "fast":
        # g = -20 a token on every other channel, and channels that
        # switch between -20 and ~0 from token to token
        g[:, :, ::2] = -20.0
        g[::3, :, 1::4] = -20.0
        g[1::3, :, 1::4] = -1e-3
    return dict(
        q=unit(f(t, H, K)) * K ** -0.5, k=unit(f(t, H, K)), v=f(t, H, V),
        g=g, beta=(1 / (1 + np.exp(-f(t, H)))).astype(np.float32),
        state=f(2, B, H, K, V))


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


def _stepped(inp, runs, layer, *, use_beta=True, head_decay=False):
    """The recurrence itself, a token at a time, in float64. The two
    switches make the WRONG forms a test must tell from the right one."""
    t = inp["q"].shape[0]
    o = np.zeros((t, H, V), np.float32)
    state = inp["state"].copy()
    cur = 0
    for s, pos0, n in runs:
        st = (state[layer, s].astype(np.float64) if pos0
              else np.zeros((H, K, V)))
        for tok in range(cur, cur + n):
            for h in range(H):
                g = inp["g"][tok, h].astype(np.float64)
                if head_decay:
                    g = np.full_like(g, g.mean())
                b = float(inp["beta"][tok, h]) if use_beta else 1.0
                k = inp["k"][tok, h].astype(np.float64)
                st[h] = np.exp(g)[:, None] * st[h]
                st[h] += b * np.outer(k, inp["v"][tok, h] - st[h].T @ k)
                o[tok, h] = st[h].T @ inp["q"][tok, h]
        state[layer, s] = st
        cur += n
    return o, state


def _run(inp, tick, layer, impl):
    slot_ids, positions, valid, start, last_idx = (jnp.array(a)
                                                   for a in tick)
    marks = segment_marks(slot_ids, positions, valid, start, last_idx)
    o, state = kda_scan.kda_ragged_scan(
        *(jnp.array(inp[n]) for n in ("q", "k", "v", "g", "beta")), marks,
        slot_ids, valid, last_idx, jnp.array(inp["state"]), layer,
        impl=impl)
    return np.asarray(o), np.asarray(state)


CASES = {
    # a chunk of a prompt that continues, decode rows, a prompt that
    # starts in a slot that held something, padding behind
    "mixed": ([(2, 7, 11), (0, 30, 1), (4, 3, 1), (5, 0, 9), (1, 12, 1)],
              32),
    "decode_only": ([(0, 5, 1), (3, 9, 1), (5, 1, 1)], 8),
    "one_long_fresh": ([(1, 0, 24)], 24),
    # over 64 tokens: runs that start mid-chunk and straddle chunks
    "straddle": ([(3, 4, 1), (0, 9, 140), (2, 0, 100), (4, 50, 1),
                  (5, 6, 7)], 256),
    "empty": ([], 16),
}
IMPLS = ["gather", "pallas_interpret"]


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("decay", ["mid", "none", "fast"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_ragged_scan_matches_the_stepped_recurrence(case, decay, impl):
    runs, t = CASES[case]
    inp = _inputs(3, t, decay)
    layer = 1
    want_o, want_state = _stepped(inp, runs, layer)
    o, state = _run(inp, _tick(runs, t), layer, impl)
    assert np.isfinite(o).all() and np.isfinite(state).all()
    n_valid = sum(n for _, _, n in runs)
    np.testing.assert_allclose(o[:n_valid], want_o[:n_valid], **TOL)
    np.testing.assert_array_equal(o[n_valid:], 0)
    np.testing.assert_allclose(state, want_state, **TOL)
    # the other layer's rows, and the slots without a run, are as given
    np.testing.assert_array_equal(state[0], inp["state"][0])
    idle = sorted(set(range(B)) - {s for s, _, _ in runs})
    np.testing.assert_array_equal(state[layer][idle],
                                  inp["state"][layer][idle])


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("wrong", ["beta_left_out", "decay_a_head"])
def test_wrong_forms_are_not_within_the_tolerance(wrong, impl):
    """What the tolerance is worth: beta left out, or one decay a head
    (the mean of its channels') in place of one a channel, is far
    outside it."""
    runs, t = CASES["mixed"]
    inp = _inputs(3, t)
    o, state = _run(inp, _tick(runs, t), 1, impl)
    kw = (dict(use_beta=False) if wrong == "beta_left_out"
          else dict(head_decay=True))
    bad_o, bad_state = _stepped(inp, runs, 1, **kw)
    n_valid = sum(n for _, _, n in runs)
    assert np.abs(o[:n_valid] - bad_o[:n_valid]).max() > 1e-2
    assert np.abs(state - bad_state).max() > 1e-2


def test_inactive_rows_between_active_ones_are_passed_by():
    """A decode tick's layout: one token a slot, some slots inactive."""
    inp = _inputs(5, B)
    slot_ids = np.arange(B, dtype=np.int32)
    positions = np.array([4, 0, 7, 2, 0, 9], np.int32)
    valid = np.array([True, False, True, True, False, True])
    tick = (slot_ids, positions, valid, positions, slot_ids)
    for impl in IMPLS:
        o, state = _run(inp, tick, 0, impl)
        for s in range(B):
            one = {k: (v[s:s + 1] if k != "state" else v)
                   for k, v in inp.items()}
            wo, ws = _stepped(one, [(s, int(positions[s]), 1)], 0)
            if valid[s]:
                np.testing.assert_allclose(o[s], wo[0], **TOL)
                np.testing.assert_allclose(state[0, s], ws[0, s], **TOL)
            else:
                np.testing.assert_array_equal(state[0, s],
                                              inp["state"][0, s])


def test_a_traced_layer_index_picks_the_layer():
    """A stack that scans its layers hands the index traced."""
    runs, t = CASES["mixed"]
    inp = _inputs(7, t)
    tick = _tick(runs, t)
    for impl in IMPLS:
        want = _run(inp, tick, 1, impl)
        got = jax.jit(lambda l: _run_traced(inp, tick, l, impl))(
            jnp.int32(1))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-6, atol=1e-6)


def _run_traced(inp, tick, layer, impl):
    slot_ids, positions, valid, start, last_idx = (jnp.array(a)
                                                   for a in tick)
    marks = segment_marks(slot_ids, positions, valid, start, last_idx)
    return kda_scan.kda_ragged_scan(
        *(jnp.array(inp[n]) for n in ("q", "k", "v", "g", "beta")), marks,
        slot_ids, valid, last_idx, jnp.array(inp["state"]), layer,
        impl=impl)


def test_padded_tokens_are_whole_chunks_or_a_power_of_two():
    assert [kda_scan.padded_tokens(t) for t in (1, 8, 9, 48, 64, 65, 512)
            ] == [8, 8, 16, 64, 64, 128, 512]
