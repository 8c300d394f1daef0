"""The ragged selective scan and its conv (`ops/selective_scan.py`)
against a sequential loop over each sequence's whole history, at a toy
size on the CPU, in float32: runs that continue a stored state, runs that
start from zero in a slot another sequence left, invalid tokens, several
runs a tick, one token a row; the associative-scan path and the Pallas
kernel (interpreted) against the same loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as ssm

E, N, K, B = 128, 16, 4, 3


def _weights(seed=0):
    r = np.random.default_rng(seed)
    return {"a_t": -np.exp(r.normal(size=(N, E))).astype(np.float32),
            "d": r.normal(size=E).astype(np.float32),
            "w": r.normal(size=(K, E)).astype(np.float32) / 2,
            "b": r.normal(size=E).astype(np.float32) / 10}


def _sequence(seed, length):
    """One sequence's inputs: u (the conv's input), and what the model
    would make of the conv's output is left to `_loop`: delta, B, C are
    drawn, not projected."""
    r = np.random.default_rng(seed)
    return {"u": r.normal(size=(length, E)).astype(np.float32),
            "delta": np.abs(r.normal(size=(length, E))).astype(np.float32)
            / 4,
            "bm": r.normal(size=(length, N)).astype(np.float32),
            "cm": r.normal(size=(length, N)).astype(np.float32)}


def _loop(wt, seq):
    """Token by token from zero state: the conv over the whole history,
    silu, then the recurrence. Returns (conv output, y)."""
    u = seq["u"]
    length = u.shape[0]
    padded = np.concatenate([np.zeros((K - 1, E), np.float32), u])
    conv = np.stack([sum(padded[t + k] * wt["w"][k] for k in range(K))
                     for t in range(length)]) + wt["b"]
    x = conv / (1 + np.exp(-conv))
    s = np.zeros((N, E), np.float32)
    ys = []
    for t in range(length):
        s = (np.exp(seq["delta"][t][None] * wt["a_t"]) * s
             + (seq["delta"][t] * x[t])[None] * seq["bm"][t][:, None])
        ys.append((s * seq["cm"][t][:, None]).sum(0) + wt["d"] * x[t])
    return conv, np.stack(ys)


def _run(wt, seqs, ticks, impl, t_bucket=16, state=None):
    """ticks: [[(slot, sequence, first position, tokens)]]. Runs the conv
    and the scan a tick at a time on ONE state; returns ({(sequence,
    position): (conv row, y row)}, final (conv inputs, scan state))."""
    conv_in = jnp.zeros((B, K - 1, E), jnp.float32)
    scan = jnp.zeros((2, B, N, E), jnp.float32)     # layer 1 is ours
    if state is not None:
        conv_in, scan = state
    got = {}
    for rows in ticks:
        tok = {k: np.zeros((t_bucket,) + v.shape[1:], np.float32)
               for k, v in seqs[0].items()}
        slot = np.zeros(t_bucket, np.int32)
        pos = np.zeros(t_bucket, np.int32)
        valid = np.zeros(t_bucket, bool)
        start = np.zeros(B, np.int32)
        last = np.zeros(B, np.int32)
        cur, where = 0, []
        for s, q, p0, n in rows:
            for k in tok:
                tok[k][cur:cur + n] = seqs[q][k][p0:p0 + n]
            slot[cur:cur + n], valid[cur:cur + n] = s, True
            pos[cur:cur + n] = np.arange(p0, p0 + n)
            start[s], last[s] = p0, cur + n - 1
            where += [(q, p0 + i, cur + i) for i in range(n)]
            cur += n
        slot, pos, valid, start, last = map(
            jnp.array, (slot, pos, valid, start, last))
        marks = ssm.segment_marks(slot, pos, valid, start, last)
        conv, conv_in = ssm.causal_conv_ragged(
            jnp.array(tok["u"]), jnp.array(wt["w"]), jnp.array(wt["b"]),
            slot, last, marks, conv_in)
        x = jax.nn.silu(conv)
        y, scan = ssm.selective_scan_ragged(
            x, jnp.array(tok["delta"]), jnp.array(wt["a_t"]),
            jnp.array(tok["bm"]), jnp.array(tok["cm"]), jnp.array(wt["d"]),
            slot, valid, last, marks, scan, 1, impl=impl)
        conv, y = np.asarray(conv), np.asarray(y)
        got.update({(q, p): (conv[i], y[i]) for q, p, i in where})
        assert np.all(np.asarray(scan[0]) == 0)     # the other layer's
    return got, (conv_in, scan)


PACKINGS = {
    "one pass": [[(1, 0, 0, 16)], [(1, 0, 16, 14)]],
    "boundaries inside the taps": [[(1, 0, 0, 5)], [(1, 0, 5, 1)],
                                   [(1, 0, 6, 2)], [(1, 0, 8, 3)],
                                   [(1, 0, 11, 16)], [(1, 0, 27, 3)]],
    "several runs a tick": [[(0, 0, 0, 5), (2, 1, 0, 6), (1, 2, 0, 5)],
                            [(1, 2, 5, 9), (0, 0, 5, 1), (2, 1, 6, 6)],
                            [(2, 1, 12, 1), (0, 0, 6, 14)]],
    "one token a row": ([[(0, 0, 0, 3), (1, 1, 0, 2)]]
                        + [[(0, 0, 3 + i, 1), (1, 1, 2 + i, 1)]
                           for i in range(8)]),
    "a slot another sequence left": [[(1, 0, 0, 16)], [(1, 0, 16, 4)],
                                     [(1, 1, 0, 7), (0, 2, 0, 9)],
                                     [(1, 1, 7, 9)]],
    "a row that sits a tick out": [[(0, 0, 0, 8), (1, 1, 0, 8)],
                                   [(1, 1, 8, 16)], [(0, 0, 8, 8)]],
}


@pytest.fixture(scope="module")
def world():
    wt = _weights()
    seqs = [_sequence(10 + i, 30) for i in range(3)]
    return wt, seqs, [_loop(wt, s) for s in seqs]


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
@pytest.mark.parametrize("name", list(PACKINGS))
def test_every_packing_is_the_sequential_loop(world, name, impl):
    wt, seqs, want = world
    got, _ = _run(wt, seqs, PACKINGS[name], impl)
    assert got
    for (q, p), (conv, y) in got.items():
        np.testing.assert_allclose(conv, want[q][0][p], atol=2e-5)
        np.testing.assert_allclose(y, want[q][1][p], atol=2e-4)


@pytest.mark.parametrize("impl", ["gather", "pallas_interpret"])
def test_state_carried_over_chunks_is_one_pass(world, impl):
    wt, seqs, _ = world
    _, whole = _run(wt, seqs, [[(2, 0, 0, 16)]], impl)
    _, parts = _run(wt, seqs, [[(2, 0, 0, 3)], [(2, 0, 3, 6)],
                               [(2, 0, 9, 1)], [(2, 0, 10, 6)]], impl)
    for a, b in zip(whole, parts):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # slots without a token kept what they had: zeros
    assert np.all(np.asarray(parts[1][1, :2]) == 0)
    assert np.abs(np.asarray(parts[1][1, 2])).max() > 0.1


def test_kernel_is_the_associative_scan_on_one_state(world):
    """Both paths from the SAME non-zero stored state, with padding
    behind the runs and a tick narrower than a chunk."""
    wt, seqs, _ = world
    _, state = _run(wt, seqs, [[(0, 0, 0, 9), (1, 1, 0, 4)]], "gather")
    tick = [[(1, 1, 4, 3), (0, 0, 9, 2), (2, 2, 0, 4)]]
    a, sa = _run(wt, seqs, tick, "gather", state=state)
    b, sb = _run(wt, seqs, tick, "pallas_interpret", state=state)
    for key in a:
        np.testing.assert_allclose(a[key][1], b[key][1], atol=1e-5)
    np.testing.assert_allclose(np.asarray(sa[1]), np.asarray(sb[1]),
                               atol=1e-5)


def test_marks_by_hand():
    slot = jnp.array([2, 2, 2, 0, 1, 1, 0, 0], jnp.int32)
    pos = jnp.array([0, 1, 2, 7, 3, 4, 0, 0], jnp.int32)
    valid = jnp.array([1, 1, 1, 1, 1, 1, 0, 0], bool)
    start = jnp.array([7, 3, 0], jnp.int32)
    last = jnp.array([3, 5, 2], jnp.int32)
    offset, first, lastm, has = ssm.segment_marks(slot, pos, valid, start,
                                                  last)
    assert has.tolist() == [True, True, True]
    assert offset.tolist() == [0, 1, 2, 0, 0, 1, 0, 0]
    # slot 2 starts its sequence (2), slots 0 and 1 continue (1)
    assert first.tolist() == [2, 0, 0, 1, 1, 0, 0, 0]
    assert lastm.tolist() == [0, 0, 1, 1, 0, 1, 0, 0]


def test_kernel_refuses_a_width_off_the_lanes():
    z = jnp.zeros
    with pytest.raises(ValueError, match="128-lane"):
        ssm.selective_scan_ragged(
            z((8, 96)), z((8, 96)), z((N, 96)), z((8, N)), z((8, N)),
            z((96,)), z((8,), jnp.int32), z((8,), bool),
            z((B,), jnp.int32),
            ssm.Marks(z((8,), jnp.int32), z((8,), jnp.int32),
                      z((8,), jnp.int32), z((B,), bool)),
            z((1, B, N, 96)), 0, impl="pallas_interpret")
