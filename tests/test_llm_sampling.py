"""engine._sample: the tokens it draws and the set it draws them from.

The oracle is the body `_sample` had until PR 28 (argsort, a gather of
the sorted logits, a scatter of the keep mask back to vocabulary order),
kept here word for word: the new body must return its tokens for the same
logits, keys and parameters, ties on the cut included.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm._internal.engine import (EngineConfig,
                                          InferenceEngine, Request,
                                          SamplingParams, _kept_tokens,
                                          _row_sample_keys, _sample)
from ray_tpu.models import llama


def _oracle(logits, key, temps, top_ps, top_ks=None, rep_pens=None,
            seen=None, all_greedy=False, row_keys=None):
    if rep_pens is not None and seen is not None:
        pen = jnp.where(logits > 0,
                        logits / rep_pens[:, None],
                        logits * rep_pens[:, None])
        logits = jnp.where(seen, pen, logits)
    greedy = jnp.argmax(logits, axis=-1)
    if all_greedy:
        return greedy.astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sort_idx = jnp.argsort(-scaled, axis=-1)
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    if top_ks is not None:
        rank = jnp.arange(logits.shape[-1])[None, :]
        sorted_logits = jnp.where(
            (top_ks[:, None] > 0) & (rank >= top_ks[:, None]),
            -jnp.inf, sorted_logits)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = ((cum - probs) < top_ps[:, None]) \
        & jnp.isfinite(sorted_logits)
    keep = jnp.zeros_like(keep_sorted).at[
        jnp.arange(logits.shape[0])[:, None], sort_idx].set(keep_sorted)
    filtered = jnp.where(keep, scaled, -jnp.inf)
    if row_keys is not None:
        sampled = jax.vmap(jax.random.categorical)(row_keys, filtered)
    else:
        sampled = jax.random.categorical(key, filtered, axis=-1)
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


# how a caller hands over keys and penalties: the pipeline-stage programs
# (shared key, no top-k), the tick programs (a key a row, every
# parameter), and the two between
MODES = {
    "shared": dict(rows=False, top_k=False, rep=False),
    "shared+topk+rep": dict(rows=False, top_k=True, rep=True),
    "rows+topk": dict(rows=True, top_k=True, rep=False),
    "rows+topk+rep": dict(rows=True, top_k=True, rep=True),
}


@functools.lru_cache(maxsize=None)
def _programs(mode):
    """(new, oracle) jitted once a mode; a shape compiles on first use."""
    m = MODES[mode]

    def call(fn, logits, key, temps, top_ps, top_ks, rep_pens, seen,
             seeds, idx):
        return fn(logits, key, temps, top_ps,
                  top_ks if m["top_k"] else None,
                  rep_pens if m["rep"] else None,
                  seen if m["rep"] else None, False,
                  row_keys=_row_sample_keys(seeds, idx)
                  if m["rows"] else None)
    return (jax.jit(functools.partial(call, _sample)),
            jax.jit(functools.partial(call, _oracle)))


def _inputs(b, v, seed):
    """Logits on a grid of 0.25 (so that equal logits sit on the cut),
    a third of the tokens seen, penalties 1.0-1.6."""
    rng = np.random.default_rng(seed)
    logits = np.round(rng.normal(0.0, 2.0, (b, v)) * 4) / 4
    return dict(
        logits=jnp.asarray(logits, jnp.float32),
        rep_pens=jnp.asarray(rng.uniform(1.0, 1.6, b), jnp.float32),
        seen=jnp.asarray(rng.random((b, v)) < 0.33),
        seeds=jnp.asarray(rng.integers(0, 2**31 - 1, b), jnp.int32))


def _tokens_equal_the_oracle(x, top_p, top_k, temp, mode):
    new, oracle = _programs(mode)
    b, v = x["logits"].shape
    args = dict(
        x, temps=jnp.full((b,), temp, jnp.float32),
        top_ps=jnp.full((b,), top_p, jnp.float32),
        top_ks=jnp.full((b,), top_k, jnp.int32))
    for draw in range(4):
        key = jax.random.PRNGKey(1000 * draw + int(np.max(top_k)))
        idx = jnp.arange(b, dtype=jnp.int32) + 17 * draw
        got = np.asarray(new(key=key, idx=idx, **args))
        want = np.asarray(oracle(key=key, idx=idx, **args))
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32 and (0 <= got).all() and (got < v).all()


@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("temp", [0.0, 0.7, 5.0])
@pytest.mark.parametrize("top_k", [0, 1, 5, 50])
@pytest.mark.parametrize("top_p", [0.1, 0.5, 0.9, 1.0])
@pytest.mark.parametrize("b,v", [(4, 1000), (6, 2048), (8, 4096)])
def test_tokens_equal_the_gather_and_scatter_oracle(b, v, top_p, top_k,
                                                    temp, mode):
    _tokens_equal_the_oracle(_inputs(b, v, seed=v + b), top_p, top_k,
                             temp, mode)


def _cell_rows(b, v, seed):
    """Row 0 on the grid of 0.25 (thousands of equal logits on the cut),
    the others the benchmark's own: near N(0, 1) off any grid, about two
    fifths of a row kept at temperature 0.7 and top_p 0.9."""
    x = _inputs(b, v, seed)
    rng = np.random.default_rng(seed + 1)
    logits = np.array(x["logits"])
    logits[1:] = rng.normal(0.0, 1.0, (b - 1, v))
    return dict(x, logits=jnp.asarray(logits, jnp.float32))


def _signed_zeros_on_the_cut(b, v, seed):
    """Most of each row is one logit, 0, written +0.0 and -0.0 by turns
    (one key, as lax.sort has them), under a few larger ones: every cut
    of top-k and of top-p falls among the zeros and is settled by id."""
    x = _inputs(b, v, seed)
    rng = np.random.default_rng(seed + 1)
    logits = np.where(rng.random((b, v)) < 0.5, 0.0, -0.0)
    logits[:, ::97] = np.round(rng.normal(2.0, 1.0, (b, len(
        range(0, v, 97)))) * 4) / 4
    logits[:, 5::89] = -1.5
    return dict(x, logits=jnp.asarray(logits, jnp.float32),
                rep_pens=jnp.ones((b,), jnp.float32))


def _masked_vocabulary(b, v, seed):
    """All but one id in 23 at -inf, as a grammar or an adapter's
    vocabulary masks a row; one row keeps three ids, one a single id."""
    x = _inputs(b, v, seed)
    rng = np.random.default_rng(seed + 1)
    logits = np.array(x["logits"])
    logits[rng.random((b, v)) < 22 / 23] = -np.inf
    logits[0] = -np.inf
    logits[0, [7, v // 2, v - 1]] = [1.0, 1.0, 0.25]
    logits[1] = -np.inf
    logits[1, v - 3] = -2.0
    return dict(x, logits=jnp.asarray(logits, jnp.float32))


# the four serving cells' row widths (16,160 and 25,024 are no multiple
# of 128) and the rows no random draw holds
ROWS = {
    "dsv3-longchat": (2, 16160, _cell_rows),
    "trinity-mixed": (2, 25024, _cell_rows),
    "chat-open": (2, 92544, _cell_rows),
    "phi4flash-reason": (2, 200064, _cell_rows),
    "signed-zeros-on-the-cut": (4, 3000, _signed_zeros_on_the_cut),
    "masked-vocabulary": (4, 5003, _masked_vocabulary),
}


@pytest.mark.parametrize("mode", ["rows+topk", "rows+topk+rep", "shared"])
@pytest.mark.parametrize("top_k", [0, 50, "some-rows"])
@pytest.mark.parametrize("rows", sorted(ROWS))
def test_tokens_equal_the_oracle_at_the_cells_rows(rows, top_k, mode):
    b, v, make = ROWS[rows]
    if top_k == "some-rows":        # one batch: rows that set it, rows
        top_k = jnp.asarray([50, 0, 3, 0][:b], jnp.int32)   # that do not
    _tokens_equal_the_oracle(make(b, v, seed=v), 0.9, top_k, 0.7, mode)


def test_rows_of_one_batch_keep_their_own_parameters():
    """Every row its own temperature, top-p, top-k and penalty, greedy
    rows among sampled ones: still the oracle's tokens."""
    b, v = 8, 3000
    new, oracle = _programs("rows+topk+rep")
    x = _inputs(b, v, seed=5)
    args = dict(
        x, temps=jnp.asarray([0.0, 0.7, 5.0, 1.0, 0.0, 0.3, 2.0, 0.7]),
        top_ps=jnp.asarray([0.9, 0.1, 1.0, 0.5, 0.5, 0.9, 0.0, 1e-9]),
        top_ks=jnp.asarray([0, 5, 50, 1, 3, v + 7, 0, 0], jnp.int32))
    for draw in range(8):
        key = jax.random.PRNGKey(draw)
        idx = jnp.full((b,), draw, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(new(key=key, idx=idx, **args)),
            np.asarray(oracle(key=key, idx=idx, **args)))


def _kept_reference(scaled, top_p, top_k):
    """float64: a stable sort by falling logit, the first top_k ranks,
    then the shortest prefix whose probabilities (renormalised over what
    top-k left) reach top_p. Returns, in vocabulary order, (keep, how far
    the probability in front of each token is from top_p: float32 may put
    a token nearer than 1e-5 on either side)."""
    scaled = np.asarray(scaled, np.float64)
    order = np.argsort(-scaled, kind="stable")
    s = scaled[order]
    if top_k > 0:
        s = np.where(np.arange(s.size) < top_k, s, -np.inf)
    p = np.exp(s - s.max())
    p /= p.sum()
    before = np.cumsum(p) - p
    keep_sorted = (before < top_p) & np.isfinite(s)
    keep = np.zeros(s.size, bool)
    keep[order] = keep_sorted
    dist = np.empty(s.size)
    # what top-k cut is dropped wherever top_p falls
    dist[order] = np.where(np.isfinite(s), np.abs(before - top_p), np.inf)
    return keep, dist


@pytest.mark.parametrize("quantised", [False, True])
@pytest.mark.parametrize("top_k", [0, 1, 5, 50, 5000])
@pytest.mark.parametrize("top_p", [0.1, 0.5, 0.9, 1.0])
def test_kept_set_is_the_float64_reference(top_p, top_k, quantised):
    b, v = 8, 2048
    rng = np.random.default_rng(top_k + int(10 * top_p))
    scaled = rng.normal(0.0, 2.0, (b, v))
    if quantised:
        scaled = np.round(scaled * 4) / 4
    scaled = scaled.astype(np.float32)
    keep = np.asarray(jax.jit(_kept_tokens)(
        jnp.asarray(scaled), jnp.full((b,), top_p, jnp.float32),
        jnp.full((b,), top_k, jnp.int32)))
    for row in range(b):
        want, dist = _kept_reference(scaled[row], top_p, top_k)
        clear = dist > 1e-5
        assert clear.mean() > 0.7
        np.testing.assert_array_equal(keep[row][clear], want[clear])
        assert keep[row].sum() <= (top_k or v)
        # a prefix of the stable order: no token kept below one dropped
        order = np.argsort(-scaled[row], kind="stable")
        assert not np.diff(keep[row][order].astype(int)).max() > 0


CHI2_999 = {1: 10.83, 2: 13.82, 3: 16.27, 4: 18.47, 5: 20.52, 6: 22.46,
            7: 24.32, 8: 26.12, 9: 27.88, 10: 29.59, 11: 31.26, 12: 32.91,
            13: 34.53, 14: 36.12, 15: 37.70}


@pytest.mark.parametrize("rows", [False, True])
@pytest.mark.parametrize("temp,top_p,top_k", [
    (1.0, 1.0, 0), (0.7, 0.8, 0), (1.0, 1.0, 5), (2.0, 0.9, 8),
    (1.5, 0.9, 3)])
def test_draws_follow_the_renormalised_probabilities(temp, top_p, top_k,
                                                     rows):
    """20,000 draws of one 16-token row against the probabilities of the
    kept tokens, renormalised: chi-square under its 99.9% point."""
    n, v = 20000, 16
    rng = np.random.default_rng(7)
    row = np.round(rng.normal(0.0, 1.5, v) * 4) / 4
    logits = jnp.asarray(np.tile(row, (n, 1)), jnp.float32)
    toks = np.asarray(jax.jit(_sample)(
        logits, jax.random.PRNGKey(3), jnp.full((n,), temp),
        jnp.full((n,), top_p), jnp.full((n,), top_k, jnp.int32),
        row_keys=_row_sample_keys(jnp.arange(n, dtype=jnp.int32),
                                  jnp.zeros(n, jnp.int32))
        if rows else None))
    keep, dist = _kept_reference(row / temp, top_p, top_k)
    assert dist.min() > 1e-5
    p = np.where(keep, np.exp(row / temp - (row / temp).max()), 0.0)
    p /= p.sum()
    counts = np.bincount(toks, minlength=v)
    assert counts[~keep].sum() == 0
    chi2 = (((counts - n * p) ** 2)[keep] / (n * p[keep])).sum()
    df = int(keep.sum()) - 1
    assert df >= 1 and chi2 < CHI2_999[df], (chi2, df)


@pytest.mark.parametrize("quantised", [False, True])
def test_kept_set_at_the_ends_of_top_p_and_top_k(quantised):
    b, v = 6, 1000
    rng = np.random.default_rng(3)
    scaled = rng.normal(0.0, 2.0, (b, v))
    if quantised:      # several tokens share the largest logit
        scaled = np.minimum(np.round(scaled * 4) / 4, 4.0)
    scaled = jnp.asarray(scaled, jnp.float32)
    kept = jax.jit(_kept_tokens)
    ones = jnp.ones((b,), jnp.float32)
    no_k = jnp.zeros((b,), jnp.int32)
    first_max = np.asarray(jnp.argmax(scaled, axis=-1))

    # top_p -> 0: rank 0 alone, the lowest id among equal largest logits
    for top_p in (1e-9, 1e-4):
        keep = np.asarray(kept(scaled, top_p * ones, no_k))
        assert (keep.sum(-1) == 1).all()
        assert (keep.argmax(-1) == first_max).all()
    # so a draw at any temperature is the argmax
    toks = jax.jit(_sample)(scaled, jax.random.PRNGKey(0), 5.0 * ones,
                            1e-9 * ones)
    assert (np.asarray(toks) == first_max).all()
    # top_p 0 keeps nothing, as before: every logit -inf, token 0
    assert not np.asarray(kept(scaled, 0.0 * ones, no_k)).any()
    # top_p 1 keeps everything; top_k over V is top_k off
    assert np.asarray(kept(scaled, ones, no_k)).all()
    assert np.asarray(kept(scaled, ones, no_k + v + 1)).all()
    assert np.asarray(kept(scaled, ones, None)).all()
    # top_k 1 is rank 0 alone whatever top_p says
    keep = np.asarray(kept(scaled, ones, no_k + 1))
    assert (keep.sum(-1) == 1).all() and (keep.argmax(-1) == first_max).all()
    # a row of -inf alone keeps nothing and draws token 0
    dead = jnp.full((b, v), -jnp.inf)
    assert not np.asarray(kept(dead, ones, no_k)).any()


def test_all_greedy_never_sorts():
    text = jax.jit(lambda l, k: _sample(
        l, k, jnp.ones(2), jnp.ones(2), all_greedy=True)).lower(
            jnp.zeros((2, 64)), jax.random.PRNGKey(0)).as_text()
    assert "stablehlo.sort" not in text


@pytest.mark.parametrize("top_ks", [False, True])
@pytest.mark.parametrize("rows", [False, True])
def test_sampled_never_sorts_gathers_or_scatters(rows, top_ks):
    """The mechanism, where no ledger line can see it: a sampled program
    holds a loop of compares and row sums, and of a sort, a gather or a
    scatter over the row nothing."""
    b, v = 4, 1000

    def sampled(logits, key, top_k, seeds):
        return _sample(
            logits, key, jnp.full((b,), 0.7), jnp.full((b,), 0.9),
            top_k if top_ks else None,
            row_keys=_row_sample_keys(seeds, seeds) if rows else None)
    text = jax.jit(sampled).lower(
        jnp.zeros((b, v)), jax.random.PRNGKey(0),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32)).as_text()
    for op in ("stablehlo.sort", "stablehlo.gather", "stablehlo.scatter",
               "chlo.top_k", "stablehlo.custom_call"):
        assert op not in text, op
    # the passes are a loop's trips, not copies of its body, and the
    # top-k cut is a trip of the loop that makes the top-p cut (it starts
    # there where no row sets top_k), not a second copy of the bisection
    assert "stablehlo.while" in text
    assert text.count("stablehlo.reduce") <= 8
    assert "stablehlo.case" not in text


def test_top_k_is_no_static_argument_of_the_tick_programs():
    """An engine builds the programs for a token bucket that it built:
    one sampled ragged program a (tokens, context) bucket and one decode
    program, whether a request sets top_k or none does."""
    eng = InferenceEngine(EngineConfig(
        model=llama.config("debug", dtype=jnp.float32), max_batch_size=3,
        page_size=8, num_pages=64, max_prefill_tokens=16, seed=9,
        enable_prefix_caching=False))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, 250, n).tolist() for n in (12, 12, 7)]

    def serve(tag, **sp):
        reqs = [Request(f"{tag}{i}", list(p), SamplingParams(
            max_tokens=6, temperature=0.7, top_p=0.9, **sp))
            for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        while eng.has_work():
            eng.step()
        return [r.output_tokens for r in reqs]

    plain = serve("a")
    built = (eng.compiles, sorted(eng._ragged_fns))
    assert all(not greedy for _, _, greedy in eng._ragged_fns)
    limited = serve("b", top_k=2)
    mixed = serve("c", top_k=0)
    assert (eng.compiles, sorted(eng._ragged_fns)) == built
    for fn in [eng._decode_fn, *eng._ragged_fns.values()]:
        assert fn._cache_size() == 1
    assert all(len(t) == 6 for t in plain + limited + mixed)
