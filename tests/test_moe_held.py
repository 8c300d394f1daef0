"""`ops/moe.held_experts_ffn`: one grouped product over the tick's
assignments sorted by held expert, against a plain loop over tokens and
picks. Both ways off the chip: `lax.ragged_dot` over the sorted rows
("gather", what a CPU engine runs) and the Pallas kernels interpreted
("pallas_interpret", the logic the chip runs; its tiling is held by
tests/test_tpu_aot_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_experts as ge
from ray_tpu.ops import moe

E_ALL, HELD, PICKS, H, F = 32, 8, 4, 32, 24
IMPLS = ["gather", "pallas_interpret"]


def _weights(rng, e=HELD, h=H, f=F):
    wg, wi = (jnp.asarray(rng.normal(size=(e, h, f)) * h ** -0.5,
                          jnp.float32) for _ in range(2))
    return wg, wi, jnp.asarray(rng.normal(size=(e, f, h)) * f ** -0.5,
                               jnp.float32)


# each form's activation over its up products, in float64; the ungated
# one has wg alone as its up matrix
ACTS = {"swiglu": lambda g, u: g / (1 + np.exp(-g)) * u,
        "reglu": lambda g, u: np.maximum(g, 0) * u,
        "relu2": lambda g, u: np.maximum(g, 0) ** 2}


def _ffn(x, gates, took, wg, wi, wd, act="swiglu", **kw):
    """`held_experts_ffn` on matrices kept in by out: the ungated form's
    up matrix is handed over out by in, as it is stored."""
    ups = (jnp.swapaxes(wg, 1, 2),) if act == "relu2" else (wg, wi)
    return moe.held_experts_ffn(x, gates, took, ups, wd, act=act, **kw)


def _loop(x, idx, w, valid, wg, wi, wd, act="swiglu"):
    """Token by token, pick by pick, in float64."""
    x, wg, wi, wd = (np.asarray(a, np.float64) for a in (x, wg, wi, wd))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[t, j])
            if valid[t] and 0 <= e < wg.shape[0]:
                g, u = x[t] @ wg[e], x[t] @ wi[e]
                out[t] += w[t, j] * (ACTS[act](g, u) @ wd[e])
    return out


def _picks(rng, t, how):
    """(idx [T, PICKS] over E_ALL experts of which 0..HELD are held,
    valid [T]) for a named routing."""
    valid = np.ones(t, bool)
    idx = np.stack([rng.permutation(E_ALL)[:PICKS] for _ in range(t)])
    if how == "balanced":
        # every token's first pick is held, round robin
        idx[:, 0] = np.arange(t) % HELD
        idx[:, 1:] = HELD + np.stack(
            [rng.permutation(E_ALL - HELD)[:PICKS - 1] for _ in range(t)])
    elif how == "one_expert":
        idx[:, 0] = 3
        idx[:, 1:] = HELD + 1 + np.arange(PICKS - 1)
    elif how == "none_held":
        idx = HELD + np.stack(
            [rng.permutation(E_ALL - HELD)[:PICKS] for _ in range(t)])
    elif how == "padding":
        valid = rng.uniform(size=t) < 0.5
        valid[-(t // 4):] = False
    elif how == "all_held":
        # every pick of every token lands here: the row bound is met
        idx = np.stack([rng.permutation(HELD)[:PICKS] for _ in range(t)])
    return idx.astype(np.int32), valid


# SwiGLU through every size and routing; the other two forms share all
# but the up kernel's matrices and activation, so they take the cases
# that reach a branch of their own there: experts nobody picked beside
# one tile half full (8, one_expert), every row tile full (64 x 4 picks,
# all_held), two tiles with padding rows between the groups (96, padding)
CASES = ([(t, how, "swiglu") for t in (8, 64, 96, 512)
          for how in ("balanced", "one_expert", "none_held", "padding",
                      "random", "all_held")]
         + [(t, how, act) for act in ("reglu", "relu2")
            for t, how in ((8, "one_expert"), (64, "all_held"),
                           (96, "padding"))])


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("t,how,act", CASES)
def test_held_experts_ffn_is_the_loop_over_picks(t, how, act, impl):
    rng = np.random.default_rng(t)
    wg, wi, wd = _weights(rng)
    x = jnp.asarray(rng.normal(size=(t, H)), jnp.float32)
    idx, valid = _picks(rng, t, how)
    w = rng.uniform(0.05, 1.5, size=idx.shape).astype(np.float32)
    gates, took, counts = moe.held_gates(
        jnp.asarray(idx), jnp.asarray(w), 0, HELD, jnp.asarray(valid))
    landed = ((idx < HELD) & valid[:, None]).sum()
    assert int(counts.sum()) == landed == int(took.sum())
    got = _ffn(x, gates, took, wg, wi, wd, act, picks=PICKS, impl=impl)
    assert got.shape == (t, H) and got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), _loop(x, idx, w, valid, wg, wi, wd, act),
        rtol=2e-4, atol=2e-4)
    if how == "none_held":
        assert not np.asarray(got).any()


@pytest.mark.parametrize("impl", IMPLS)
def test_96_rows_on_one_expert_nothing_dropped(impl):
    """96 tokens that all pick expert 0 (once the case over the 64
    gathered rows that fell back to the every-row form): one group of
    96 rows, every one computed."""
    rng = np.random.default_rng(2)
    t, h, f, e = 96, 16, 8, 4
    x = jnp.asarray(rng.normal(size=(t, h)), jnp.float32)
    wg, wi = (jnp.asarray(rng.normal(size=(e, h, f)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(e, f, h)), jnp.float32)
    idx = jnp.zeros((t, 1), jnp.int32)
    gates, took, counts = moe.held_gates(idx, jnp.ones((t, 1)), 0, e)
    assert counts.tolist() == [96, 0, 0, 0]
    got = _ffn(x, gates, took, wg, wi, wd, picks=1, impl=impl)
    want = (jax.nn.silu(x @ wg[0]) * (x @ wi[0])) @ wd[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("t,picks", [(10, 3), (50, 3), (7, 1)])
def test_a_row_bound_that_is_no_multiple_of_the_tile(t, picks, impl):
    """T * picks = 30, 150, 7 rows: under one tile, over one and not a
    whole number of them, under a vector's 16 rows."""
    rng = np.random.default_rng(t)
    wg, wi, wd = _weights(rng)
    x = jnp.asarray(rng.normal(size=(t, H)), jnp.float32)
    idx = np.stack([rng.permutation(HELD + 2)[:picks] for _ in range(t)]
                   ).astype(np.int32)
    w = rng.uniform(0.05, 1.5, size=idx.shape).astype(np.float32)
    valid = np.ones(t, bool)
    gates, took, _ = moe.held_gates(jnp.asarray(idx), jnp.asarray(w), 0,
                                    HELD)
    got = _ffn(x, gates, took, wg, wi, wd, picks=picks, impl=impl)
    np.testing.assert_allclose(
        np.asarray(got), _loop(x, idx, w, valid, wg, wi, wd),
        rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("how", ["random", "one_expert", "none_held"])
def test_an_expert_nobody_picked_is_not_part_of_the_result(how):
    """The unpicked experts' matrices are NaN and the output is finite
    and equal to the clean one: the kernels' grid never fetches them
    (the every-row form multiplied them by a zero gate: NaN)."""
    rng = np.random.default_rng(5)
    t = 16
    wg, wi, wd = _weights(rng)
    x = jnp.asarray(rng.normal(size=(t, H)), jnp.float32)
    idx, valid = _picks(rng, t, how)
    valid[5:] = False                  # a decode tick's few live rows
    w = rng.uniform(0.05, 1.5, size=idx.shape).astype(np.float32)
    gates, took, counts = moe.held_gates(
        jnp.asarray(idx), jnp.asarray(w), 0, HELD, jnp.asarray(valid))
    unpicked = (np.asarray(counts) == 0)[:, None, None]
    assert unpicked.any()

    def run(*ws):
        return np.asarray(_ffn(x, gates, took, *ws, picks=PICKS,
                               impl="pallas_interpret"))

    clean = run(wg, wi, wd)
    dirty = run(*(jnp.where(unpicked, jnp.nan, a) for a in (wg, wi, wd)))
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("t,share", [(8, 0.1), (64, 0.5), (512, 0.3)])
def test_assignment_rows_are_the_stable_sort_by_expert(t, share):
    rng = np.random.default_rng(t)
    took = rng.uniform(size=(t, 16)) < share
    place, offsets = (np.asarray(a) for a in ge.assignment_rows(
        jnp.asarray(took)))
    es, ts = np.nonzero(took.T)                  # by expert, then token
    np.testing.assert_array_equal(place[ts, es], np.arange(len(es)))
    assert (place[~took] == -1).all()
    np.testing.assert_array_equal(np.diff(offsets), took.sum(0))


@pytest.mark.parametrize("sizes,tm", [
    ([0] * 16, 128), ([3, 0, 0, 1] + [0] * 12, 128), ([96] + [0] * 15, 128),
    ([130, 126, 0, 256] + [1] * 12, 128), ([16] * 16, 16), ([5, 20, 7], 16)])
def test_tile_visits_are_the_tile_group_pairs_that_share_a_row(sizes, tm):
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    rows = max(-(-int(offsets[-1]) // tm), 1) * tm + tm
    vis_g, vis_t, n = ge.tile_visits(jnp.asarray(offsets), rows, tm)
    want = [(tile, g) for tile in range(rows // tm)
            for g in range(len(sizes))
            if max(offsets[g], tile * tm) < min(offsets[g + 1],
                                                (tile + 1) * tm)]
    n = int(n)
    assert n == len(want) <= rows // tm + len(sizes) - 1 == len(vis_g)
    assert list(zip(np.asarray(vis_t)[:n].tolist(),
                    np.asarray(vis_g)[:n].tolist())) == want
    assert (np.asarray(vis_t) < rows // tm).all()
    assert (np.asarray(vis_g) < len(sizes)).all()


@pytest.mark.parametrize("impl", ["auto", "", "xla", None])
def test_an_impl_that_is_none_of_the_three_is_refused(impl):
    """No value picks a path by not being another: the caller resolves
    `impl` (the engine's `_resolve_impl`, `platform_impl`) and a name
    the function does not know raises."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, H)), jnp.float32)
    gates, took, _ = moe.held_gates(
        jnp.zeros((8, PICKS), jnp.int32), jnp.ones((8, PICKS), jnp.float32),
        0, HELD)
    with pytest.raises(ValueError, match="impl"):
        _ffn(x, gates, took, *_weights(rng), picks=PICKS, impl=impl)


def test_platform_impl_is_what_the_engine_resolves_auto_to():
    """A check that calls one `moe_block` with no engine gets the
    engine's answer to `decode_impl="auto"`, by the same question."""
    from ray_tpu.llm._internal.engine import EngineConfig, InferenceEngine

    class Auto:
        config = EngineConfig(decode_impl="auto")
    assert moe.platform_impl() == InferenceEngine._resolve_impl(Auto())
    assert moe.platform_impl() in moe.HELD_IMPLS
