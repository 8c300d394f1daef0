"""`phi4flash.scatter_rows`, the merged-rows families' row write (scope
`kv_write`), against a plain loop over tokens, layers and rows kept
here: the three families' rows a token and layers a call, a decode tick
and a chunk, rows with `valid` false, a position on a page's last row,
both pool dtypes. Every page but the scratch page (`num_pages - 1`,
where invalid rows go and may collide) is equal bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.phi4flash import scatter_rows

PAGE, PAGES, SLOTS, WIDTH, W = 4, 40, 6, 5, 128

# rows a token, layers a call
GEOMETRIES = {"phi4flash": (10, 1), "smallthinker": (4, 9),
              "nemotron_h": (2, 2)}


def _tick(kind, rng):
    """(slot, position, valid) a token. decode: a token a slot, one on
    its page's last row, two slots idle. chunk: three decode rows, a run
    of 11 tokens across three pages, and an invalid tail."""
    if kind == "decode":
        slot = np.arange(SLOTS)
        pos = rng.integers(0, WIDTH * PAGE, SLOTS)
        pos[1] = 2 * PAGE - 1
        valid = np.ones(SLOTS, bool)
        valid[[2, 4]] = False
    else:
        runs = [(0, 7, 1), (1, PAGE - 1, 1), (2, 13, 1), (3, 5, 11)]
        slot = np.concatenate([np.full(m, s) for s, _, m in runs]
                              + [np.zeros(6, int)])
        pos = np.concatenate([np.arange(p, p + m) for _, p, m in runs]
                             + [np.arange(6)])
        valid = np.arange(slot.size) < slot.size - 6
    return slot.astype(np.int32), pos.astype(np.int32), valid


def _plain(pool, rows, own, pos, valid):
    """The write, one row at a time; an invalid token writes nothing."""
    out = pool.copy()
    kvh = rows.shape[2]
    for n in range(rows.shape[1]):
        if not valid[n]:
            continue
        page, row = own[n, pos[n] // PAGE], pos[n] % PAGE
        for layer in range(rows.shape[0]):
            for h in range(kvh):
                out[layer, page, row * kvh + h] = rows[layer, n, h]
    return out


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("kind", ["decode", "chunk"])
@pytest.mark.parametrize("family", list(GEOMETRIES))
def test_rows_land_where_a_plain_loop_puts_them(family, kind, dtype):
    kvh, layers = GEOMETRIES[family]
    rng = np.random.default_rng(kvh + (kind == "chunk"))
    tables = rng.permutation(PAGES - 1)[:SLOTS * WIDTH].reshape(
        SLOTS, WIDTH).astype(np.int32)
    slot, pos, valid = _tick(kind, rng)
    key = jax.random.PRNGKey(layers)
    pool = jax.random.normal(key, (layers, PAGES, PAGE * kvh, W), dtype)
    rows = jax.random.normal(jax.random.fold_in(key, 1),
                             (layers, slot.size, kvh, W), dtype)
    own = tables[slot]
    got = jax.jit(scatter_rows)(pool, rows, jnp.asarray(own),
                                jnp.asarray(pos), jnp.asarray(valid))
    assert got.shape == pool.shape and got.dtype == pool.dtype
    want = _plain(np.asarray(pool), np.asarray(rows), own, pos, valid)
    np.testing.assert_array_equal(_bits(got)[:, :-1], _bits(want)[:, :-1])
    # something was written, and not on the scratch page alone
    assert (_bits(got)[:, :-1] != _bits(pool)[:, :-1]).any()


def test_narrow_rows_are_padded_to_the_pools_lanes():
    """Rows of 64 in a pool 128 wide (`_fit_lanes`), float32 rows into a
    bf16 pool: the lanes past the row are zero, the cast is the pool's."""
    kvh, layers = 2, 2
    rng = np.random.default_rng(0)
    tables = rng.permutation(PAGES - 1)[:SLOTS * WIDTH].reshape(
        SLOTS, WIDTH).astype(np.int32)
    slot, pos, valid = _tick("chunk", rng)
    pool = jnp.ones((layers, PAGES, PAGE * kvh, W), jnp.bfloat16)
    rows = jax.random.normal(jax.random.PRNGKey(3),
                             (layers, slot.size, kvh, 64), jnp.float32)
    own = tables[slot]
    got = scatter_rows(pool, rows, jnp.asarray(own), jnp.asarray(pos),
                       jnp.asarray(valid))
    wide = jnp.pad(rows, [(0, 0)] * 3 + [(0, W - 64)]).astype(jnp.bfloat16)
    want = _plain(np.asarray(pool), np.asarray(wide), own, pos, valid)
    np.testing.assert_array_equal(_bits(got)[:, :-1], _bits(want)[:, :-1])
