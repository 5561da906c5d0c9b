"""ops/live_rows.py: a position-wise function walked over the leading
chunks that hold a token equals the bare function there and leaves zeros
past them, whatever the rows' lengths and whether or not the chunk divides
the rows; rows of one chunk are the bare function, text-equal; the host
count of what a program walks."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import live_rows

C = 8


def _fn(w):
    """Position-wise, of two arrays, to a pytree of two; the position
    itself enters (as a rotary half's does)."""
    def fn(arrays, first):
        x, y = arrays
        at = jnp.arange(x.shape[1]) + (0 if first is None else first)
        h = jnp.tanh(x @ w) + at[None, :, None]
        return {"a": h.reshape(*h.shape[:2], 2, -1),
                "b": (y * 2 + 1).astype(jnp.bfloat16)}
    return fn


def _case(b, T, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, T, 6)),
            jax.random.normal(ks[1], (b, T, 3, 2)),
            jax.random.normal(ks[2], (6, 4)))


@pytest.mark.parametrize("T", [32, 29], ids=["T_4C", "T_3C_and_5"])
@pytest.mark.parametrize("lens", [[0], [1], [C], [C + 1], ["T"],
                                  [3, C + 1], ["T", 2], [0, 0]],
                         ids=lambda v: "lens_" + "_".join(map(str, v)))
def test_walked_rows_equal_the_bare_function_and_zeros_follow(T, lens):
    lens = [T if n == "T" else n for n in lens]
    x, y, w = _case(len(lens), T)
    fn = _fn(w)
    n_live = jnp.max(jnp.asarray(lens, jnp.int32))
    got = jax.jit(lambda x, y, n: live_rows.walk(fn, (x, y), n, C))(
        x, y, n_live)
    want = fn((x, y), None)
    done = min(T, -(-max(lens) // C) * C)
    assert done == (T if T <= C else min(T, live_rows.walked(
        T, max(lens), C)))
    for name in ("a", "b"):
        g, wnt = np.asarray(got[name], np.float32), \
            np.asarray(want[name], np.float32)
        assert g.shape == wnt.shape and got[name].dtype == want[name].dtype
        np.testing.assert_allclose(g[:, :done], wnt[:, :done], rtol=1e-6,
                                   atol=1e-6)
        # a chunk that does not divide T: the last one starts at T - C, so
        # a walk that reaches it has written up to T
        assert not g[:, done:].any()


@pytest.mark.parametrize("T", [C, 5, 1])
def test_rows_of_one_chunk_lower_to_the_bare_function(T):
    """Nothing but the static shape decides: T <= C adds no line to the
    program, not even the count's."""
    x, y, w = _case(2, T)
    fn = _fn(w)

    def bare(x, y, lens):
        return fn((x, y), None)

    def walked(x, y, lens):
        return live_rows.walk(fn, (x, y), jnp.max(lens), C)

    lens = jnp.asarray([T, 1], jnp.int32)
    texts = [jax.jit(f).lower(x, y, lens).as_text().replace(
        f.__name__, "f") for f in (bare, walked)]
    assert texts[0] == texts[1]
    assert "while" not in texts[1]
    looped = jax.jit(walked).lower(*_case(2, C + 1)[:2], lens).as_text()
    assert "stablehlo.while" in looped


def test_the_trip_count_is_the_devices():
    """One program for every length: the count is an operand, and a
    Python count (whole rows) walks them all."""
    x, y, w = _case(1, 4 * C)
    fn = _fn(w)
    walk = jax.jit(lambda x, y, n: live_rows.walk(fn, (x, y), n, C))
    short, full = walk(x, y, jnp.int32(3)), walk(x, y, jnp.int32(4 * C))
    assert walk._cache_size() == 1
    assert not np.asarray(short["a"][:, C:]).any()
    np.testing.assert_array_equal(np.asarray(short["a"][:, :C]),
                                  np.asarray(full["a"][:, :C]))
    whole = live_rows.walk(fn, (x, y), 4 * C, C)
    np.testing.assert_array_equal(np.asarray(whole["a"]),
                                  np.asarray(full["a"]))


def test_count_is_one_past_the_last_live_position():
    live = jnp.asarray([[1, 1, 0, 0, 0], [1, 0, 0, 1, 0], [0, 0, 0, 0, 0]],
                       bool)
    assert int(live_rows.count(live)) == 4
    assert int(live_rows.count(live[2:])) == 0
    assert int(live_rows.count(jnp.ones((2, 7), bool))) == 7


@pytest.mark.parametrize("lens,bucket,want", [
    ([6144], 8192, 6144), ([6145], 8192, 7168), ([4097, 8192], 8192, 16384),
    ([1], 8192, 1024), ([300, 17], 1024, 2048), ([5], 512, 512)])
def test_the_host_count_of_what_a_program_walks(lens, bucket, want):
    """rows x the chunks under the longest true length above a chunk, rows
    x bucket at or under it."""
    work, shown = live_rows.prefill_work(np.asarray(lens), bucket)
    assert work == {"prefill_walked_tokens": want}
    assert shown == {"walked_tokens": want}
    assert set(work) == set(live_rows.COUNTERS)
