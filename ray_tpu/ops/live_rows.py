"""Position-wise work over the positions that hold a token.  A prefill
program's rows are right-padded to a length bucket; what is computed a
position alone (a norm, a projection, a rotary half, a feed-forward) is
worth computing only up to the longest row's true length.  `walk` runs
such a function over the leading chunks of `chunk` positions, by a loop
whose trip count the device holds, and leaves zeros past them.

Nothing but the call's static shape decides the form: rows of at most one
chunk (every decode call, every short bucket) are the bare function,
text-equal; longer rows loop.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# positions a trip of the loop computes (PERF.md section 5, PR 53: chosen
# on the chip over the 1 x 8192 program of `models/mimo_v2.py`)
CHUNK = 1024

# What a serving module whose prefill walks its position-wise halves
# reports of it (models/serving.ServingSpec.counters): over
# prefill_true_tokens, the positions computed a true prompt token.
COUNTERS = {
    "prefill_walked_tokens": "Token positions the position-wise halves of "
                             "the full-prompt prefill programs computed "
                             "(rows x the chunks under the longest row's "
                             "true length; rows x bucket where a bucket "
                             "is one chunk)",
}


def walked(bucket: int, longest: int, chunk: int | None = None) -> int:
    """Positions a row `walk` computes of rows padded to `bucket` whose
    longest holds `longest` (host arithmetic)."""
    chunk = chunk or CHUNK
    return bucket if bucket <= chunk else -(-int(longest) // chunk) * chunk


def prefill_work(true_lens, bucket: int) -> tuple[dict, dict]:
    """`ServingSpec.prefill_work`'s part of a program of len(true_lens)
    rows padded to `bucket` whose position-wise halves `walk`."""
    n = len(true_lens) * walked(bucket, max(true_lens))
    return {"prefill_walked_tokens": n}, {"walked_tokens": n}


def count(live) -> jnp.ndarray:
    """live [b, T] bool -> int32 scalar: one past the last position any
    row holds."""
    T = live.shape[1]
    return jnp.max(jnp.where(live, jnp.arange(1, T + 1, dtype=jnp.int32), 0))


def walk(fn, arrays, n_live, chunk: int | None = None):
    """fn(chunk of `arrays`, first) -> pytree of [b, C, ...], computed a
    position alone, over `arrays` (a pytree of [b, T, ...]) up to
    position `n_live` (int32 scalar on the device: the longest row's true
    length).  `first` is the position the chunk starts at, None where the
    chunk is the whole row.  Returns the pytree of [b, T, ...].

    T <= `chunk` (CHUNK): `fn(arrays, None)`, nothing added.  Longer rows:
    `lax.fori_loop` over cdiv(n_live, chunk) chunks, sliced out of
    `arrays` and written into zero-initialised outputs that the loop
    carries; positions at or past cdiv(n_live, chunk) x chunk are zeros.
    Where `chunk` does not divide T the last chunk starts at T - chunk:
    its leading positions are computed twice, to the same values.

    The loop reads and carries every array FLAT, [b, T, features] (PERF.md
    section 5, PR 53: heads that a kernel left heads-major are gathered
    once, whole, before the loop, and a chunk is then rows of a matrix;
    sliced and written as [b, C, heads, d] the 1 x 8192 program of
    `models/mimo_v2.py` ran 11 % longer).  Device-side name of the loop:
    `live_rows`."""
    chunk = chunk or CHUNK
    T = jax.tree.leaves(arrays)[0].shape[1]
    if T <= chunk:
        return fn(arrays, None)
    leaves, tree = jax.tree.flatten(arrays)
    flat = [a.reshape(a.shape[0], T, -1) for a in leaves]

    def rows(flat, first):
        return fn(jax.tree.unflatten(tree, [
            a.reshape(a.shape[:2] + whole.shape[2:])
            for a, whole in zip(flat, leaves)]), first)

    shapes = jax.eval_shape(
        rows, [jax.ShapeDtypeStruct((a.shape[0], chunk, a.shape[2]), a.dtype)
               for a in flat], jax.ShapeDtypeStruct((), jnp.int32))
    outs = jax.tree.map(lambda s: jnp.zeros(
        (s.shape[0], T, math.prod(s.shape[2:])), s.dtype), shapes)

    def body(i, outs):
        first = jnp.minimum(i * chunk, T - chunk)
        got = rows([lax.dynamic_slice_in_dim(a, first, chunk, axis=1)
                    for a in flat], first)
        return jax.tree.map(
            lambda o, g: lax.dynamic_update_slice_in_dim(
                o, g.reshape(o.shape[0], chunk, -1), first, axis=1),
            outs, got)

    trips = (jnp.asarray(n_live, jnp.int32) + (chunk - 1)) // chunk
    with jax.named_scope("live_rows"):
        outs = lax.fori_loop(0, trips, body, outs)
    return jax.tree.map(lambda o, s: o.reshape(o.shape[:2] + s.shape[2:]),
                        outs, shapes)
