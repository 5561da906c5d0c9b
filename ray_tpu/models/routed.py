"""The served routed-expert layer, shared by every serving module that
has one (`models/lfm2.py`, `models/mla_moe.py`, `models/glm5_next.py`,
`models/dots3_note.py`, `models/nemotron_h.py`, `models/mimo_v2.py`,
`models/cohere2_moe.py`, `models/solar_open2.py`): the router, the experts'
part over the dropless grouped matmul (`ops/grouped_matmul.gmm`) for the
range of experts a chip holds, and the shared expert beside them.

`cfg` is the serving module's config; it gives `n_experts` (the ROUTER's
width, every expert of the deployment), `top_k`, `moe_ffn_dim`,
`use_expert_bias`, `norm_topk_prob` and `routed_scaling`, and may give
`swiglu_limit` (a clamp on every expert's SwiGLU, `clamp`; absent or 0:
none).  The router
scores every expert and selects and normalises over all of them whatever
range is held: a chip that holds experts lo..hi computes THEIR part of
the sum, and the parts of disjoint ranges add up to the layer.  Two
things are the caller's to say (`routed_ffn`): an expert's FORM
(`swiglu_experts` over `w13` and `w2`, the default; `relu2_experts`, two
matrices around a squared relu), and the rows the router READS where
they are not the rows the experts multiply (a layer whose experts work in
a latent narrower than the stream the router scores).

What is sized by the assignment list is walked over its HELD head.  The
counting sort puts the rows of the experts this chip holds first and
nobody's last; `total`, the length of that head, is a value the device
holds.  The list is cut into static blocks of `BLOCK` rows and a loop of
`cdiv(total, BLOCK)` trips runs the gather, both grouped matmuls and the
SwiGLU on a block, and adds the block's weighted rows at their tokens'
rows: a chip that holds an eighth of the experts moves about an eighth of
the rows, at any skew, and drops none (every expert's rows on one chip:
every block).  A list of at most `BLOCK` rows (every decode program) is
one block with no loop.  Nothing but the call's static shape decides.

Device-side names: `moe_router`, `moe_experts`, `shared_expert`; the
grouped matmul's kernel is `moe_gmm`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.grouped_matmul import gmm, row_tile, visits, visits_static

COUNTS = 5      # entries of a routed layer's counts (`routed_ffn`)
# Rows of the sorted assignment list `routed_ffn` walks at a time (the
# movers alone on a v5e picked it: PERF.md section 5, PR 47).
BLOCK = 8192
# The most bytes of a float32 column strip the blocks' rows are added into.
ACC_BYTES = 32 << 20


def route(h2, lp, cfg):
    """h2 [T, d] -> (experts [T, k] int32, weights [T, k] float32)."""
    with jax.named_scope("moe_router"):
        s = jax.nn.sigmoid(jnp.dot(
            h2.astype(jnp.float32), lp["router"].astype(jnp.float32),
            precision=lax.Precision.HIGHEST))
        sel = s + lp["expert_bias"] if cfg.use_expert_bias else s
        _, idx = lax.top_k(sel, cfg.top_k)
        # s at the selected (a masked sum: the gather form takes the
        # TPU compiler seconds a program)
        chosen = idx[..., None] == jnp.arange(cfg.n_experts)
        wts = jnp.sum(jnp.where(chosen, s[:, None, :], 0.0), axis=-1)
        if cfg.norm_topk_prob:
            wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-6)
        return idx.astype(jnp.int32), wts * cfg.routed_scaling


def swiglu_experts(rows, lp, sizes, cfg):
    """An expert as a SwiGLU, W_2(silu(W_1 x) * W_3 x), for rows [m, d]
    sorted by expert (`sizes` [G] of them each): `w13` [G, d, 2f] holds
    W_1 and W_3 side by side, `w2` [G, f, d]; clamped by
    `cfg.swiglu_limit` where the config has one."""
    f = cfg.moe_ffn_dim
    h13 = gmm(rows, lp["w13"], sizes)
    gate, up = clamp(h13[:, :f], h13[:, f:],
                     getattr(cfg, "swiglu_limit", 0.0))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(rows.dtype) * up
    return gmm(act, lp["w2"], sizes)


def relu2_experts(rows, lp, sizes, cfg):
    """An expert as two matrices, W_2 relu(W_1 x)**2, not gated: `w1`
    [G, d, f], `w2` [G, f, d]."""
    return gmm(relu2(gmm(rows, lp["w1"], sizes)), lp["w2"], sizes)


def relu2(h):
    """relu(h) squared, in h's dtype."""
    return jnp.square(jnp.maximum(h, 0))


def routed_ffn(h2, lp, cfg, live=None,
               experts: tuple[int, int] | None = None, route_fn=None,
               router_rows=None, expert_fn=swiglu_experts):
    """The routed experts' part of FF for rows h2 [T, d].

    `experts` = (lo, hi): the range of experts whose weights `lp` holds
    (`expert_fn`'s, e.g. `w13` [hi-lo, d, 2f], `w2` [hi-lo, f, d]);
    default all.  The result
    is THEIR part of the sum, so the parts of disjoint ranges add up to
    the layer.  `live` [T] bool: rows that hold a request; the others are
    routed nowhere.  `route_fn`: the caller's router (default `route`;
    a serving module passes its own name for it, so that a test's
    control can stand in for that module's router alone).
    `router_rows` [T, any width]: what the router reads, where that is
    not h2.  `expert_fn(rows, lp, sizes, cfg)`: the experts' form
    (`swiglu_experts`, `relu2_experts`).  Returns
    (y [T, d], counts int32 [COUNTS]: experts of the range that hold a
    row, the largest load, assignments computed, the visits of ONE `gmm`
    call a block that were work, summed over the blocks (both calls walk
    the same lists; of `routed_visits` at most), and the rows of the
    sorted list the layer moved: the blocks it walked x their rows)."""
    T, d = h2.shape
    k = cfg.top_k
    lo, hi = experts or (0, cfg.n_experts)
    G = hi - lo
    N = T * k
    B = min(BLOCK, N)                  # rows a block
    blocks = -(-N // B)                # the most blocks there can be
    idx, wts = (route_fn or route)(
        h2 if router_rows is None else router_rows, lp, cfg)
    with jax.named_scope("moe_experts"):
        flat = idx.reshape(N)
        held = (flat >= lo) & (flat < hi)
        if live is not None:
            held &= jnp.repeat(live, k)
        group = jnp.where(held, flat - lo, G)     # G: nobody's, goes last
        # A counting sort, by group and then by row (a TPU `sort` of
        # 65,536 keys is a bitonic network that takes the compiler 10 s):
        # an assignment's place is its group's offset plus how many of
        # the group came before it.
        mine = (group[:, None] == jnp.arange(G + 1)[None, :]).astype(
            jnp.int32)
        before = jnp.cumsum(mine, axis=0)                  # [T*k, G+1]
        n_all = before[-1]
        place = (jnp.cumsum(n_all) - n_all)[group] + jnp.take_along_axis(
            before, group[:, None], axis=1)[:, 0] - 1
        order = jnp.zeros((blocks * B,), jnp.int32).at[place].set(
            jnp.arange(N, dtype=jnp.int32))
        sizes = n_all[:G]
        total = jnp.sum(sizes)         # the held head of the list: [0, total)
        ends = jnp.cumsum(sizes)

        def block(b):
            """Rows [b B, (b + 1) B) of the sorted list through the
            experts: (the assignments they are [B], y [B, d], zero past
            the head)."""
            if blocks == 1:
                ids, held_b = order, sizes
            else:
                ids = lax.dynamic_slice(order, (b * B,), (B,))
                held_b = jnp.diff(jnp.clip(
                    jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
                    b * B, (b + 1) * B))
            rows = h2[ids // k]                   # [B, d] by group
            return ids, expert_fn(rows, lp, held_b, cfg)  # nobody's: 0

        if blocks == 1:
            # every decode program and the smallest prefill waves: the
            # whole list is the block, and a token's k parts are read
            # back by place
            y = block(0)[1][place].reshape(T, k, d).astype(jnp.float32)
            out = jnp.sum(y * wts[..., None], axis=1)
            walked = 1
        else:
            # the blocks that hold a held row, a count the device holds;
            # each row is added at its token's row under its weight.  The
            # sum is held as column strips of ACC_BYTES at most: the
            # chip's scatter reads and writes a row of the operand for
            # each update, and is several times faster on an operand it
            # can keep in VMEM
            w_flat = wts.reshape(N)
            cols = max(128, min(d, ACC_BYTES // (4 * T) // 128 * 128))
            strips = range(0, d, cols)

            def step(b, acc):
                ids, y = block(b)
                w = w_flat[ids][:, None]
                return tuple(a.at[ids // k].add(
                    y[:, c:c + cols].astype(jnp.float32) * w)
                    for a, c in zip(acc, strips))

            walked = -(-total // B)
            out = jnp.concatenate(lax.fori_loop(
                0, walked, step,
                tuple(jnp.zeros((T, min(cols, d - c)), jnp.float32)
                      for c in strips)), axis=1)
        tm = row_tile(B)
        counts = jnp.stack([jnp.sum(sizes > 0, dtype=jnp.int32),
                            jnp.max(sizes), total,
                            visits(sizes, N + -N % tm, tm)[3],
                            jnp.asarray(walked * B, jnp.int32)])
    return out.astype(h2.dtype), counts


def routed_visits(cfg, rows: int,
                  experts: tuple[int, int] | None = None) -> int:
    """The length the visit list of `routed_ffn`'s grouped matmul is
    padded to for `rows` rows over the range `experts` (default all):
    what `counts[3]` is a share of."""
    lo, hi = experts or (0, cfg.n_experts)
    return visits_static(rows * cfg.top_k, hi - lo)


# What a serving module with routed layers reports of them
# (models/serving.ServingSpec.counters): the decode windows' under these
# names, the prefill programs' under "prefill_" + the same.  The device
# counts (`routed_ffn`); the numbers ride the token fetch of the window
# (wave) they belong to.  Experts hit a layer-step = moe_experts_hit /
# moe_layer_steps; moe_visits / moe_visits_static = the share of the
# grouped matmul's visit list that was work.
_ROUTED = {
    "moe_layer_steps": "Routed layers x steps run",
    "moe_experts_hit": "Experts that held a row, summed over routed "
                       "layer-steps",
    "moe_max_load": "The largest expert's rows, summed over routed "
                    "layer-steps",
    "moe_assignments": "Token-expert assignments computed",
    "moe_assignments_absent": "Selected experts this chip does not hold "
                              "(an expert-parallel share)",
    "moe_visits": "Visits (a group's row tile) a moe_gmm call walked, "
                  "summed over routed layer-steps",
    "moe_visits_static": "The length its visit list is padded to (row "
                         "tiles + experts held - 1), summed likewise",
    "moe_rows_moved": "Rows of the sorted assignment list a routed layer "
                      "gathered (blocks walked x their rows), summed over "
                      "routed layer-steps",
}
COUNTERS = {p + name: f"{text}, {where}"
            for p, where in (("", "in decode"),
                             ("prefill_", "in prefill programs"))
            for name, text in _ROUTED.items()}


def routed_work(cfg, experts: tuple[int, int] | None, counts, steps: int,
                rows: int, shape_rows: int, prefill: bool
                ) -> tuple[dict, dict]:
    """`ServingSpec.routed_work` (after `functools.partial` over `cfg`
    and the range of experts held, default all): one program's counts on
    the host ([layers, COUNTS], each summed over the program's `steps`)
    as COUNTERS' rows.  `rows`: the rows it routed in each layer, summed
    over the steps; each selected `cfg.top_k` experts, and the
    selections that were not computed went to experts this chip does not
    hold.  `shape_rows`: the rows a step of the program is shaped for,
    which set the length its visit lists are padded to.  A decode
    window's span shows experts hit and the largest load as means a
    layer-step."""
    layers = counts.shape[0]
    n = layers * steps
    hit, load, computed, visits, moved = (
        int(c) for c in counts.sum(axis=0))
    work = {"moe_layer_steps": n, "moe_experts_hit": hit,
            "moe_max_load": load, "moe_assignments": computed,
            "moe_assignments_absent": rows * cfg.top_k * layers - computed,
            "moe_visits": visits,
            "moe_visits_static": n * routed_visits(cfg, shape_rows,
                                                   experts),
            "moe_rows_moved": moved}
    if prefill:
        return {"prefill_" + name: v for name, v in work.items()}, {}
    return work, {"experts_hit": round(hit / n, 2),
                  "max_load": round(load / n, 2)}


def clamp(gate, up, limit: float):
    """A clamped SwiGLU's two inputs (`swiglu_limit`; the form is an
    assumption the configurations that use it list: the gate bounded
    above, the other input on both sides): (min(gate, limit), clip(up,
    -limit, limit)); as they came where `limit` is 0 or absent."""
    if not limit:
        return gate, up
    return jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)


def swiglu(h, w1, w3, w2, dtype, limit: float = 0.0):
    """W_2(silu(W_1 h) * W_3 h), the gate's activation in float32."""
    gate, up = clamp(h @ w1, h @ w3, limit)
    g = jax.nn.silu(gate.astype(jnp.float32))
    return (g.astype(dtype) * up) @ w2


def shared_ffn(h2, lp, dtype, limit: float = 0.0):
    """The shared expert: a SwiGLU every row passes through, whatever
    the router chose (`sw1`, `sw3` [d, f], `sw2` [f, d]); every chip of
    an expert-parallel layer computes it alike for its own rows."""
    with jax.named_scope("shared_expert"):
        return swiglu(h2, lp["sw1"], lp["sw3"], lp["sw2"], dtype, limit)


def stack_counts(per_layer: list) -> jnp.ndarray:
    """The routed layers' counts of one program, int32 [layers, COUNTS]
    (no rows for a program without a routed layer).  A row handed in
    shorter (the benchmark's `layer_skipped` control stands in for a
    layer with three zeros, and its file is a `benchmark` PR's to edit)
    reads 0 in what it lacks."""
    return (jnp.stack([jnp.pad(c, (0, COUNTS - c.shape[0]))
                       for c in per_layer]) if per_layer
            else jnp.zeros((0, COUNTS), jnp.int32))


def prefill_params(cfg, rest: int, layers: int,
                   experts: tuple[int, int] | None = None,
                   one: int | None = None) -> tuple[int, int]:
    """(streamed, multiplied) matmul parameters of a model with `layers`
    routed layers over the range `experts` (default all) and `rest`
    parameters that every position multiplies (attention, convolutions,
    dense and shared SwiGLUs; the routers are added here).  STREAMED: what
    a prefill program reads whatever it holds: every held expert, since
    the grouped matmul reads a hit expert whole and ~100 positions hit
    them all.  MULTIPLIED: what ONE position multiplies: of its `top_k`
    experts those this chip holds.  Neither counts the embedding (a
    lookup) or the head (one position a row).  `one`: an expert's
    parameters where it is no SwiGLU of `dim` x `moe_ffn_dim`."""
    lo, hi = experts or (0, cfg.n_experts)
    one = one or 3 * cfg.dim * cfg.moe_ffn_dim
    rest += layers * cfg.dim * cfg.n_experts
    return (rest + layers * (hi - lo) * one,
            rest + layers * cfg.top_k * (hi - lo) * one // cfg.n_experts)
