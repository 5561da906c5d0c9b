"""Model family `mimo_v2`: the decoder `ray_tpu/models/mimo_v2.py` serves
(`model_type` `mimo_v2_flash`, e.g. MiMo-V2-Flash: window grouped-query
layers with a learned sink kept as a K and a V ring a lane beside global
grouped-query layers of another kv-head count in pages, keys 192 wide
over values of 128, rotary on a part of each head, routed experts of
which this chip holds a range, no shared expert).

`benchmarks/README.md`, "A model family", holds the contract.  Nothing
here imports `jax` at load: the driver process loads the family before
the cluster starts and never initializes a backend.  It does look, at
load, for the program's module: a checkout whose program cannot serve
this family (the parent of the PR that added it) stops here with a
sentence, before any process is started.
"""
from __future__ import annotations

import os

from benchmarks.harness import spec

_PROGRAM = os.path.join(spec.ROOT, "ray_tpu", "models", "mimo_v2.py")
if not os.path.isfile(_PROGRAM):
    raise SystemExit(
        f"model family mimo_v2: this checkout's program has no {_PROGRAM}"
        " (ray_tpu.models.mimo_v2), so it cannot serve the family")

KEYS = ("add_full_attention_sink_bias", "add_swa_attention_sink_bias",
        "attention_bias", "attention_chunk_size", "attention_value_scale",
        "head_dim", "hidden_act", "hidden_size", "hybrid_layer_pattern",
        "intermediate_size", "layernorm_epsilon", "max_position_embeddings",
        "model_type", "moe_intermediate_size", "moe_layer_freq", "n_group",
        "n_routed_experts", "n_shared_experts", "norm_topk_prob",
        "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
        "num_key_value_heads", "partial_rotary_factor", "rope_theta",
        "routed_scaling_factor", "scoring_func", "sliding_window",
        "sliding_window_size", "swa_head_dim", "swa_num_attention_heads",
        "swa_num_key_value_heads", "swa_rope_theta", "swa_v_head_dim",
        "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
        "vocab_size")
GLOBAL, WINDOW = 0, 1           # `hybrid_layer_pattern`'s entries

# Serve: `correct` for this family rests on FIVE readings, each with its
# own limit (`Judge` folds them into the one number the harness compares).
# The first is taken for every sample request, the others for the first
# request a replica judges, on its first BLOCK_POSITIONS positions (they
# cost a reference pass a block).
#
# (1) SERVED TOKENS, end to end: the MEAN teacher-forced gap of a
#     request's served tokens under the plain float32 reference (a routed
#     model's near-ties flip and cascade).  The one reading taken from
#     the engine's own timed programs (the prefill of the sample's wave
#     with `flash_fwd` and the banded `swa_band`, the scatter into the K
#     and V pages and the rings, three decode windows through
#     `paged_attn` at 192 / 128 and `swa_attn` over rings that wrap every
#     128 positions).  Limit REFERENCE_GAP_TOL.
# Readings (2)-(4) are of the program's BLOCKS, which this file jits itself
# (`_program_blocks`) from the functions the engine's two programs are made
# of: the pages are built here from a prefill block's rows, the rings are
# the ones a prefill block hands.  They are NOT the engine's own cache after
# a served request: the harness hands a judge the parameters, the prompt
# and the served tokens, and no handle on the engine's pool or lane state.
# So a fault in how the ENGINE stores, merges or scatters rows is seen only
# where it goes through a function the blocks call too (`prog.stored`,
# `kv_ring_from_rows`, `kv_ring_write`, `global_decode`'s tail) or moves
# the served tokens (reading 1: the whole-run control with a row's rings in
# the next lane).  Reading the engine's cache takes a method on the
# harness's replica: a `benchmark` PR's (PERF.md section 7, item 13 d).
#
# (2) The PROGRAM'S BLOCKS, one at a time at the served widths, each
#     from the program's OWN input on the request's tokens right-padded
#     with `true_lens` passed: the attention half and the feed-forward of
#     every layer, the head, and one DECODE STEP of every attention layer
#     (two lanes of which one holds the request: a global layer over
#     pages filled from the prefill's rows, a window layer over the rings
#     the prefill handed), against the reference's same block.  A routed
#     block leaves out the positions whose routing margin is under
#     MARGIN_EPS.  The reading is the relative error of what a block adds
#     to the stream: BLOCK_ERR_TOL for the attention halves, the decode
#     steps and the head; FFN_ERR_TOL for the feed-forwards, apart,
#     because a routed layer whose sixteen held experts serve 0, 1 or 2 of
#     a token's eight with no shared expert beside them adds a row that
#     is ONE expert's output at most positions: its worst position of
#     thousands reads six times the attention's.
# (3) The ROWS handed to the pages and the RINGS at their true positions:
#     the K and V rows of a global layer, every slot of a window layer's
#     two rings at the true length (slot i: the last position that is i
#     mod 128: the ring has wrapped dozens of times) and the slot the
#     decode step writes (the other slots bit-unchanged, the idle lane's
#     rings too), against the reference's K and V.  ROW_ERR_TOL.
# (4) The WINDOW'S EDGE: at the EDGE_POSITIONS positions where the
#     reference at a window of 127 (and, apart, of 129) differs most from
#     itself at 128, how far the program has gone from the reference at
#     128 TOWARDS the reference at the other window: the component of
#     (program - reference) along (other - reference), over the latter's
#     length (the median of those positions' magnitudes; the prefill
#     block, whose band crosses a key block's boundary, and, at the two
#     most telling positions, a decode step over the ring, whose slot of
#     position t - 128 is the one the step overwrites).  One row of 128
#     moves a block's output by ~1 %, twice the program's own bfloat16
#     error, so the plain distance ratio reads 0.35-0.46 sound; the
#     rounding error has no preferred direction among 4,096, so its
#     component along one reads a sixtieth of that, and a program whose
#     band or ring bias is one row short or long reads ~1.  EDGE_TOL.
#
# Readings (my chip runs, PR 52; PERF.md section 6).  Sound = the
# benchmark's runs on their seeds of the weights and the family's judge
# alone on four more (1,400 positions); a control = the judge alone at the
# published widths on a program that carries the fault
# (`benchmarks/tests/test_mimo_v2_family._mimo_control`), each passed
# through `Judge.teacher_forced_gaps` and the harness's own comparison
# (the worst number against REFERENCE_GAP_TOL) on two seeds: `correct`
# came out false for every one in the table, sixteen of sixteen, and true
# for the sound program; for the two marked RUN a whole benchmark run
# through run.py of a tree that carries the fault, `correct: false`:
#                          sound               control
#   (1) mean token gap     0.0 ... 0.0058      RUN a row's rings scattered
#       (a run's worst request; a sound        into the next lane (a fault of
#       request's WORST token reads up to      the served path alone, which no
#       0.10)                                  block sees): 1.59 ... 2.63;
#                                              RUN keys kept in fp8: 0.0070
#                                              (reads as sound: held by rows)
#   (2) blocks             0.0058 ... 0.0063   window 127 / 129 0.077 ... 0.117;
#                                              RUN fp8 keys 0.0247 (a global
#                                              layer's decode step; fp8 pages
#                                              0.036 ... 0.038); value scale left
#                                              out 0.416; rotary over 96 columns
#                                              0.68 ... 0.71; sink left out 0.93 ...
#                                              1.20; the window layers read with
#                                              the global layers' grouping 1.39
#                                              ... 1.42
#       feed-forwards      0.0277 ... 0.0333   (no control aims at them: the
#                                              routed layer is models/routed.py's,
#                                              held by five families' judges)
#   (3) rows               0.0032 ... 0.0033   fp8 ring 0.0286 ... 0.0288; fp8
#                                              pages 0.0294 ... 0.0297 (RUN
#                                              0.0297); rotary over 96 1.06 ...
#                                              1.09
#   (4) window's edge      0.0044 ... 0.011    window 127 0.92 ... 0.95, 129
#                                              0.88 ... 0.96 (as
#                                              a plain distance ratio, the
#                                              first form: sound 0.35 ... 0.46,
#                                              the controls 0.973)
# and the share of a routed block's positions left out for a routing margin
# under MARGIN_EPS: 0.259 ... 0.283 (limit 0.5; the largest share of a limit
# in most sound runs: 0.52-0.57).
#
# ONE control the judge does NOT fail: a prefill attention whose scores,
# exponentials, sum and division are bfloat16 (float32 in the program)
# reads blocks 0.0071 ... 0.0076 on the two seeds whose sound program
# reads 0.0058 ... 0.0063, every other reading as sound, `correct: true`.
# A block's output is rounded to bfloat16 once whatever its softmax was
# computed in, and rounding the scores and the probabilities too adds an
# error of that same size: a limit between 0.0063 and 0.0071 would have
# 6 % of room a side, which the next seed of a sound program spends.
#
# 0.015 is 2.4 x the sound blocks' largest and 1.6 x under the fp8 keys'
# smallest (5 x under a window off by one); 0.06 is 1.8 x the sound
# feed-forwards' largest; 0.010 is 3.0 x the sound rows' largest and 2.9 x
# under fp8's smallest; 0.5 is 45 x the sound edge's largest and 1.8 x under
# a window off by one.  The attention logits here have a standard deviation
# near 1 at random weights (no rescaled latents), so the served tokens' mean
# gap reads thousandths where `dots3_note` reads whole tenths, and its limit
# is the 0.45 of the harness's other families: 78 x the largest sound
# request and 3.5 x under the smallest request whose rings were another
# lane's.
REFERENCE_GAP_TOL = 0.45
BLOCK_ERR_TOL = 0.015
FFN_ERR_TOL = 0.06
ROW_ERR_TOL = 0.010
EDGE_TOL = 0.5
MARGIN_EPS = 0.002
LOOSE_SHARE_MAX = 0.5
HEAD_POSITIONS = 128
# the blocks are read on the request's first positions: 21 windows, so
# every ring slot has been overwritten twenty times
BLOCK_POSITIONS = 2750
EDGE_POSITIONS = 32
EDGE_STEPS = 2          # decode steps a window layer a side of the edge


def _held(config: dict) -> tuple[int, int]:
    ep = config["expert_parallel"]
    n = config["n_routed_experts"]
    return ep["rank"] * n, (ep["rank"] + 1) * n


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run, and what the
    cut adds: `router_experts` (the router's published width),
    `experts_held` (the range this chip holds) and `num_experts` (how
    many that is: the key the shared `engine.moe_experts_hit_pct` reader
    divides by)."""
    m = {k: config[k] for k in KEYS}
    m["router_experts"] = config["published"]["n_routed_experts"]
    m["experts_held"] = list(_held(config))
    m["num_experts"] = config["n_routed_experts"]
    return m


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def ring_rows(window: int) -> int:
    """A lane's ring a window layer: the window in whole tiles of 8 rows
    (128 -> 128: the slot a step overwrites is the row the window has
    just left)."""
    return -(-window // 8) * 8


def program_config(model: dict, max_seq: int, **extra):
    """MimoV2Config for the published keys: only sizes and scalars move.
    Refuses what the program does not express."""
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2 as prog

    m = model
    n = m["num_hidden_layers"]
    refuse = {
        "another activation than silu": m["hidden_act"] != "silu",
        "a tied head": m["tie_word_embeddings"],
        "a bias in attention": m["attention_bias"],
        "a sink in the global layers or none in the window layers":
            m["add_full_attention_sink_bias"]
            or not m["add_swa_attention_sink_bias"],
        "another router than sigmoid noaux_tc in one group":
            m["scoring_func"] != "sigmoid" or m["topk_method"] != "noaux_tc"
            or not m["norm_topk_prob"] or m["n_group"] != 1
            or m["topk_group"] != 1,
        "a shared expert": bool(m["n_shared_experts"]),
        "layer lists that do not name num_hidden_layers layers":
            len(m["hybrid_layer_pattern"]) != n
            or len(m["moe_layer_freq"]) != n
            or set(m["hybrid_layer_pattern"]) - {GLOBAL, WINDOW},
        "window layers of other widths or query heads than the global":
            m["swa_head_dim"] != m["head_dim"]
            or m["swa_v_head_dim"] != m["v_head_dim"]
            or m["swa_num_attention_heads"] != m["num_attention_heads"],
        "two windows": m["sliding_window"] != m["sliding_window_size"],
    }
    bad = [what for what, is_so in refuse.items() if is_so]
    if bad:
        raise ValueError(f"the program does not express {bad}")
    scale = m["routed_scaling_factor"]
    return prog.MimoV2Config(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        layer_types=tuple(prog.GLOBAL if k == GLOBAL else prog.WINDOW
                          for k in m["hybrid_layer_pattern"]),
        moe_layers=tuple(m["moe_layer_freq"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        swa_n_kv_heads=m["swa_num_key_value_heads"],
        qk_head_dim=m["head_dim"], v_head_dim=m["v_head_dim"],
        rope_dim=int(m["partial_rotary_factor"] * m["head_dim"]),
        rope_theta=float(m["rope_theta"]),
        swa_rope_theta=float(m["swa_rope_theta"]),
        window=m["sliding_window"], ring_rows=ring_rows(m["sliding_window"]),
        value_scale=float(m["attention_value_scale"]),
        ffn_dim=m["intermediate_size"],
        moe_ffn_dim=m["moe_intermediate_size"],
        n_experts=m["router_experts"],
        experts_held=tuple(m["experts_held"]),
        top_k=m["num_experts_per_tok"],
        routed_scaling=1.0 if scale is None else float(scale),
        norm_eps=float(m["layernorm_epsilon"]), max_seq=max_seq,
        dtype=jnp.bfloat16, **extra)


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it.  The bits come from the device's own generator (jax's
    "rbg" keys seeded from the harness's key: the same seed, the same
    weights), as `families/ssm_hybrid.py` found it worth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2

    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    wide = jax.random.wrap_key_data(jnp.concatenate([key, key])[:4],
                                    impl="rbg")
    return mimo_v2.init_params(wide, cfg)


def reference():
    """The judge of a serve cell: `teacher_forced_gaps(params, prompt,
    served, model)` over the plain reference `refs/mimo_v2.py`."""
    return Judge


class Judge:
    """The served tokens' mean gap under the plain reference for every
    request, and for the first one this process judges the four readings
    of `block_errors`, each held to its own limit (the reasons stand
    above `REFERENCE_GAP_TOL`).  The harness compares ONE number with
    `REFERENCE_GAP_TOL`, so each reading is returned as its share of its
    limit times `REFERENCE_GAP_TOL`; all readings and limits are printed
    (stderr reaches the run's output)."""

    _seen: dict = {}
    _blocks_done: list = []

    @classmethod
    def teacher_forced_gaps(cls, params, prompt, served, model
                            ) -> list[float]:
        key = (id(params["embed"]), tuple(prompt), tuple(served))
        if key not in cls._seen:
            cls._seen[key] = cls._judge(params, prompt, served, model)
        return list(cls._seen[key])

    @classmethod
    def _judge(cls, params, prompt, served, model) -> list[float]:
        import json
        import sys
        import time

        from benchmarks.harness.refs import mimo_v2 as ref

        t0 = time.perf_counter()
        gaps = ref.token_gaps(params, prompt, served, model)
        t1 = time.perf_counter()
        mean_gap = sum(gaps) / len(gaps)
        shares = {"token_gap": mean_gap / REFERENCE_GAP_TOL}
        line = {"step": "mimo_v2.judge", "mean_token_gap": mean_gap,
                "worst_token_gap": max(gaps), "limit": REFERENCE_GAP_TOL,
                "tokens": len(prompt) + len(served),
                "token_gaps_s": round(t1 - t0, 2)}
        if not cls._blocks_done:
            cls._blocks_done.append(True)
            b = block_errors(
                params, (list(prompt) + list(served[:-1]))[:BLOCK_POSITIONS],
                model)
            shares.update(
                block_err=b["block"][0] / BLOCK_ERR_TOL,
                ffn_err=b["ffn"][0] / FFN_ERR_TOL,
                row_err=b["rows"][0] / ROW_ERR_TOL,
                edge=b["edge"][0] / EDGE_TOL,
                loose_share=b["loose_share"] / LOOSE_SHARE_MAX)
            line.update(
                worst_block_err=b["block"], block_limit=BLOCK_ERR_TOL,
                worst_ffn_err=b["ffn"], ffn_limit=FFN_ERR_TOL,
                worst_row_err=b["rows"], row_limit=ROW_ERR_TOL,
                worst_edge=b["edge"], edge_limit=EDGE_TOL,
                loose_share=b["loose_share"], by_block=b["by_block"],
                blocks_s=round(time.perf_counter() - t1, 2))
        worst = max(shares.values())
        if any(v != v for v in shares.values()):     # a NaN anywhere
            worst = float("inf")
        line["held_by"] = max(shares, key=shares.get)
        print(json.dumps(line), file=sys.stderr, flush=True)
        reading = worst * REFERENCE_GAP_TOL
        out = [0.0 if g == 0.0 else reading for g in gaps]
        if not any(out):
            out[0] = reading
        return out


_BLOCKS: dict = {}
# what of a layer's weights each block reads: handed over as a subset, so
# that the layers of one kind share ONE compiled program a block
MIXER_KEYS = {GLOBAL: ("norm1", "wq", "wk", "wv", "wo"),
              WINDOW: ("norm1", "wq", "wk", "wv", "wo", "sink")}
FFN_KEYS = {True: ("norm2", "w1", "w3", "w2"),
            False: ("norm2", "router", "expert_bias", "w13", "w2")}
JUDGE_PAGE = 512


def _program_blocks(cfg, n: int):
    """The program's blocks, each jitted once for a true length n and
    taking the layer's own weights (a subset of its dict), so that every
    layer of a kind runs the one compiled program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2 as prog
    from ray_tpu.ops import ssm

    F32 = jnp.float32
    lens_of = lambda m: jnp.reshape(m, (1,)).astype(jnp.int32)  # noqa: E731

    def mix(kind):
        fn = prog.global_prefill if kind == GLOBAL else prog.window_prefill

        def run(lp, x, m):
            y, kept = fn(x, lp, cfg, lens_of(m))
            return x + y, kept
        return jax.jit(run)

    def ffn(dense: bool):
        lid = cfg.moe_layers.index(0 if dense else 1)  # any layer of the kind

        def run(lp, x):
            live = jnp.arange(x.shape[1])[None, :] < n
            return x + prog.ffn(x, lp, lid, cfg, live)[0]
        return jax.jit(run)

    def token(x, at):
        return jnp.repeat(jax.lax.dynamic_index_in_dim(
            x, at, axis=1, keepdims=False), 2, axis=0)

    live = jnp.asarray([False, True])       # lane 0 idle, lane 1 the request

    def global_decode(lp, x, at, ks, vs):
        """One decode step of a global layer for the token at position
        `at`: the pages filled from the prefill's rows below it."""
        P = ks.shape[1]
        maxp = -(-P // JUDGE_PAGE)

        def pool(rows):
            rows = jnp.pad(rows[0], ((0, maxp * JUDGE_PAGE - P), (0, 0),
                                     (0, 0)))
            leaf = rows.reshape(maxp, JUDGE_PAGE, *rows.shape[1:]).transpose(
                0, 2, 1, 3)
            return jnp.concatenate([jnp.zeros_like(leaf[:1]), leaf])

        table = jnp.stack([jnp.zeros((maxp,), jnp.int32),
                           jnp.arange(1, maxp + 1, dtype=jnp.int32)])
        pos = jnp.stack([jnp.int32(0), at])
        kt = jnp.zeros((2, ks.shape[2], 8, ks.shape[3]), cfg.dtype)
        vt = jnp.zeros((2, vs.shape[2], 8, vs.shape[3]), cfg.dtype)
        y, kt, vt = prog.global_decode(
            token(x, at), lp, pool(ks), pool(vs), kt, vt, table, pos, pos,
            0, cfg)
        return y.astype(F32)[1], kt[1, :, 0], vt[1, :, 0]

    def window_decode(lp, x, at, k1, v1):
        """One decode step of a window layer for the token at position
        `at` over the rings a prefill of true length `at` handed: lane 1
        holds them, the idle lane 0 twice them."""
        rk, rv = (jnp.concatenate([2 * r, r]) for r in (k1, v1))
        pos = jnp.stack([jnp.int32(0), at])
        lanes, count = ssm.live_lanes(live)
        y, ak, av = prog.window_decode(token(x, at), lp, rk, rv, pos,
                                       x.shape[1] + 8, live, lanes, count,
                                       cfg)
        slot = at % cfg.ring_rows
        others = (jnp.arange(cfg.ring_rows) != slot)[None, :, None]
        untouched = jnp.bool_(True)
        for before, after in ((rk, ak), (rv, av)):
            untouched &= jnp.all(after[0] == before[0]) & jnp.all(
                jnp.where(others, after[1] == before[1], True))
        return y.astype(F32)[1], ak[1, :, slot], av[1, :, slot], untouched

    return {
        "embed": jax.jit(lambda params, tok: prog.embed_lookup(
            params["embed"], tok, cfg.dtype)),
        "mix": {kind: mix(kind) for kind in (GLOBAL, WINDOW)},
        "ffn": {dense: ffn(dense) for dense in (True, False)},
        "global_decode": jax.jit(global_decode),
        "window_decode": jax.jit(window_decode),
        "head": jax.jit(lambda params, x: prog.project_logits(
            params, prog.rmsnorm(x, params["final_norm"], cfg.norm_eps))),
    }


def _comparisons(n: int):
    """What is computed FROM the blocks' outputs, jitted once for a true
    length n."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32

    def cut(a):
        return a[0, :n].astype(F32)

    def err(got, want):
        """Relative error a position (2-norms over everything else; a
        position whose reference nearly cancels is measured against the
        median norm of the positions that have one: a routed layer with
        no shared expert adds exactly 0 where none of a token's eight
        experts is held here, six positions in ten)."""
        got, want = (a.reshape(a.shape[0], -1) for a in (got, want))
        size = jnp.linalg.norm(want, axis=-1)
        typical = jnp.nanmedian(jnp.where(size > 0, size, jnp.nan))
        return (jnp.linalg.norm(got - want, axis=-1)
                / jnp.maximum(size, typical))

    def edge(got, at_window, other):
        """A position: how far the program has gone from the reference
        at the published window towards the reference at the other one
        (the component of its error along their difference, over that
        difference's length: 0 at the one, 1 at the other)."""
        towards = other - at_window
        return jnp.abs(jnp.sum((got - at_window) * towards, axis=-1)
                       / jnp.sum(towards * towards, axis=-1))

    return {"cut": jax.jit(cut), "err": jax.jit(err), "edge": jax.jit(edge),
            "added": jax.jit(lambda after, before, want:
                             err(cut(after) - cut(before), want))}


def block_errors(params, tokens: list[int], model: dict) -> dict:
    """Readings (2)-(4) on one sequence, each block from the program's
    own input, the sequence right-padded and its TRUE length passed.
    Returns {"block", "ffn", "rows", "edge": (the worst reading, where),
    "loose_share": the largest share of a routed block's positions left
    out for a routing margin under MARGIN_EPS, "by_block": [kind, how
    many, median, worst]}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import mimo_v2 as ref

    n = len(tokens)
    P = -(-(n + 1) // 128) * 128
    key = (P, n, tuple(sorted((k, str(v)) for k, v in model.items())))
    if key not in _BLOCKS:
        cfg = program_config(model, max_seq=P + 8)
        _BLOCKS[key] = (cfg, _program_blocks(cfg, n), _comparisons(n))
    cfg, fn, cmp = _BLOCKS[key]
    window, R = cfg.window, cfg.ring_rows
    pad = [(7 * i + 3) % model["vocab_size"] for i in range(P - n)]
    tok = jnp.asarray([list(tokens) + pad], jnp.int32)
    last = jnp.int32(n - 1)
    f32 = lambda a: jnp.asarray(a).astype(jnp.float32)      # noqa: E731

    block, ffn, rows, edge, loose = [], [], [], [], [0.0]
    x = fn["embed"](params, tok)
    for lid, lp in enumerate(params["layers"]):
        kind = model["hybrid_layer_pattern"][lid]
        name = "global" if kind == GLOBAL else "window"
        dense = ref.is_dense(lid, model)
        mp = {k: lp[k] for k in MIXER_KEYS[kind]}
        fp = {k: lp[k] for k in FFN_KEYS[dense]}
        xc = cmp["cut"](x)
        y, info = ref.mixer(xc, lp, lid, model)
        want = {"k": np.asarray(info["k"]), "v": np.asarray(info["v"])}
        if kind == GLOBAL:
            x_mid, (ks, vs) = fn["mix"][kind](mp, x, jnp.int32(n))
            d1, k1, v1 = fn["global_decode"](mp, x, last, ks, vs)
            # (3) the rows the pages are filled from, and the step's own
            # (a K row is stored a lane tile wide: zeros past the head)
            for leaf, got, step in (("k", ks, k1), ("v", vs, v1)):
                w = want[leaf].shape[-1]
                rows.append((f"{lid}.page_{leaf}", np.asarray(cmp["err"](
                    cmp["cut"](got)[..., :w], jnp.asarray(want[leaf])))))
                rows.append((f"{lid}.tail_{leaf}", np.asarray(cmp["err"](
                    f32(step)[None, ..., :w],
                    jnp.asarray(want[leaf][n - 1:n])))))
                rows.append((f"{lid}.pad_{leaf}", float("inf") if np.asarray(
                    got[..., w:]).any() else 0.0))
        else:
            x_mid, rings = fn["mix"][kind](mp, x, jnp.int32(n))
            _, rings1 = fn["mix"][kind](mp, x, jnp.int32(n - 1))
            d1, k1, v1, untouched = fn["window_decode"](mp, x, last, *rings1)
            # (3) every slot of both rings at the true length: slot i
            # holds the last position below n that is i mod R
            held = (n - 1) - (n - 1 - np.arange(R)) % R
            for leaf, ring, step in (("k", rings[0], k1), ("v", rings[1], v1)):
                w = want[leaf].shape[-1]
                got = np.asarray(f32(ring[0])).transpose(1, 0, 2)  # [R, G, .]
                rows.append((f"{lid}.ring_{leaf}", np.asarray(cmp["err"](
                    jnp.asarray(got[held >= 0][..., :w]),
                    jnp.asarray(want[leaf][held[held >= 0]])))))
                rows.append((f"{lid}.ring_empty_slots", 0.0 if not (
                    got[held < 0].any() or got[..., w:].any())
                    else float("inf")))
                rows.append((f"{lid}.ring_step_{leaf}", np.asarray(
                    cmp["err"](f32(step)[None, ..., :w],
                               jnp.asarray(want[leaf][n - 1:n])))))
            rows.append((f"{lid}.ring_other_slots",
                         0.0 if bool(untouched) else float("inf")))
            # (4) the window's edge
            got_y = cmp["cut"](x_mid) - xc
            for w in (window - 1, window + 1):
                other, _ = ref.mixer(xc, lp, lid, model, window=w)
                far = np.array(jnp.linalg.norm(other - y, axis=-1)
                               / jnp.linalg.norm(y, axis=-1))
                far[:window - 1] = 0.0      # both windows hold everything
                at = np.argsort(-far)[:EDGE_POSITIONS]
                at = at[far[at] > 0]
                if not at.size:
                    continue
                edge.append((f"{lid}.prefill.{w}", float(np.median(
                    np.asarray(cmp["edge"](got_y[at], y[at], other[at]))))))
                for p in at[:EDGE_STEPS].tolist():
                    _, rings_p = fn["mix"][kind](mp, x, jnp.int32(p))
                    dp = fn["window_decode"](mp, x, jnp.int32(p),
                                             *rings_p)[0]
                    edge.append((f"{lid}.decode_step.{w}", float(cmp["edge"](
                        dp[None], y[p][None], other[p][None])[0])))
        block.append((f"{lid}.{name}", np.asarray(cmp["added"](x_mid, x, y))))
        block.append((f"{lid}.{name}_decode_step", np.asarray(cmp["err"](
            d1[None], y[n - 1][None]))))
        x_out = fn["ffn"][dense](fp, x_mid)
        xm = cmp["cut"](x_mid)
        with jax.default_matmul_precision("highest"):
            y_ffn, margin = ref.ff(xm, lp, lid, model)
        e = np.asarray(cmp["added"](x_out, x_mid, y_ffn))
        if margin is not None:
            firm = np.asarray(margin) >= MARGIN_EPS
            loose.append(1.0 - float(firm.mean()))
            e = e[firm]
        ffn.append((f"{lid}.ffn", e))
        x = x_out
    tail = slice(max(0, n - HEAD_POSITIONS), n)
    block.append(("head", np.asarray(cmp["err"](
        fn["head"](params, x)[0, tail].astype(jnp.float32),
        ref.head(cmp["cut"](x)[tail], params, model)))))

    def worst_of(readings):
        vals = [(float(np.max(e)) if np.size(e) else 0.0, name)
                for name, e in readings]
        if any(v != v for v, _ in vals):
            return (float("nan"), "a NaN")
        return max(vals) if vals else (0.0, "")

    kinds: dict = {}
    for name, e in block + ffn + rows + edge:
        kinds.setdefault(name.split(".", 1)[-1], []).append(
            np.atleast_1d(np.asarray(e, np.float64)))
    return {"block": worst_of(block), "ffn": worst_of(ffn),
            "rows": worst_of(rows), "edge": worst_of(edge),
            "loose_share": max(loose),
            "by_block": [[kind, len(es),
                          float(np.median([np.median(e) for e in es])),
                          float(max(np.max(e) for e in es))]
                         for kind, es in kinds.items()]}


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal: both kinds of layer, routed and dense.
    ONE global layer: interpreted on the CPU the paged kernel walks its
    (lane, page) pairs one by one, and a second global layer was two
    fifths of a rehearsal's three minutes."""
    config.update(
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        vocab_size=512, num_attention_heads=8, swa_num_attention_heads=8,
        num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=24,
        swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16,
        sliding_window=9, sliding_window_size=9, n_routed_experts=4,
        num_experts_per_tok=2, num_hidden_layers=3,
        hybrid_layer_pattern=[GLOBAL, WINDOW, WINDOW],
        moe_layer_freq=[0, 1, 1])
    config["published"] = dict(config["published"], n_routed_experts=8)
    config["expert_parallel"] = {"chips": 2, "rank": 0}


# ---------------------------------------------------------------- counts
def _n(m: dict, kind: int) -> int:
    return m["hybrid_layer_pattern"].count(kind)


def _routed_layers(m: dict) -> int:
    return sum(m["moe_layer_freq"])


def _held_experts(m: dict) -> int:
    return m["experts_held"][1] - m["experts_held"][0]


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _attn_params(m: dict, p: str) -> int:
    """W_q, W_k, W_v and W_o of one kind of layer (`p`: "" global,
    "swa_" window)."""
    d, H, G = (m["hidden_size"], m[p + "num_attention_heads"],
               m[p + "num_key_value_heads"])
    dk, dv = m[p + "head_dim"], m[p + "v_head_dim"]
    return d * ((H + G) * dk + G * dv + H * dv)


def _non_expert_matmul_params(m: dict) -> int:
    d = m["hidden_size"]
    dense = m["num_hidden_layers"] - _routed_layers(m)
    return (_n(m, GLOBAL) * _attn_params(m, "")
            + _n(m, WINDOW) * _attn_params(m, "swa_")
            + dense * 3 * d * m["intermediate_size"]
            + _routed_layers(m) * d * m["router_experts"]
            + m["vocab_size"] * d)


def param_count(m: dict) -> int:
    """Parameters as the program holds them: the embedding and the head
    apart, the norms, the sinks, the HELD experts, the expert biases."""
    d = m["hidden_size"]
    small = ((2 * m["num_hidden_layers"] + 1) * d
             + _n(m, WINDOW) * m["swa_num_attention_heads"]
             + _routed_layers(m) * m["router_experts"])
    return (_non_expert_matmul_params(m) + m["vocab_size"] * d + small
            + _routed_layers(m) * _held_experts(m) * _expert_params(m))


def matmul_params(m: dict) -> int:
    """Parameters a token's step MULTIPLIES on this chip: of a routed
    layer the share of the selected experts that is held here."""
    active = (m["num_experts_per_tok"] * _held_experts(m)
              / m["router_experts"])
    return int(_non_expert_matmul_params(m)
               + _routed_layers(m) * active * _expert_params(m))


def decode_step_bytes(m: dict, lanes: int = 64) -> float:
    """Bytes a decode step of a FULL batch must stream at the least:
    every matmul weight held here once (bf16); the global layers' pages
    and the rings' live rows are the `paged_attn` and `swa_attn`
    rooflines'."""
    del lanes
    return 2.0 * (_non_expert_matmul_params(m)
                  + _routed_layers(m) * _held_experts(m) * _expert_params(m))


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name."""
    if kernel == "moe_gmm":
        return _routed_layers(m)
    if kernel in ("paged_attn", "flash_fwd"):
        return _n(m, GLOBAL)
    if kernel in ("swa_attn", "swa_band"):
        return _n(m, WINDOW)
    return 0


def moe_gmm_cost(m: dict, assignments: float, experts_hit: float
                 ) -> tuple[float, float]:
    """(flops, bytes) the `moe_gmm` calls NEED (`families/lfm2_moe.py`
    has the reasoning)."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * _expert_params(m) * assignments
    nbytes = 2.0 * (_expert_params(m) * experts_hit
                    + (2 * d + 3 * f) * assignments)
    return flops, nbytes


def paged_attn_cost(m: dict, rows: float) -> tuple[float, float]:
    """(flops, bytes) the `paged_attn` calls of ONE global layer NEED to
    attend `rows` context rows in all (summed over lanes and steps): a
    row's K (192 wide: the pool stores it a lane tile wider, which is no
    work) and V (128 wide) read once a kv head (bf16), and scored and
    weighed for every query head."""
    dk, dv = m["head_dim"], m["v_head_dim"]
    return (2.0 * m["num_attention_heads"] * (dk + dv) * rows,
            2.0 * m["num_key_value_heads"] * (dk + dv) * rows)


def swa_attn_cost(m: dict, rows: float) -> tuple[float, float]:
    """(flops, bytes) the `swa_attn` calls NEED to attend `rows` LIVE
    ring rows in all (summed over lanes, layers and steps): a row's K and
    V read once a kv head of a window layer (8 x (192 + 128), bf16) and
    scored and weighed for every query head."""
    dk, dv = m["swa_head_dim"], m["swa_v_head_dim"]
    return (2.0 * m["swa_num_attention_heads"] * (dk + dv) * rows,
            2.0 * m["swa_num_key_value_heads"] * (dk + dv) * rows)


def swa_band_cost(m: dict, lens: list[int]) -> tuple[float, float]:
    """(flops, bytes) ONE window layer's banded call (`swa_band`) needs
    for sequences of the given TRUE lengths: a query scores its own
    position and the window - 1 before it over 192 and takes values 128
    wide; q and o once a query head, k and v once a kv head, bf16."""
    w = m["sliding_window"]
    H, G = m["swa_num_attention_heads"], m["swa_num_key_value_heads"]
    width = m["swa_head_dim"] + m["swa_v_head_dim"]
    pairs = sum(min(s, w) * (min(s, w) + 1) // 2 + max(s - w, 0) * w
                for s in lens)
    return 2.0 * pairs * H * width, 2.0 * sum(lens) * width * (H + G)
