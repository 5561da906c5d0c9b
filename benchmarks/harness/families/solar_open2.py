"""Model family `solar_open2`: the decoder `ray_tpu/models/solar_open2.py`
serves (`model_type` `solar_open2`, e.g. Solar-Open2-250B: KDA
linear-attention layers whose decay gate has NO lower bound and whose
write strength reaches 2, three in four, beside a gated softmax GQA layer
without position embedding, every layer routed with a shared expert; this
chip holds a range of the experts).

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

_PROGRAM = os.path.join(spec.ROOT, "ray_tpu", "models", "solar_open2.py")
if not os.path.isfile(_PROGRAM):
    raise SystemExit(
        f"model family solar_open2: this checkout's program has no "
        f"{_PROGRAM} (ray_tpu.models.solar_open2), so it cannot serve the "
        "family")

KEYS = ("model_type", "partial_rotary_factor", "linear_attn_config",
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "head_dim", "num_key_value_heads", "vocab_size", "intermediate_size",
        "moe_intermediate_size", "rms_norm_eps", "rope_theta",
        "tie_word_embeddings", "max_position_embeddings",
        "first_k_dense_replace", "use_rope", "gqa_interval", "gqa_layers",
        "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
        "n_routed_experts", "n_shared_experts", "norm_topk_prob",
        "routed_scaling_factor", "num_experts_per_tok")
KDA, GQA = "kda", "gqa"

# Serve: `correct` for this family rests on SIX readings, each with its
# own limit (`Judge` folds them into the one number the harness compares).
# The first is taken for every sample request, the others for the first
# request a replica judges, on the WHOLE of it (they cost a reference pass
# a block, and a run has 345 s; at the request's own length the blocks'
# reference runs the programs the token gaps compiled).
#
# (1) SERVED TOKENS, end to end: the MEAN teacher-forced gap of a
#     request's served tokens under the plain float32 reference (a routed
#     model's near-ties flip under bfloat16 and cascade, so the worst
#     token of a sound request reads whole units: every routed family
#     holds the mean).  The one reading taken from the engine's own timed
#     programs (the 1 x 8,192 prefill of the sample's wave, the scatter
#     into the pool and the lane, three decode windows through
#     `kda_update`, `paged_attn` and the routed layer at 8 of 320), so it
#     alone sees a row's state scattered into another lane or a carry
#     lost between steps.  REFERENCE_GAP_TOL.
# (2) The PROGRAM'S BLOCKS, one at a time at the served widths, each from
#     the program's OWN input on the request's tokens right-padded with
#     `true_lens` passed: the mixer and the routed layer of every layer
#     (`kda_prefill` / `gqa_prefill`, `ffn`: what the engine's prefill
#     program is made of), the head, ONE DECODE STEP of every KDA mixer
#     (the convolution's shift, `kda_update` over four lanes of which two
#     hold a request, the gated head norm) and of the GQA mixer
#     (`paged_attn` over pages filled from the prefill's rows, two lanes
#     of which one holds the request, the output gate), against the plain
#     reference's same block on the same input at the true length.  The
#     reading is the relative error (2-norms over the features) of what a
#     block adds to the stream, the worst over blocks and positions; a
#     routed block leaves out the positions whose routing margin is under
#     MARGIN_EPS (the program's router reads bfloat16 rows and flips a
#     near-tie at the 8th place, which is no fault), and the share left
#     out is bounded by LOOSE_SHARE_MAX.  BLOCK_ERR_TOL.
# (3) The ROWS handed to the pool and the lane: the K and V rows of the
#     GQA layer (a prefill's, and the row a decode step writes its tail)
#     and the convolution rows of every KDA layer, against the
#     reference's.  ROW_ERR_TOL.
# (4) The GATE: the log decay a key channel and the write strength a head
#     that the program hands its scan (`kda_inputs`' g and beta on the
#     program's own input) against the reference's, a position's 2-norm
#     over channels.  A gate CLAMPED at a bound (GLM's -5) reads the
#     clamped channels' whole distance here and nowhere else: a state row
#     scaled by exp(-5) where exp(-20) was due is 0.7 % of a row in a few
#     channels of 8,192, under every other reading's rounding.
#     GATE_ERR_TOL.
# (5) The SCAN'S OWN ARITHMETIC: the chunked scan's state for the padded
#     row against the reference's token-by-token recurrence at the TRUE
#     length (on the host's float32, every SCAN_HEADS-th head: on the chip
#     thousands of products of exp() drift ten times the scan's own error;
#     PERF.md section 6, PR 41), both on the program's own materialised
#     operands, steep channels and all; and each live lane's state after
#     `kda_update` against one step of the recurrence from what it held,
#     the idle lanes' and every other layer's state BIT-UNCHANGED.  Sound:
#     float32 rounding; a state kept in bfloat16: 2**-9.  STATE_ERR_TOL.
# (6) The STATE FROM THE LAYER'S INPUT: what the layer hands the lane for
#     the padded row, and lane 1's state after the decode step, against
#     the reference's state at the true length by the reference's OWN
#     in-projection, convolution, gate and recurrence.  STATE_FROM_X_TOL.
#
# Readings (my chip runs, PR 58; PERF.md section 6): sound = 23 benchmark
# runs on 21 seeds of the weights (13 of them before the GQA decode block
# was read); each control a whole benchmark run through run.py of a tree
# that carries ONE fault (`.bench_ab/pr58/F_*`), every one `correct:
# false`.  "fp8" is `lax.reduce_precision(., 4, 3)`: the precision below
# the stated bfloat16.
#                          sound                  control
#   (1) mean token gap     0.0 ... 0.0189         lane state zeroed at the
#       (a sound request's WORST token             scatter 1.13 ... 1.33 (the
#       reads up to 0.33)                          blocks sound: only the
#                                                  served tokens see it)
#   (2) blocks             0.0065 ... 0.0068      K/V rows stored in fp8:
#       (the GQA decode step 0.0045 ... 0.0048)    0.0333 (the GQA decode
#                                                  step alone: the prefill
#                                                  attends its unrounded
#                                                  rows, 0.0051); q, k, v
#                                                  after the convolution in
#                                                  fp8: 0.0461; the gated
#                                                  attention output in fp8:
#                                                  0.32 / 0.40 (token gap
#                                                  1.1 ... 1.6)
#   (3) rows               0.0030 ... 0.0031      K/V rows in fp8: 0.0293
#   (4) gate               0.0038 ... 0.0045      decay clamped at -5: 0.574
#       (the steepest decay a step the gate gave:  (every other reading of
#       -33.9 ... -48.5)                           that run sound)
#   (5) scan's arithmetic  2.3e-6 ... 3.3e-6      bfloat16 state 1.68e-3; g
#       (`kda_update` against one step: 0.0)       and beta not zeroed past
#                                                  the true length: 1.33
#   (6) state from input   0.0040 ... 0.0041      q, k, v in fp8: 0.0416;
#                                                  g and beta not zeroed
#                                                  past the true length:
#                                                  1.29 (a bfloat16 state
#                                                  reads 0.0046 here: (5)'s)
# and the share of a routed block's positions left out: 0.281 ... 0.299
# (limit 0.5).  Each limit lies between its two readings, at their
# geometric mean where the control is the precision below: 0.2 is 10.6 x
# the sound mean gap and 5.6 x under the control's; 0.015 is 2.2 x / 2.2 x;
# 0.0095 is 3.1 x / 3.1 x; 0.05 is 11 x / 11 x; 1e-4 is 30 x / 17 x; 0.013
# is 3.2 x / 3.2 x.
REFERENCE_GAP_TOL = 0.2
BLOCK_ERR_TOL = 0.015
ROW_ERR_TOL = 0.0095
GATE_ERR_TOL = 0.05
STATE_ERR_TOL = 1e-4
STATE_FROM_X_TOL = 0.013
MARGIN_EPS = 0.002
LOOSE_SHARE_MAX = 0.5
HEAD_POSITIONS = 128
SCAN_HEADS = 8      # reading (5)'s recurrence runs every eighth head
JUDGE_PAGE = 512    # the page of the pool a GQA decode block attends
DECODE_LIVE = (False, True, False, True)    # the KDA step's four lanes


def _held(config: dict) -> tuple[int, int]:
    ep = config["expert_parallel"]
    n = config["n_routed_experts"]
    return ep["rank"] * n, (ep["rank"] + 1) * n


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run (nested
    groups whole), and what the cut adds: `router_experts` (the router's
    published width), `experts_held` (the range this chip holds) and
    `num_experts` (how many that is: the key the shared
    `engine.moe_experts_hit_pct` reader divides by)."""
    m = {k: config[k] for k in KEYS}
    m["router_experts"] = config["published"]["n_routed_experts"]
    m["experts_held"] = list(_held(config))
    m["num_experts"] = config["n_routed_experts"]
    return m


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def program_config(model: dict, max_seq: int, **extra):
    """SolarOpen2Config for the published keys: only sizes and scalars
    move.  Refuses what the program does not express."""
    import jax.numpy as jnp

    from ray_tpu.models.solar_open2 import SolarOpen2Config

    m, la = model, model["linear_attn_config"]
    refuse = {
        "a rotary embedding in the GQA layers": m["use_rope"],
        "a GQA layer without its output gate": not m["use_gqa_gate"],
        "full-rank KDA gates": m["kda_use_full_proj"],
        "a write strength held to (0, 1)": not m["kda_allow_neg_eigval"],
        "a tied head": m["tie_word_embeddings"],
        "leading dense layers": m["first_k_dense_replace"] != 0,
        "KDA heads other than the attention's, or grouped KDA keys":
            la["num_heads"] != m["num_attention_heads"]
            or la["num_kv_heads"] is not None,
        "a GQA layer past the depth, or none":
            not m["gqa_layers"]
            or max(m["gqa_layers"]) >= m["num_hidden_layers"],
    }
    bad = [what for what, is_so in refuse.items() if is_so]
    if bad:
        raise ValueError(f"the program does not express {bad}")
    return SolarOpen2Config(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        gqa_layers=tuple(m["gqa_layers"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"],
        moe_ffn_dim=m["moe_intermediate_size"],
        n_experts=m["router_experts"],
        experts_held=tuple(m["experts_held"]),
        top_k=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling=float(m["routed_scaling_factor"]),
        norm_eps=float(m["rms_norm_eps"]), max_seq=max_seq,
        dtype=jnp.bfloat16, **extra)


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it.  The bits come from the device's own generator (jax's
    "rbg" keys seeded from the harness's key: the same seed, the same
    weights), as `families/ssm_hybrid.py` found it worth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import solar_open2

    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    wide = jax.random.wrap_key_data(jnp.concatenate([key, key])[:4],
                                    impl="rbg")
    return solar_open2.init_params(wide, cfg)


def reference():
    """The judge of a serve cell: `teacher_forced_gaps(params, prompt,
    served, model)` over the plain reference `refs/solar_open2.py`."""
    return Judge


class Judge:
    """The served tokens' mean gap under the plain reference for every
    request, and for the first one this process judges the five readings
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

        from benchmarks.harness.refs import solar_open2 as ref

        t0 = time.perf_counter()
        gaps = ref.token_gaps(params, prompt, served, model)
        t1 = time.perf_counter()
        mean_gap = sum(gaps) / len(gaps)
        shares = {"token_gap": mean_gap / REFERENCE_GAP_TOL}
        line = {"step": "solar_open2.judge", "mean_token_gap": mean_gap,
                "worst_token_gap": max(gaps), "limit": REFERENCE_GAP_TOL,
                "tokens": len(prompt) + len(served),
                "token_gaps_s": round(t1 - t0, 2)}
        if not cls._blocks_done:
            cls._blocks_done.append(True)
            b = block_errors(params, list(prompt) + list(served[:-1]),
                             model)
            shares.update(
                block_err=b["block"][0] / BLOCK_ERR_TOL,
                row_err=b["rows"][0] / ROW_ERR_TOL,
                gate_err=b["gate"][0] / GATE_ERR_TOL,
                state_err=b["state"][0] / STATE_ERR_TOL,
                state_from_x_err=b["from_x"][0] / STATE_FROM_X_TOL,
                loose_share=b["loose_share"] / LOOSE_SHARE_MAX)
            line.update(
                worst_block_err=b["block"], block_limit=BLOCK_ERR_TOL,
                worst_row_err=b["rows"], row_limit=ROW_ERR_TOL,
                worst_gate_err=b["gate"], gate_limit=GATE_ERR_TOL,
                worst_state_err=b["state"], state_limit=STATE_ERR_TOL,
                worst_state_from_x_err=b["from_x"],
                state_from_x_limit=STATE_FROM_X_TOL,
                loose_share=b["loose_share"], steepest=b["steepest"],
                by_block=b["by_block"],
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


def _program_blocks(cfg, n: int):
    """The program's blocks, each jitted once for a true length n and
    taking the layer's own weights (a subset of its dict), so that every
    layer of a kind runs the one compiled program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kda_layer, solar_open2 as prog
    from ray_tpu.ops import kda, ssm

    lens_of = lambda m: jnp.reshape(m, (1,)).astype(jnp.int32)  # noqa: E731

    def ffn(lp, x):
        live = jnp.arange(x.shape[1])[None, :] < n
        return prog.ffn(x, lp, cfg, live)[0]

    def kda_inputs(lp, x):
        h = prog.rmsnorm(x, lp["norm1"], cfg.norm_eps)
        return kda_layer.inputs(h, lp, cfg, lens_of(jnp.int32(n)),
                                prog.kda_gate)[:5]

    def kda_scan(q, k, v, g, beta):
        return kda.kda_scan(q, k, v, g, beta, cfg.kda_chunk,
                            unbounded=True)[1].astype(cfg.state_dtype)

    def kda_decode(lp, x, at, rows1, state1, i):
        """One decode step of the KDA layer that is the i-th of its kind,
        for the token at position `at`, over FOUR lanes of which two hold
        a request, from the state a prefill handed: lane 1 holds it as
        handed, lane 3 half of it, the idle lanes 0 and 2 twice and three
        times it."""
        live = DECODE_LIVE
        x4 = jnp.repeat(jax.lax.dynamic_index_in_dim(
            x, at, axis=1, keepdims=False), len(live), axis=0)
        conv = jnp.repeat(rows1, len(live), axis=0)
        held = jnp.concatenate([2.0 * state1, state1, 3.0 * state1,
                                0.5 * state1])
        shape = (cfg.count(KDA), len(live)) + state1.shape[1:]
        lane = jnp.zeros(shape, state1.dtype).at[i].set(held)
        lanes, count = ssm.live_lanes(jnp.asarray(live))
        y, _, st = prog.kda_decode(x4, lp, conv, lane, i, lanes, count, cfg)
        idle = jnp.asarray([j for j, on in enumerate(live) if not on])
        written = jnp.sum(jnp.any(st != 0, axis=(1, 2, 3, 4)))
        untouched = (jnp.all(st[i][idle] == held[idle]) & (written == 1))
        ins = kda_layer.decode_inputs(x4, lp, conv, cfg, prog.kda_gate)[1]
        return (y[1].astype(jnp.float32), st[i], held, untouched,
                tuple(a[1::2] for a in ins))

    def gqa_decode(lp, x, at, ks, vs):
        """One decode step of the GQA layer for the token at position
        `at`, over two lanes of which lane 1 holds the request: the pages
        filled from the prefill's rows below it, the token's own row
        landing in the tail."""
        P = ks.shape[1]
        maxp = -(-P // JUDGE_PAGE)

        def pool(rows):
            rows = jnp.pad(rows[0], ((0, maxp * JUDGE_PAGE - P), (0, 0),
                                     (0, 0)))
            leaf = rows.reshape(maxp, JUDGE_PAGE, *rows.shape[1:]).transpose(
                0, 2, 1, 3)
            return jnp.concatenate([jnp.zeros_like(leaf[:1]), leaf])

        x2 = jnp.repeat(jax.lax.dynamic_index_in_dim(
            x, at, axis=1, keepdims=False), 2, axis=0)
        table = jnp.stack([jnp.zeros((maxp,), jnp.int32),
                           jnp.arange(1, maxp + 1, dtype=jnp.int32)])
        pos = jnp.stack([jnp.int32(0), at])
        tail = jnp.zeros((2, ks.shape[2], 8, ks.shape[3]), cfg.dtype)
        y, kt, vt = prog.gqa_decode(x2, lp, pool(ks), pool(vs), tail, tail,
                                    table, pos, pos, 0, cfg)
        return y[1].astype(jnp.float32), kt[1, :, 0], vt[1, :, 0]

    return {
        "gqa_decode": jax.jit(gqa_decode),
        "embed": jax.jit(lambda params, tok: prog.embed_lookup(
            params["embed"], tok, cfg.dtype)),
        "mix": {KDA: jax.jit(lambda lp, x, m: prog.kda_prefill(
                    x, lp, cfg, lens_of(m))),
                GQA: jax.jit(lambda lp, x, m: prog.gqa_prefill(
                    x, lp, cfg, lens_of(m)))},
        "ffn": jax.jit(ffn),
        "kda_inputs": jax.jit(kda_inputs), "kda_scan": jax.jit(kda_scan),
        "kda_decode": jax.jit(kda_decode),
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
        median position's norm)."""
        got, want = (a.reshape(a.shape[0], -1) for a in (got, want))
        size = jnp.linalg.norm(want, axis=-1)
        return (jnp.linalg.norm(got - want, axis=-1)
                / jnp.maximum(size, jnp.median(size)))

    def rel(got, want):
        return (jnp.linalg.norm(got.astype(F32) - want)
                / jnp.linalg.norm(want))

    return {"cut": jax.jit(cut), "err": jax.jit(err), "rel": jax.jit(rel),
            "added": jax.jit(lambda after, before, want_after:
                             err(cut(after) - cut(before),
                                 want_after - cut(before)))}


def block_errors(params, tokens: list[int], model: dict) -> dict:
    """Readings (2)-(6) on one sequence, each block from the program's
    own input, the sequence right-padded and its TRUE length passed.
    Returns {"block", "rows", "gate", "state", "from_x": (the worst
    reading, where), "loose_share": the largest share of a routed block's
    positions left out for a routing margin under MARGIN_EPS, "steepest":
    the lowest log decay a step the program's gate gave, "by_block":
    [kind, how many, median, worst]}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import solar_open2 as ref

    n = len(tokens)
    P = -(-(n + 1) // 128) * 128    # the flash kernel's multiple, and at
    #                                 least one row of padding
    key = (P, n, tuple(sorted((k, str(v)) for k, v in model.items())))
    if key not in _BLOCKS:
        cfg = program_config(model, max_seq=P)
        _BLOCKS[key] = (cfg, _program_blocks(cfg, n), _comparisons(n))
    cfg, fn, cmp = _BLOCKS[key]
    # the padding is token ids of its own, not zeros: what is computed
    # past the true length must not reach what is compared
    pad = [(7 * i + 3) % model["vocab_size"] for i in range(P - n)]
    tok = jnp.asarray([list(tokens) + pad], jnp.int32)
    last = jnp.int32(n - 1)
    cpu = jax.devices("cpu")[0]
    hi = jax.default_matmul_precision("highest")

    block, rows, gate, state, from_x, loose = [], [], [], [], [], [0.0]
    steepest = 0.0
    x = fn["embed"](params, tok)
    seen = {KDA: 0, GQA: 0}
    for lid, lp in enumerate(params["layers"]):
        kind = ref.kind(lid, model)
        # what of a layer's weights each block reads, handed over as a
        # subset: the layers of one kind share ONE compiled program a block
        mp = {k: lp[k] for k in ref.MIXER_KEYS[kind]}
        fp = {k: lp[k] for k in ref.FF_KEYS}
        nth = jnp.int32(seen[kind])
        seen[kind] += 1
        xc = cmp["cut"](x)
        y_ref, info = ref.mixer(xc, lp, lid, model)
        x_mid_ref = xc + y_ref
        x_mid, kept = fn["mix"][kind](mp, x, jnp.int32(n))
        block.append((f"{lid}.{kind}", np.asarray(
            cmp["added"](x_mid, x, x_mid_ref))))
        if kind == GQA:
            for name, got in zip("kv", kept):
                rows.append((f"{lid}.{name}_rows", np.asarray(cmp["err"](
                    cmp["cut"](got), info[name]))))
            # one decode step over the pages those rows fill: the token
            # at n - 1 attends the rows below it and its own in the tail
            d1, k1, v1 = fn["gqa_decode"](mp, x, last, *kept)
            block.append((f"{lid}.gqa_decode_step", np.asarray(cmp["err"](
                d1[None], (x_mid_ref - xc)[n - 1:]))))
            for name, got in (("k", k1), ("v", v1)):
                rows.append((f"{lid}.{name}_tail", np.asarray(cmp["err"](
                    got.astype(jnp.float32)[None], info[name][n - 1:n]))))
        else:
            conv_rows, st = kept
            rows.append((f"{lid}.conv_rows", np.asarray(cmp["err"](
                conv_rows[0].astype(jnp.float32), info["conv"]))))
            from_x.append((f"{lid}.prefill", float(cmp["rel"](
                st[0], info["state"]))))
            # (4) the gate the program hands its scan against the
            # reference's own, on the same input
            ins = fn["kda_inputs"](mp, x)
            with hi:
                want_g, want_b = ref._jitted(model)["gate"](xc, mp)
            g_own = cmp["cut"](ins[3])
            steepest = min(steepest, float(jnp.min(g_own)))
            gate.append((f"{lid}.decay", np.asarray(cmp["err"](
                g_own, want_g))))
            gate.append((f"{lid}.beta", np.asarray(cmp["err"](
                cmp["cut"](ins[4]), want_b))))
            # (5) the chunked scan of the PADDED row against the
            # token-by-token recurrence at the TRUE length, on the host,
            # every SCAN_HEADS-th head, both on the program's own
            # materialised operands
            on_host = [jax.device_put(np.asarray(a[0, :n, ::SCAN_HEADS]),
                                      cpu) for a in ins]
            _, want = ref._jitted(model)["recurrence"](*on_host)
            state.append((f"{lid}.scan", float(cmp["rel"](
                np.asarray(fn["kda_scan"](*ins)[0, ::SCAN_HEADS],
                           np.float32), np.asarray(want)))))
            # one decode step from what the program hands at n - 1
            _, (rows1, st1) = fn["mix"][kind](mp, x, jnp.int32(n - 1))
            d1, after, held, untouched, step = fn["kda_decode"](
                mp, x, last, rows1, st1, nth)
            with hi:
                wants = [ref._jitted(model)["recurrence"](
                    *(a[j:j + 1] for a in step),
                    held[lane].astype(jnp.float32))[1]
                    for j, lane in enumerate((1, 3))]
            state.append((f"{lid}.update", max(
                float(cmp["rel"](after[lane], w))
                for lane, w in zip((1, 3), wants))))
            state.append((f"{lid}.idle_lanes",
                          0.0 if bool(untouched) else float("inf")))
            from_x.append((f"{lid}.decode", float(cmp["rel"](
                after[1], info["state"]))))
            block.append((f"{lid}.decode_step", np.asarray(cmp["err"](
                d1[None], (x_mid_ref - xc)[n - 1:]))))
        x_out = fn["ffn"](fp, x_mid)
        with hi:
            routed, shared, margin = ref._jitted(model)["ff"](
                cmp["cut"](x_mid), fp)
        e = np.asarray(cmp["added"](x_out, x_mid,
                                    cmp["cut"](x_mid) + routed + shared))
        firm = np.asarray(margin) >= MARGIN_EPS
        loose.append(1.0 - float(firm.mean()))
        block.append((f"{lid}.ffn", e[firm]))
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
    for name, e in block + rows + gate + state + from_x:
        kinds.setdefault(name.split(".", 1)[-1], []).append(
            np.atleast_1d(np.asarray(e, np.float64)))
    return {"block": worst_of(block), "rows": worst_of(rows),
            "gate": worst_of(gate), "state": worst_of(state),
            "from_x": worst_of(from_x), "loose_share": max(loose),
            "steepest": steepest,
            "by_block": [[kind, len(es),
                          float(np.median([np.median(e) for e in es
                                           if np.size(e)] or [0.0])),
                          float(max([np.max(e) for e in es if np.size(e)]
                                    or [0.0]))]
                         for kind, es in kinds.items()]}


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal: one period, half of eight experts
    held, two heads (the interpreted kernels' compiles cost by the head)."""
    config.update(
        hidden_size=64, num_attention_heads=2, num_key_value_heads=1,
        head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        vocab_size=512, n_routed_experts=4, num_experts_per_tok=2,
        num_hidden_layers=4, gqa_layers=[0],
        linear_attn_config=dict(config["linear_attn_config"], num_heads=2,
                                head_dim=16))
    config["published"] = dict(config["published"], n_routed_experts=8)
    config["expert_parallel"] = {"chips": 2, "rank": 0}


# ---------------------------------------------------------------- counts
def _n(m: dict, kind: str) -> int:
    n_gqa = len(m["gqa_layers"])
    return n_gqa if kind == GQA else m["num_hidden_layers"] - n_gqa


def _held_experts(m: dict) -> int:
    return m["experts_held"][1] - m["experts_held"][0]


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _kda_params(m: dict) -> int:
    """W_q, W_k, W_v, W_o, the decay and output gates (two low-rank
    pairs of the head's width), beta."""
    d, la = m["hidden_size"], m["linear_attn_config"]
    inner, r = la["num_heads"] * la["head_dim"], la["head_dim"]
    return 4 * d * inner + 2 * (d * r + r * inner) + d * la["num_heads"]


def _gqa_params(m: dict) -> int:
    """W_q, W_gate, W_o at the query heads' width; W_k, W_v at the kv
    heads'."""
    d, hd = m["hidden_size"], m["head_dim"]
    return (3 * d * m["num_attention_heads"] * hd
            + 2 * d * m["num_key_value_heads"] * hd)


def _non_expert_matmul_params(m: dict) -> int:
    """Every matmul weight but the experts, the head among them; the
    embedding lookup is no matmul."""
    d = m["hidden_size"]
    return (_n(m, KDA) * _kda_params(m) + _n(m, GQA) * _gqa_params(m)
            + m["num_hidden_layers"] * (d * m["router_experts"]
                                        + m["n_shared_experts"]
                                        * _expert_params(m))
            + m["vocab_size"] * d)


def param_count(m: dict) -> int:
    """Parameters as the program holds them: the embedding and the head
    apart, two norms a layer and a final norm, a KDA layer's convolution
    taps, A_log, dt_bias and head norm, the HELD experts, the expert
    biases."""
    d, la = m["hidden_size"], m["linear_attn_config"]
    inner = la["num_heads"] * la["head_dim"]
    small = ((2 * m["num_hidden_layers"] + 1) * d
             + _n(m, KDA) * (la["short_conv_kernel_size"] * 3 * inner
                             + la["num_heads"] + inner + la["head_dim"])
             + m["num_hidden_layers"] * m["router_experts"])
    return (_non_expert_matmul_params(m) + m["vocab_size"] * d + small
            + m["num_hidden_layers"] * _held_experts(m) * _expert_params(m))


def matmul_params(m: dict) -> int:
    """Parameters a token's step MULTIPLIES on this chip: of a routed
    layer the share of the selected experts that is held here."""
    active = (m["num_experts_per_tok"] * _held_experts(m)
              / m["router_experts"])
    return int(_non_expert_matmul_params(m)
               + m["num_hidden_layers"] * active * _expert_params(m))


def lane_state_bytes(m: dict) -> int:
    """Bytes of ONE lane's state matrices in ONE KDA layer (float32)."""
    la = m["linear_attn_config"]
    return 4 * la["num_heads"] * la["head_dim"] ** 2


def expected_experts_hit(m: dict, lanes: int) -> float:
    """The held experts a step of `lanes` live lanes hits in one routed
    layer, if each lane's choice is uniform over the router's width."""
    p = m["num_experts_per_tok"] / m["router_experts"]
    return _held_experts(m) * (1.0 - (1.0 - p) ** lanes)


def decode_step_bytes(m: dict, lanes: int = 64) -> float:
    """Bytes a decode step of a FULL batch must stream at the least:
    every matmul weight outside the experts once (bf16), the experts the
    batch HITS once (an expert nobody chose is never read), every live
    lane's state matrices of every KDA layer read and written once, and
    the GQA layer's K and V rows of a docs-mix lane (~6.5 k tokens)."""
    kv = (lanes * 6500 * _n(m, GQA) * 2 * 2
          * m["num_key_value_heads"] * m["head_dim"])
    return (2.0 * (_non_expert_matmul_params(m)
                   + m["num_hidden_layers"] * expected_experts_hit(m, lanes)
                   * _expert_params(m))
            + 2.0 * lanes * _n(m, KDA) * lane_state_bytes(m) + kv)


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name."""
    if kernel == "moe_gmm":
        return m["num_hidden_layers"]
    if kernel in ("kda_update", "kda_scan"):
        return _n(m, KDA)
    if kernel in ("paged_attn", "flash_fwd"):
        return _n(m, GQA)
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


def kda_update_cost(m: dict, lane_steps: float) -> tuple[float, float]:
    """(flops, bytes) the `kda_update` calls NEED for `lane_steps` (lane,
    layer, step) triples that were work (`ops/kda.update_cost`'s
    arithmetic, kept here so that the yardstick does not import the
    program): the lane's state read and written once, its vectors in and
    out, and a state element's decay, two sums and write.  A lane that
    holds no request is no work."""
    la = m["linear_attn_config"]
    H, dk = la["num_heads"], la["head_dim"]
    nbytes = 2 * 4 * H * dk * dk + 4 * H * (3 * dk + 2 * dk + 1)
    return 7.0 * H * dk * dk * lane_steps, float(nbytes) * lane_steps


def kda_scan_cost(m: dict, positions: float, rows: float
                  ) -> tuple[float, float]:
    """(flops, bytes) ONE KDA layer's `kda_scan` calls NEED for
    `positions` true positions in `rows` prompts: `kda_cost.scan_cost` for
    a gate WITHOUT a bound (A and B once a level of the halved anchors)."""
    from benchmarks.harness import kda_cost

    la = m["linear_attn_config"]
    return kda_cost.scan_cost(la["num_heads"], la["head_dim"], positions,
                              rows, halved=True)


def _row(m: dict) -> tuple[float, float]:
    """(flops, bytes) of ONE cached row of ONE GQA layer in a decode
    step: scored by every query head and taken as value (a multiply-add
    two operations), read once a kv head as K and as V (bf16)."""
    hd = m["head_dim"]
    return (4.0 * m["num_attention_heads"] * hd,
            2.0 * 2 * m["num_key_value_heads"] * hd)


def paged_attn_cost(m: dict, rows: float) -> tuple[float, float]:
    """(flops, bytes) the `paged_attn` calls of ONE GQA layer NEED to
    attend `rows` context rows in all (summed over lanes and steps)."""
    fl, by = _row(m)
    return fl * rows, by * rows


def flash_fwd_cost(m: dict, lens: list[int]) -> tuple[float, float]:
    """(flops, bytes) ONE GQA layer's causal call (`flash_fwd`) needs for
    sequences of the given TRUE lengths at 64 query heads over 8 kv heads
    of 128: a query scores every position up to its own and takes it as
    value; q and o once a query head, k and v once a kv head, bf16."""
    H, G, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                m["head_dim"])
    pairs = sum(s * (s + 1) // 2 for s in lens)
    return (4.0 * H * hd * pairs,
            2.0 * (2 * H + 2 * G) * hd * sum(lens))
