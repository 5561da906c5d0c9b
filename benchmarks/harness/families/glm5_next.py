"""Model family `glm5_next`: the decoder `ray_tpu/models/glm5_next.py`
serves (`model_type` `glm5_next_text`, e.g. GLM-5.3-Flash: KDA
linear-attention layers beside latent-attention layers read through a
learned top-k selection over pooled index keys, a four-stream mHC
residual around every sublayer, routed experts of which this chip holds a
range, a shared expert).

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

_PROGRAM = os.path.join(spec.ROOT, "ray_tpu", "models", "glm5_next.py")
if not os.path.isfile(_PROGRAM):
    raise SystemExit(
        f"model family glm5_next: this checkout's program has no {_PROGRAM}"
        " (ray_tpu.models.glm5_next), so it cannot serve the family")

KEYS = ("attention_bias", "first_k_dense_replace", "hc_eps", "hc_mult",
        "hc_sinkhorn_iters", "head_dim", "hidden_act", "hidden_size",
        "index_head_dim", "index_kpool", "index_kpool_always_select_tail",
        "index_kpool_compress", "index_n_heads", "index_topk",
        "index_share_for_mtp_iteration", "indexer_rope_interleave",
        "indexer_types", "intermediate_size", "kv_lora_rank", "layer_types",
        "linear_attn_config", "max_position_embeddings", "mhc",
        "mla_use_nope", "mlp_layer_types", "model_type",
        "moe_intermediate_size", "n_group", "n_routed_experts",
        "n_shared_experts", "norm_topk_prob", "num_attention_heads",
        "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
        "num_nextn_predict_layers", "q_lora_rank", "qk_head_dim",
        "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
        "routed_scaling_factor", "scoring_func", "swiglu_limit",
        "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
        "vocab_size")
KDA, DSA = "linear_attention", "deepseek_sparse_attention"

# Serve: `correct` for this family rests on SIX readings, each with its
# own limit (`Judge` folds them into the one number the harness compares).
# The first is taken for every sample request, the others for the first
# request a replica judges, on its first BLOCK_POSITIONS positions (they
# cost a reference pass a block, and a run has 345 s).
#
# (1) SERVED TOKENS, end to end: the MEAN teacher-forced gap of a
#     request's served tokens under the plain float32 reference (a
#     routed model's near-ties flip and cascade: the worst token of a
#     sound request reads whole units).  The one reading taken
#     from the engine's own timed programs (the prefill of the sample's
#     wave, the scatter into both pool leaves and the lane, three decode
#     windows through the indexer, the selection, the gather and
#     `kda_update`).  Limit REFERENCE_GAP_TOL.
# (2) The PROGRAM'S BLOCKS, one at a time at the served widths, each
#     from the program's OWN input on the request's tokens right-padded
#     with `true_lens` passed: both sublayers of every layer WITH their
#     residual maps (so a Sinkhorn cut short shows), the head, and one
#     DECODE STEP of every mixer (the KDA step over four lanes of which
#     two hold a request; the sparse step over a pool filled from the
#     prefill's rows), against the reference's same block.  The sparse
#     mixer's reference is GIVEN the groups the program chose (reading
#     (5) holds the choice itself), and a routed block leaves out the
#     positions whose routing margin is under MARGIN_EPS.  The reading
#     is the relative error of what a block adds to the four streams.
#     Limit BLOCK_ERR_TOL.
# (3) The ROWS handed to the pools and the lane: the latent rows, the
#     pooled index keys of the complete groups, the convolution rows and
#     the incomplete group's sum, against the reference's.  ROW_ERR_TOL.
# (4) The SCAN'S OWN ARITHMETIC: the chunked scan's state for the padded
#     row against the reference's token-by-token recurrence at the true
#     length on the program's own materialised operands, and each live
#     lane's state after `kda_update` against one step of the
#     recurrence from what it held, the idle lanes' and the other
#     layers' state bit-unchanged.  Sound: float32 rounding; a state
#     kept in bfloat16: 2**-9.  STATE_ERR_TOL.
# (5) The STATE FROM THE LAYER'S INPUT: what the layer hands the lane
#     against the reference's own in-projection, convolution, gate and
#     recurrence.  STATE_FROM_X_TOL.
# (6) The SELECTION: the share of the reference's chosen groups that the
#     program did not choose (prefill rows past the selection's size,
#     and the decode step), bounded by SELECT_MISS_TOL (index scores in
#     bfloat16 flip near-ties at the 512th place); and the rows of a
#     query's own incomplete group, which no tie can touch: one missing
#     is a fault (the reading is then infinite).
#
# Readings (my chip runs, PR 41; PERF.md section 6): sound = 16 benchmark
# runs on 16 seeds of the weights; each control a whole benchmark run
# through run.py of a tree that carries the fault, `correct: false`:
#                          sound               control
#   (1) mean token gap     4e-4 ... 0.086      no decay gate 2.46 ... 2.77
#       (a sound request's WORST token reads up to 0.84)
#   (2) blocks             0.0087 ... 0.0109   Sinkhorn once 0.66; no decay
#                                              gate 1.08; own group not
#                                              selected 0.9985
#   (3) rows               0.0040 ... 0.0043   (no control aims at it; the
#                                              limit is mla_moe's room: 2.8 x)
#   (4) scan's arithmetic  1.3e-5 ... 3.9e-5   bfloat16 state 1.68e-3
#   (5) state from input   0.0043 ... 0.0045   no decay gate 3.32
#   (6) selection missed   8e-4 ... 0.002      last 2,048 rows 0.14 (prefill
#                                              rows) / 0.22 (decode step);
#       own group's rows   all present         one missing: infinite
# and the share of a routed block's positions left out for a routing
# margin under MARGIN_EPS: 0.263 ... 0.291 (limit 0.5).  Each limit lies
# between its two readings with room on both sides: 0.45 is 5.2 x the
# sound mean gap and 5.5 x under the control's; 0.03 is 2.7 x / 22 x;
# 3e-4 is 7.7 x / 5.6 x; 0.03 is 6.7 x / 110 x; 0.05 is 25 x / 2.8 x.
REFERENCE_GAP_TOL = 0.45
BLOCK_ERR_TOL = 0.03
ROW_ERR_TOL = 0.012
STATE_ERR_TOL = 3e-4
STATE_FROM_X_TOL = 0.03
SELECT_MISS_TOL = 0.05
MARGIN_EPS = 0.002
LOOSE_SHARE_MAX = 0.5
HEAD_POSITIONS = 128
# the blocks are read on the request's first positions: past the
# selection's size by a third (687 complete groups for 512 kept), ending
# inside a group (two positions of the last one), and a run has 345 s
BLOCK_POSITIONS = 2750
SCAN_HEADS = 8      # reading (4)'s recurrence runs every eighth head
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
    """Glm5NextConfig for the published keys: only sizes and scalars
    move.  Refuses what the program does not express."""
    import jax.numpy as jnp

    from ray_tpu.models.glm5_next import Glm5NextConfig
    from ray_tpu.ops.kda import max_chunk

    m, la = model, model["linear_attn_config"]
    refuse = {
        "no mHC residual": not m["mhc"],
        "another activation than silu": m["hidden_act"] != "silu",
        "a tied head": m["tie_word_embeddings"],
        "a rotary part of the attention": m["qk_rope_head_dim"] != 0
        or not m["mla_use_nope"] or m["qk_head_dim"] != m["qk_nope_head_dim"],
        "a bias in attention": m["attention_bias"],
        "another router than sigmoid noaux_tc without groups":
            m["scoring_func"] != "sigmoid" or m["topk_method"] != "noaux_tc"
            or m["n_group"] != 1 or m["topk_group"] != 1
            or not m["norm_topk_prob"],
        "an index pool that keeps more than the pooled key, or no tail":
            not m["index_kpool_compress"]
            or not m["index_kpool_always_select_tail"],
        "an indexer that is not full in every layer":
            set(m["indexer_types"]) != {"full"},
        "layer lists that do not name num_hidden_layers layers":
            len(m["layer_types"]) != m["num_hidden_layers"]
            or len(m["mlp_layer_types"]) != m["num_hidden_layers"],
        "KDA heads other than the attention's":
            la["num_heads"] != m["num_attention_heads"],
        "grouped keys": m["num_key_value_heads"] != m["num_attention_heads"],
    }
    bad = [what for what, is_so in refuse.items() if is_so]
    if bad:
        raise ValueError(f"the program does not express {bad}")
    return Glm5NextConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        layer_types=tuple(m["layer_types"]),
        ffn_types=tuple(m["mlp_layer_types"]),
        n_heads=m["num_attention_heads"], kda_head_dim=la["head_dim"],
        conv_kernel=la["short_conv_kernel_size"],
        gate_lower_bound=float(la["gate_lower_bound"]),
        kda_chunk=min(32, max_chunk(float(la["gate_lower_bound"]))),
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_head_dim=m["qk_nope_head_dim"], v_head_dim=m["v_head_dim"],
        index_heads=m["index_n_heads"], index_dim=m["index_head_dim"],
        index_rope_dim=m["index_head_dim"] // 2,
        index_topk=m["index_topk"], index_pool=m["index_kpool"],
        ffn_dim=m["intermediate_size"],
        moe_ffn_dim=m["moe_intermediate_size"],
        n_experts=m["router_experts"],
        experts_held=tuple(m["experts_held"]),
        top_k=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        routed_scaling=float(m["routed_scaling_factor"]),
        swiglu_limit=float(m["swiglu_limit"]), hc_mult=m["hc_mult"],
        hc_iters=m["hc_sinkhorn_iters"], hc_eps=float(m["hc_eps"]),
        norm_eps=float(m["rms_norm_eps"]), max_seq=max_seq,
        dtype=jnp.bfloat16, **extra)


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it.  The bits come from the device's own generator (jax's
    "rbg" keys seeded from the harness's key: the same seed, the same
    weights), as `families/ssm_hybrid.py` found it worth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import glm5_next

    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    wide = jax.random.wrap_key_data(jnp.concatenate([key, key])[:4],
                                    impl="rbg")
    return glm5_next.init_params(wide, cfg)


def reference():
    """The judge of a serve cell: `teacher_forced_gaps(params, prompt,
    served, model)` over the plain reference `refs/glm5_next.py`."""
    return Judge


class Judge:
    """The served tokens' worst gap under the plain reference for every
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

        from benchmarks.harness.refs import glm5_next as ref

        t0 = time.perf_counter()
        gaps = ref.token_gaps(params, prompt, served, model)
        t1 = time.perf_counter()
        # a routed model's near-ties flip under bfloat16 and cascade: a
        # sound request's worst token reads whole units, so the reading
        # is the request's MEAN gap (as `lfm2_moe` and `mla_moe` hold it)
        mean_gap = sum(gaps) / len(gaps)
        shares = {"token_gap": mean_gap / REFERENCE_GAP_TOL}
        line = {"step": "glm5_next.judge", "mean_token_gap": mean_gap,
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
                row_err=b["rows"][0] / ROW_ERR_TOL,
                state_err=b["state"][0] / STATE_ERR_TOL,
                state_from_x_err=b["from_x"][0] / STATE_FROM_X_TOL,
                select_miss=b["select"][0] / SELECT_MISS_TOL,
                loose_share=b["loose_share"] / LOOSE_SHARE_MAX)
            line.update(
                worst_block_err=b["block"], block_limit=BLOCK_ERR_TOL,
                worst_row_err=b["rows"], row_limit=ROW_ERR_TOL,
                worst_state_err=b["state"], state_limit=STATE_ERR_TOL,
                worst_state_from_x_err=b["from_x"],
                state_from_x_limit=STATE_FROM_X_TOL,
                worst_select_miss=b["select"],
                select_limit=SELECT_MISS_TOL,
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
MIXER_KEYS = {
    KDA: ("norm1", "w_qkv", "conv_w", "wf1", "wf2", "A_log", "dt_bias",
          "w_beta", "wg1", "wg2", "o_norm", "wo", "hc_mix"),
    DSA: ("norm1", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "w_uk", "w_uv",
          "wo", "wqi", "wki", "ki_norm_w", "ki_norm_b", "ww", "hc_mix")}
FFN_KEYS = {"dense": ("norm2", "w1", "w3", "w2", "hc_ffn"),
            "sparse": ("norm2", "router", "expert_bias", "w13", "w2", "sw1",
                       "sw3", "sw2", "hc_ffn")}


def _program_blocks(cfg, n: int):
    """The program's blocks, each jitted once for a true length n and
    taking the layer's own weights (a subset of its dict), so that every
    layer of a kind runs the one compiled program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import glm5_next as prog
    from ray_tpu.ops import kda, ssm

    F32 = jnp.float32
    lens_of = lambda m: jnp.reshape(m, (1,)).astype(jnp.int32)  # noqa: E731

    def kda_mix(lp, X, m):
        return prog.sublayer(X, lp["hc_mix"], cfg, lambda x:
                             prog.kda_prefill(x, lp, cfg, lens_of(m)))

    def dsa_mix(lp, X, m):
        return prog.sublayer(X, lp["hc_mix"], cfg, lambda x:
                             prog.dsa_prefill(x, lp, cfg, lens_of(m),
                                              want_selection=True))

    def ffn(kind):
        lid = cfg.ffn_types.index(kind)     # any layer of the kind

        def run(lp, X):
            live = jnp.arange(X.shape[1])[None, :] < n
            return prog.sublayer(X, lp["hc_ffn"], cfg,
                                 lambda x: prog.ffn(x, lp, lid, cfg, live))[0]
        return jax.jit(run)

    def mixer_input(X, lp):
        pre, _, _ = prog.mhc_maps(X, lp["hc_mix"], cfg)
        return jnp.einsum("...n,...nd->...d", pre,
                          X.astype(F32)).astype(cfg.dtype)

    def kda_inputs(lp, X):
        h = prog.rmsnorm(mixer_input(X, lp), lp["norm1"], cfg.norm_eps)
        return prog.kda_inputs(h, lp, cfg, lens_of(jnp.int32(n)))[:5]

    def kda_scan(q, k, v, g, beta):
        return kda.kda_scan(q, k, v, g, beta, cfg.kda_chunk)[1].astype(
            cfg.state_dtype)

    def kda_decode(lp, X, at, rows1, state1, i):
        """One decode step of the KDA layer that is the i-th of its kind,
        for the token at position `at`, over FOUR lanes of which two hold
        a request, from the state a prefill handed: lane 1 holds it as
        handed, lane 3 half of it, the idle lanes 0 and 2 twice and three
        times it."""
        live = DECODE_LIVE
        X4 = jnp.repeat(jax.lax.dynamic_index_in_dim(
            X, at, axis=1, keepdims=False), len(live), axis=0)
        conv = jnp.repeat(rows1, len(live), axis=0)
        held = jnp.concatenate([2.0 * state1, state1, 3.0 * state1,
                                0.5 * state1])
        shape = (cfg.count(KDA), len(live)) + state1.shape[1:]
        lane = jnp.zeros(shape, state1.dtype).at[i].set(held)
        lanes, count = ssm.live_lanes(jnp.asarray(live))

        def mixer(x):
            y, _, st = prog.kda_decode(x, lp, conv, lane, i, lanes, count,
                                       cfg)
            return y, st

        X_new, st = prog.sublayer(X4, lp["hc_mix"], cfg, mixer)
        idle = jnp.asarray([j for j, on in enumerate(live) if not on])
        written = jnp.sum(jnp.any(st != 0, axis=(1, 2, 3, 4)))
        untouched = (jnp.all(st[i][idle] == held[idle]) & (written == 1))
        ins = prog.kda_decode_inputs(mixer_input(X4, lp), lp, conv, cfg)[1]
        return ((X_new.astype(F32) - X4.astype(F32))[1], st[i], held,
                untouched, tuple(a[1::2] for a in ins))

    def dsa_decode(lp, X, at, latent, index, ipart):
        """One decode step of a sparse layer for the token at position
        `at` = n - 1: the pool filled from the prefill's rows below it,
        lane 0 idle, lane 1 the request."""
        page = 512
        P = latent.shape[1]
        maxp = -(-P // page)
        g = cfg.index_pool

        def pool(rows, per):
            rows = jnp.pad(rows[0], ((0, maxp * per - rows.shape[1]),
                                     (0, 0), (0, 0)))
            leaf = rows.reshape(maxp, per, 1, -1).transpose(0, 2, 1, 3)
            return jnp.concatenate([jnp.zeros_like(leaf[:1]), leaf])

        table = jnp.stack([jnp.zeros((maxp,), jnp.int32),
                           jnp.arange(1, maxp + 1, dtype=jnp.int32)])
        pos = jnp.stack([jnp.int32(0), at])
        X2 = jnp.repeat(jax.lax.dynamic_index_in_dim(
            X, at, axis=1, keepdims=False), 2, axis=0)
        lanes, count = ssm.live_lanes(jnp.asarray([False, True]))
        lt = jnp.zeros((2, 1, 8, cfg.kv_lora_rank), cfg.dtype)
        it = jnp.zeros((2, 1, 2, cfg.index_dim), cfg.dtype)

        def mixer(x):
            y, _, _, _, sel = prog.dsa_decode(
                x, lp, pool(latent, page), pool(index, page // g), lt, it,
                jnp.repeat(ipart, 2, axis=0), table, pos, pos, 0, lanes,
                count, cfg, want_selection=True)
            return y, sel

        X_new, sel = prog.sublayer(X2, lp["hc_mix"], cfg, mixer)
        return ((X_new.astype(F32) - X2.astype(F32))[1],
                tuple(a[1] for a in sel))

    return {
        "embed": jax.jit(lambda params, tok: prog.embed_streams(
            params, tok, cfg)),
        "mix": {KDA: jax.jit(kda_mix), DSA: jax.jit(dsa_mix)},
        "ffn": {kind: ffn(kind) for kind in set(cfg.ffn_types)},
        "kda_inputs": jax.jit(kda_inputs), "kda_scan": jax.jit(kda_scan),
        "kda_decode": jax.jit(kda_decode), "dsa_decode": jax.jit(dsa_decode),
        "head": jax.jit(lambda params, X: prog.project_logits(
            params, prog.final_hidden(params, X, cfg))),
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
    Returns {"block", "rows", "state", "from_x", "select": (the worst
    reading, where), "loose_share": the largest share of a routed
    block's positions left out for a routing margin under MARGIN_EPS,
    "by_block": [kind, how many, median, worst]}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import glm5_next as ref

    n = len(tokens)
    P = -(-(n + 1) // 128) * 128
    key = (P, n, tuple(sorted((k, str(v)) for k, v in model.items())))
    if key not in _BLOCKS:
        cfg = program_config(model, max_seq=P)
        _BLOCKS[key] = (cfg, _program_blocks(cfg, n), _comparisons(n))
    cfg, fn, cmp = _BLOCKS[key]
    g, top = cfg.index_pool, cfg.index_topk // cfg.index_pool
    pad = [(7 * i + 3) % model["vocab_size"] for i in range(P - n)]
    tok = jnp.asarray([list(tokens) + pad], jnp.int32)
    last = jnp.int32(n - 1)
    hi = jax.default_matmul_precision("highest")

    block, rows, state, from_x, select, loose = [], [], [], [], [], [0.0]
    X = fn["embed"](params, tok)
    seen = {KDA: 0, DSA: 0}
    for lid, lp in enumerate(params["layers"]):
        kind = model["layer_types"][lid]
        ffn_kind = model["mlp_layer_types"][lid]
        mp = {k: lp[k] for k in MIXER_KEYS[kind]}
        fp = {k: lp[k] for k in FFN_KEYS[ffn_kind]}
        nth = jnp.int32(seen[kind])
        seen[kind] += 1
        Xc = cmp["cut"](X)
        with hi:
            pre, post, res = ref._jitted(model)["maps"](Xc, lp["hc_mix"])
            x_in = ref.mhc_in(Xc, pre)
        if kind == KDA:
            X_mid, (conv_rows, st) = fn["mix"][kind](mp, X, jnp.int32(n))
            y, info = ref.mixer(x_in, lp, lid, model)
            # (4) the scan on the program's own operands, padded, against
            # the token-by-token recurrence at the true length
            # (the recurrence runs on the HOST's float32, every
            # SCAN_HEADS-th head: on the chip its thousands of products
            # of exp() drift 2e-5 to 5e-4 from float64, ten times the
            # scan's own 2e-6; my chip run, PR 41)
            ins = fn["kda_inputs"](mp, X)
            on_host = [jax.device_put(np.asarray(a[0, :n, ::SCAN_HEADS]),
                                      jax.devices("cpu")[0]) for a in ins]
            _, want = ref._jitted(model)["recurrence"](*on_host)
            state.append((f"{lid}.scan", float(cmp["rel"](
                np.asarray(fn["kda_scan"](*ins)[0, ::SCAN_HEADS],
                           np.float32), np.asarray(want)))))
            from_x.append((f"{lid}.prefill", float(cmp["rel"](
                st[0], info["state"]))))
            rows.append((f"{lid}.conv_rows", np.asarray(cmp["err"](
                conv_rows[0].astype(jnp.float32), info["conv"]))))
            # one decode step from what the program hands at n - 1
            _, (rows1, st1) = fn["mix"][kind](mp, X, jnp.int32(n - 1))
            d1, after, held, untouched, step = fn["kda_decode"](
                mp, X, last, rows1, st1, nth)
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
        else:
            X_mid, (lat, idx, ipart, (mask, chosen)) = fn["mix"][kind](
                mp, X, jnp.int32(n))
            _, (lat1, idx1, ipart1, _) = fn["mix"][kind](
                mp, X, jnp.int32(n - 1))
            d1, (groups, ok, rpos, admitted) = fn["dsa_decode"](
                mp, X, last, lat1, idx1, ipart1)
            G = n // g
            picked = np.array(chosen[0, :n, :G])
            step_pick = np.zeros((G + 1,), bool)
            step_pick[np.minimum(np.asarray(groups)[np.asarray(ok)], G)] = \
                True
            picked[n - 1] = step_pick[:G]   # the LAST row: the decode step's
            _, own = ref.mixer(x_in, lp, lid, model)
            y, info = ref.mixer(x_in, lp, lid, model,
                                chosen=jnp.asarray(picked))
            # (6) the choice itself, where it is a choice
            want_pick = np.asarray(own["chosen"])
            sparse = (np.arange(n) + 1) // g > top
            miss = (want_pick & ~picked).sum(-1) / top
            select.append((f"{lid}.prefill_rows", float(
                miss[:n - 1][sparse[:n - 1]].mean()) if sparse[:n - 1].any()
                else 0.0))
            select.append((f"{lid}.decode_step", float(miss[n - 1])))
            t = np.arange(n)
            own_rows = ((t[None, :] >= ((t + 1) // g * g)[:, None])
                        & (t[None, :] <= t[:, None]))
            lost = int((own_rows[:n - 1]
                        & ~np.asarray(mask[0, :n - 1, :n])).sum())
            step_rows = set(np.asarray(rpos)[np.asarray(admitted)].tolist())
            lost += sum(1 for s in range(n // g * g, n)
                        if s not in step_rows)
            select.append((f"{lid}.own_group",
                           float("inf") if lost else 0.0))
            rows.append((f"{lid}.latent", np.asarray(cmp["err"](
                cmp["cut"](lat[:, :, 0]), info["latent"]))))
            rows.append((f"{lid}.index", np.asarray(cmp["err"](
                idx[0, :G, 0].astype(jnp.float32), info["index"]))))
            # (empty where the true length ends a group: both are 0)
            rows.append((f"{lid}.ipart", float(
                jnp.linalg.norm(ipart[0] - info["ipart"])
                / jnp.maximum(jnp.linalg.norm(info["ipart"]), 1.0))))
        with hi:
            X_ref = ref.mhc_out(Xc, y, post, res)
        e = np.asarray(cmp["added"](X_mid, X, X_ref))
        block.append((f"{lid}.{kind}", e[:n - 1] if kind == DSA else e))
        want_d1 = (X_ref - Xc)[n - 1]
        block.append((f"{lid}.decode_step", np.asarray(cmp["err"](
            d1[None], want_d1[None]))))
        X_out = fn["ffn"][ffn_kind](fp, X_mid)
        X_ffn, margin = ref.sublayer(
            cmp["cut"](X_mid), lp["hc_ffn"], model,
            lambda x: ref.ff(x, lp, lid, model))
        e = np.asarray(cmp["added"](X_out, X_mid, X_ffn))
        if margin is not None:
            firm = np.asarray(margin) >= MARGIN_EPS
            loose.append(1.0 - float(firm.mean()))
            e = e[firm]
        block.append((f"{lid}.ffn", e))
        X = X_out
    tail = slice(max(0, n - HEAD_POSITIONS), n)
    block.append(("head", np.asarray(cmp["err"](
        fn["head"](params, X)[0, tail].astype(jnp.float32),
        ref.head(cmp["cut"](X)[tail], params, model)))))

    def worst_of(readings):
        vals = [(float(np.max(e)) if np.size(e) else 0.0, name)
                for name, e in readings]
        if any(v != v for v, _ in vals):
            return (float("nan"), "a NaN")
        return max(vals) if vals else (0.0, "")

    kinds: dict = {}
    for name, e in block + rows + state + from_x + select:
        kinds.setdefault(name.split(".", 1)[-1], []).append(
            np.atleast_1d(np.asarray(e, np.float64)))
    return {"block": worst_of(block), "rows": worst_of(rows),
            "state": worst_of(state), "from_x": worst_of(from_x),
            "select": worst_of(select), "loose_share": max(loose),
            "by_block": [[kind, len(es),
                          float(np.median([np.median(e) for e in es])),
                          float(max(np.max(e) for e in es))]
                         for kind, es in kinds.items()]}


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal: both kinds of mixer, routed and dense."""
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
        intermediate_size=128, moe_intermediate_size=32, vocab_size=512,
        q_lora_rank=48, kv_lora_rank=32, qk_head_dim=16,
        qk_nope_head_dim=16, v_head_dim=16, index_n_heads=2,
        index_head_dim=16, index_topk=16, n_routed_experts=4,
        num_experts_per_tok=2, num_hidden_layers=4,
        first_k_dense_replace=1, layer_types=[KDA, DSA, KDA, KDA],
        mlp_layer_types=["dense"] + ["sparse"] * 3,
        indexer_types=["full"] * 4,
        linear_attn_config=dict(config["linear_attn_config"], num_heads=4,
                                head_dim=16, kda_layers=[0, 2, 3],
                                full_attn_layers=[1]))
    config["published"] = dict(config["published"], n_routed_experts=8)
    config["expert_parallel"] = {"chips": 2, "rank": 0}


# ---------------------------------------------------------------- counts
def _n(m: dict, kind: str) -> int:
    return m["layer_types"].count(kind)


def _routed_layers(m: dict) -> int:
    return m["mlp_layer_types"].count("sparse")


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


def _dsa_params(m: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb (= W_UK and W_UV), W_o and the indexer's
    three."""
    d, H, r, qr = (m["hidden_size"], m["num_attention_heads"],
                   m["kv_lora_rank"], m["q_lora_rank"])
    return (d * qr + qr * H * m["qk_nope_head_dim"] + d * r
            + H * r * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + H * m["v_head_dim"] * d
            + qr * m["index_n_heads"] * m["index_head_dim"]
            + d * (m["index_head_dim"] + m["index_n_heads"]))


def _hc_params(m: dict) -> int:
    n = m["hc_mult"]
    return 2 * n * m["hidden_size"] * (2 * n + n * n)


def _non_expert_matmul_params(m: dict) -> int:
    d = m["hidden_size"]
    dense = m["num_hidden_layers"] - _routed_layers(m)
    return (_n(m, KDA) * _kda_params(m) + _n(m, DSA) * _dsa_params(m)
            + m["num_hidden_layers"] * _hc_params(m)
            + dense * 3 * d * m["intermediate_size"]
            + _routed_layers(m) * (d * m["router_experts"]
                                   + m["n_shared_experts"]
                                   * _expert_params(m))
            + m["vocab_size"] * d)


def param_count(m: dict) -> int:
    """Parameters as the program holds them: the embedding and the head
    apart, the norms, the convolutions' taps, the gates' vectors, the
    mHC scalars, the HELD experts, the expert biases."""
    d, la = m["hidden_size"], m["linear_attn_config"]
    inner = la["num_heads"] * la["head_dim"]
    n = m["hc_mult"]
    small = ((2 * m["num_hidden_layers"] + 1) * d
             + m["num_hidden_layers"] * 2 * (3 + 2 * n + n * n)
             + _n(m, KDA) * (la["short_conv_kernel_size"] * 3 * inner
                             + la["num_heads"] + inner + la["head_dim"])
             + _n(m, DSA) * (m["q_lora_rank"] + m["kv_lora_rank"]
                             + 2 * m["index_head_dim"])
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


def lane_state_bytes(m: dict) -> int:
    """Bytes of ONE lane's state matrices in ONE KDA layer (float32)."""
    la = m["linear_attn_config"]
    return 4 * la["num_heads"] * la["head_dim"] ** 2


def decode_step_bytes(m: dict, lanes: int = 64) -> float:
    """Bytes a decode step of a FULL batch must stream at the least:
    every matmul weight held here once (bf16), and every lane's state
    matrices of every KDA layer read and written once; the selected
    latent rows are the `dsa_attn` roofline's."""
    return (2.0 * (_non_expert_matmul_params(m)
                   + _routed_layers(m) * _held_experts(m)
                   * _expert_params(m))
            + 2.0 * lanes * _n(m, KDA) * lane_state_bytes(m))


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name."""
    if kernel == "moe_gmm":
        return _routed_layers(m)
    if kernel == "kda_update":
        return _n(m, KDA)
    if kernel == "dsa_attn":
        return _n(m, DSA)
    return 0                    # no flash_fwd, paged_attn or mla_attn here


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


def dsa_attn_cost(m: dict, rows: float) -> tuple[float, float]:
    """(flops, bytes) the `dsa_attn` calls NEED to attend `rows` SELECTED
    rows in all (summed over lanes, layers and steps): a row read once
    for all heads at its width (bf16), scored over it and taken as
    value.  Rows that were gathered but masked are no work."""
    r = m["kv_lora_rank"]
    return 2.0 * m["num_attention_heads"] * 2 * r * rows, 2.0 * r * rows
