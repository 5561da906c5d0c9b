"""Model family `minicpm_sala`: the decoder `ray_tpu/models/minicpm_sala.py`
serves (`model_type` `minicpm_sala`, e.g. MiniCPM-SALA: lightning
linear-attention layers with a fixed decay a head, three in four, beside a
GQA softmax layer without position embedding that past `dense_len` attends
only the key blocks it selects, one selection a kv head (InfLLM-v2); every
layer a dense SwiGLU; muP scaling).

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

_PROGRAM = os.path.join(spec.ROOT, "ray_tpu", "models", "minicpm_sala.py")
if not os.path.isfile(_PROGRAM):
    raise SystemExit(
        f"model family minicpm_sala: this checkout's program has no "
        f"{_PROGRAM} (ray_tpu.models.minicpm_sala), so it cannot serve the "
        "family")

KEYS = ("model_type", "attention_bias", "attn_use_rope", "head_dim",
        "hidden_act", "hidden_size", "intermediate_size",
        "lightning_head_dim", "lightning_nh", "lightning_nkv",
        "lightning_scale", "lightning_use_rope", "max_position_embeddings",
        "mixer_types", "num_attention_heads", "num_hidden_layers",
        "num_key_value_heads", "qk_norm", "rand_init", "rms_norm_eps",
        "vocab_size", "rope_theta", "scale_emb", "scale_depth",
        "mup_denominator", "dim_model_base", "tie_word_embeddings",
        "use_output_gate", "use_output_norm", "attn_use_output_gate")
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

# Serve: `correct` for this family rests on THIRTEEN readings, each with its
# own limit (`Judge` folds them into the one number the harness compares).
# (1) and (9) are taken for every sample request, the others for the first
# request a replica judges, on the WHOLE of it (16.4 k tokens: every query
# past `dense_len` selects).
#
# What shapes them (my chip runs, PR 61; PERF.md section 6).  Random
# weights make softmax attention over thousands of rows a mean of random
# values, a sixtieth of a row's size, so the LOGITS hardly see the sparse
# mixer: it is held at the mixer itself, (3) to (5), from the program's
# own input.  A selection is discrete: the whole pass is compared with the
# reference GIVEN the program's blocks, (10), and the blocks themselves
# with the reference's, (4).  The first positions of a lightning layer
# hold a token or two a head, o_0 = (q_0 . k_0) v_0, whose RMSNorm a head
# takes its SIGN from one q . k (bfloat16 rounds it across zero for some
# head of 32 in a layer in four): the worst-position reading starts at
# LIGHTNING_WARM.  (Under the decay the TransNormerLLM paper prints, 19
# heads of 32 kept ONE token at every position and no whole-pass reading
# stood; under the public code's slopes, `assumed.lightning`, they do.)
#
# (1) SERVED TOKENS, end to end: the MEAN teacher-forced gap of a
#     request's served tokens under the plain float32 reference with the
#     reference's OWN selection.  Taken from the engine's own timed
#     programs (the 1 x 32,768 prefill of the sample's wave, the scatter
#     into the three pool leaves and the lane, three decode windows
#     through `bsa_index`, `bsa_attn` and `ssm_update`), so it sees a
#     row's state scattered into another lane or a carry lost between
#     steps.  REFERENCE_GAP_TOL.
# (2) What EVERY LAYER ADDS to the stream from its own input (the mixer,
#     the residual's scale, the SwiGLU walked over live rows; the stream
#     itself is mostly what came in, and would hide a SwiGLU below
#     bfloat16) against the reference's layer from the same rows, the
#     MEDIAN position's relative error; and the HEAD (final norm, the
#     width multiplier, the padded table) on the last HEAD_POSITIONS rows
#     as a share of the reference's largest logit.  LAYER_ERR_TOL,
#     HEAD_ERR_TOL.
# (3) The SPARSE MIXER given the program's selection: what `bsa_prefill`,
#     the gate and W_o compute for every position against the reference's
#     mixer under the blocks the program's scores took (the first blocks,
#     the window and dense_len stay the reference's own), the WORST
#     position's relative error.  With the choice given what is left is
#     rounding; a block of 64 rows is a hundredth of a query's rows and an
#     eighth of its output's size, so a dropped first block, a window a
#     block short, K or V below bfloat16 must fail it.  MIXER_ERR_TOL.
# (4) The MISSED SHARE: of the blocks the reference's scores took (the
#     top-k alone), the share the program's selection lacks, over every
#     query past `dense_len` and both kv heads.  The program scores
#     bfloat16 queries against stride rows rounded to bfloat16 (32 B a
#     token is what the pool pays), the reference float32 means of 32
#     float32 keys, so a sound run misses three in a thousand; a kernel's
#     mean over the wrong strides, a softmax over the wrong kernels or a
#     sum over the wrong heads misses a fifth and more.  MISSED_SHARE_MAX.
# (5) ONE DECODE STEP of the sparse mixer (`bsa_index`, the bias,
#     `bsa_attn` over pages, a stride pool and an incomplete stride's sum
#     filled from the prompt pass's rows; two lanes of which one holds the
#     request) for the last token against the reference's mixer at that
#     position under the blocks the STEP's scores took (the step's q
#     comes from another program than the prompt pass's, a bfloat16 ulp
#     apart, and a near-tie at the 64th score falls otherwise: one run of
#     three read 0.081 against the prompt pass's own row, an eighth of an
#     output for one block, where the others read 0.005).  DECODE_ERR_TOL.
# (6) A LIGHTNING MIXER's output from its own input: the MEDIAN position
#     (tight: what rounding does everywhere) and the WORST position from
#     LIGHTNING_WARM on (loose, but under a third of what ONE head's
#     flipped sign reads, 0.35: a fault in a few positions or one head
#     fails it).  LIGHTNING_ERR_TOL, LIGHTNING_WORST_TOL.
# (7) The LIGHTNING STATE after the prompt from the layer's own input
#     against the reference's from the same rows, the relative error of
#     the whole [32, 128, 128].  STATE_ERR_TOL.
# (8) The SCAN'S OWN ARITHMETIC, on the program's own q, k, v of the first
#     lightning layer, every SCAN_HEADS-th head: `ssd_scan` over the
#     padded row against the token-by-token recurrence at the true length
#     (on the host's float32), and one step of `ssm_update` from that
#     state over three lanes of which two hold a request against one step
#     of the recurrence, the idle lane BIT-UNCHANGED.  Sound: float32
#     rounding of lambda itself over a memory of 256 tokens (lambda up to
#     0.996); a state kept in bfloat16 reads 2**-9 and more.
#     SCAN_ERR_TOL.
# (9) The TOKENS THE REFERENCE WOULD NOT HAVE CHOSEN, a request's share
#     of its 24 served tokens (every sample request, beside (1)): a sound
#     request's logits differ from the reference's by rounding, which
#     moves the argmax at a near-tie; a lost state moves most of them.
#     TOKEN_MISS_MAX.
# (10) The WHOLE PASS through the ENGINE'S OWN prefill program at its own
#     bucket (`serve_prefill` over one row of 32,768, the body of
#     `serve/llm.py`'s prefill program, not a composition of the judge's):
#     the logits of the last HEAD_POSITIONS rows against the reference's
#     forward GIVEN the program's selection, as a share of the largest
#     logit, and the state the LAST lightning layer hands the lane against
#     that forward's: four layers' rounding compounded.  PASS_ERR_TOL,
#     PASS_STATE_ERR_TOL.
#
# Readings (my chip runs, PR 61, the tree as committed; PERF.md section 6
# has every run): sound = the benchmark's runs on fresh seeds of the
# weights; each control a whole benchmark run through run.py of a tree
# that carries planted faults (`.bench_ab/pr61/mk_controls.py`.  F_all: K,
# V and the stride rows, lightning's q, k and v, the SwiGLU's activation
# and the head's input through `lax.reduce_precision(., 4, 3)`, "fp8", the
# precision below the stated bfloat16, and the lane state in bfloat16,
# below the stated float32.  F_zero: the lane state zeroed at the
# scatter), every one `correct: false`.
#                          sound                   control
#   (1) mean token gap     0 ... 0.0027            F_zero 0.82 ... 1.28 a sample
#       a sample                                   (its blocks sound); F_all
#                                                  0.013 ... 0.032
#   (9) tokens missed      0 ... 2 of a sample's   F_zero 20 ... 23 of 24;
#                          24                      F_all 3 ... 8
#   (2) a layer's addition 0.0073 ... 0.0074       fp8 activation 0.056
#       the head           0.0030 ... 0.0039       fp8 input 0.059 ... 0.070
#   (3) sparse mixer       0.0060 ... 0.0064       fp8 K / V 0.042 ... 0.045
#   (4) missed share       0.0029 ... 0.0030       fp8 stride rows 0.0162
#   (5) decode step        0.0049 ... 0.0052       fp8 K / V 0.030 ... 0.031
#   (6) lightning, median  0.0059                  fp8 q / k / v 0.061
#       worst position     0.024 ... 0.044         0.24 ... 0.27
#   (7) lightning state    0.0036 ... 0.0037       fp8 q / k / v 0.037
#   (8) scan's arithmetic  6.0e-5 ... 7.0e-5       bfloat16 state 1.7e-3
#   (10) whole pass        0.0128 ... 0.0172       F_all 0.109 ... 0.112
#        its last state    0.0149 ... 0.0153       F_all 0.096 ... 0.098
# (sound: six judged samples and thirteen served ones on five seeds of the
# weights; control: two seeds a tree.)
# Each block limit lies near the geometric mean of its two readings (1.9 x
# to 4.9 x room on either side).  The two served-token limits are for the
# ENGINE's path (a lost state reads 30 x and 3 x over them) and stand 11 x
# and 3 x over the largest sound sample; F_all's tokens straddle them (a
# well-conditioned model's argmax survives fp8): its blocks fail it.
REFERENCE_GAP_TOL = 0.03
TOKEN_MISS_MAX = 0.25
LAYER_ERR_TOL = 0.023
HEAD_ERR_TOL = 0.016
MIXER_ERR_TOL = 0.016
MISSED_SHARE_MAX = 0.007
DECODE_ERR_TOL = 0.0125
LIGHTNING_ERR_TOL = 0.023
LIGHTNING_WORST_TOL = 0.1
STATE_ERR_TOL = 0.011
SCAN_ERR_TOL = 3.4e-4
PASS_ERR_TOL = 0.04
PASS_STATE_ERR_TOL = 0.03
HEAD_POSITIONS = 24
LIGHTNING_WARM = 16   # positions before a state holds sixteen tokens
SCAN_HEADS = 8
JUDGE_PAGE = 512    # the page of the pool the decode step attends
LIMITS = {"layer_err": LAYER_ERR_TOL, "head_err": HEAD_ERR_TOL,
          "mixer_err": MIXER_ERR_TOL, "missed_share": MISSED_SHARE_MAX,
          "decode_err": DECODE_ERR_TOL, "lightning_err": LIGHTNING_ERR_TOL,
          "lightning_worst": LIGHTNING_WORST_TOL,
          "state_err": STATE_ERR_TOL, "scan_err": SCAN_ERR_TOL,
          "pass_err": PASS_ERR_TOL, "pass_state_err": PASS_STATE_ERR_TOL}


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run, and what the
    cut adds: `published_layers` (the depth the residual's scale and the
    decay schedule are written for) and `sparse_config` (assumed:
    MiniCPM4's)."""
    m = {k: config[k] for k in KEYS}
    m["published_layers"] = config["published"]["num_hidden_layers"]
    m["sparse_config"] = dict(config["assumed"]["sparse_config"])
    return m


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def program_config(model: dict, max_seq: int, **extra):
    """MiniCpmSalaConfig for the published keys: only sizes and scalars
    move.  Refuses what the program does not express."""
    import jax.numpy as jnp

    from ray_tpu.models.minicpm_sala import MiniCpmSalaConfig

    m, sc = model, model["sparse_config"]
    kinds = m["mixer_types"]
    refuse = {
        "a rotary embedding in the sparse layers": m["attn_use_rope"],
        "lightning layers without theirs": not m["lightning_use_rope"],
        "a mixer without its output gate":
            not (m["use_output_gate"] and m["attn_use_output_gate"]),
        "a lightning layer without its output norm":
            not m["use_output_norm"],
        "q and k without their norm": not m["qk_norm"],
        "a tied head": m["tie_word_embeddings"],
        "a bias in the attention": m["attention_bias"],
        "an activation other than silu": m["hidden_act"] != "silu",
        "lightning heads other than the attention's, or grouped keys":
            m["lightning_nh"] != m["num_attention_heads"]
            or m["lightning_nkv"] != m["lightning_nh"]
            or m["lightning_head_dim"] != m["head_dim"],
        "a lightning scale other than 1/sqrt(d)":
            m["lightning_scale"] != "1/sqrt(d)",
        "a mixer it does not know, or a depth other than the list's":
            len(kinds) != m["num_hidden_layers"]
            or set(kinds) - {SPARSE, LIGHTNING},
    }
    bad = [what for what, is_so in refuse.items() if is_so]
    if bad:
        raise ValueError(f"the program does not express {bad}")
    return MiniCpmSalaConfig(**{**dict(
        vocab_size=m["vocab_size"], dim=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        sparse_layers=tuple(i for i, k in enumerate(kinds) if k == SPARSE),
        published_layers=m["published_layers"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        ffn_dim=m["intermediate_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]), scale_emb=float(m["scale_emb"]),
        scale_depth=float(m["scale_depth"]),
        dim_model_base=m["dim_model_base"], dense_len=sc["dense_len"],
        block_size=sc["block_size"], kernel_size=sc["kernel_size"],
        kernel_stride=sc["kernel_stride"], window_size=sc["window_size"],
        init_blocks=sc["init_blocks"], topk=sc["topk"], max_seq=max_seq,
        dtype=jnp.bfloat16), **extra})


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it.  The bits come from the device's own generator (jax's
    "rbg" keys seeded from the harness's key: the same seed, the same
    weights), as `families/ssm_hybrid.py` found it worth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import minicpm_sala

    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    wide = jax.random.wrap_key_data(jnp.concatenate([key, key])[:4],
                                    impl="rbg")
    return minicpm_sala.init_params(wide, cfg)


def reference():
    """The judge of a serve cell: `teacher_forced_gaps(params, prompt,
    served, model)` over the plain reference `refs/minicpm_sala.py`."""
    return Judge


class Judge:
    """The served tokens' mean gap under the plain reference, and the share
    of them the reference would not have chosen, for every request, and
    for the first one this process judges the readings of
    `selection_readings`, each held to its own limit (the reasons stand
    above `REFERENCE_GAP_TOL`).  The harness compares ONE number with
    `REFERENCE_GAP_TOL`, so each reading is returned as its share of its
    limit times `REFERENCE_GAP_TOL`; all readings and limits are printed
    (stderr reaches the run's output)."""

    _seen: dict = {}
    _readings_done: list = []

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

        from benchmarks.harness.refs import minicpm_sala as ref

        t0 = time.perf_counter()
        seq = list(prompt) + list(served[:-1])
        x_own, infos = ref.forward(params, seq, model)
        own = ref.head(x_own[-len(served):], params, model)
        gaps = ref.gaps_of(own, served)
        t1 = time.perf_counter()
        mean_gap = sum(gaps) / len(gaps)
        missed = sum(g > 0.0 for g in gaps) / len(gaps)
        shares = {"token_gap": mean_gap / REFERENCE_GAP_TOL,
                  "token_miss": missed / TOKEN_MISS_MAX}
        line = {"step": "minicpm_sala.judge", "mean_token_gap": mean_gap,
                "worst_token_gap": max(gaps), "limit": REFERENCE_GAP_TOL,
                "tokens_missed": missed, "miss_limit": TOKEN_MISS_MAX,
                "tokens": len(seq) + 1, "token_gaps_s": round(t1 - t0, 2)}
        if not cls._readings_done:
            cls._readings_done.append(True)
            r = selection_readings(params, seq, model, infos)
            shares.update({k: r[k] / lim for k, lim in LIMITS.items()})
            line.update(r, limits=LIMITS,
                        readings_s=round(time.perf_counter() - t1, 2))
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


_PROGRAMS: dict = {}


def _program(cfg, P: int):
    """The program's prompt pass over rows of P positions, its layers'
    own functions in ONE jitted program (a compile, not one a layer: a
    run has 345 s), and its two decode kernels' steps from what that pass
    hands over."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import minicpm_sala as prog
    from ray_tpu.ops import ssm

    F32 = jnp.float32
    H, hd = cfg.n_heads, cfg.head_dim

    def row_at(a, at):
        return jax.lax.dynamic_index_in_dim(a, at, axis=1, keepdims=False)

    def whole(params, tok, n):
        """(the last HEAD_POSITIONS rows' logits; xs: the stream at every
        layer's input and after the last; ys: what each mixer alone
        computes; a sparse layer each: the blocks each query attended and
        the rows it hands over; a lightning layer each: the state it hands
        the lane)."""
        lens = jnp.reshape(n, (1,)).astype(jnp.int32)
        x = prog.embed(params, tok, cfg)
        xs, ys, sparse, states = [x[0]], [], {}, {}
        for lid, lp in enumerate(params["layers"]):
            if cfg.layer_types[lid] == prog.SPARSE:
                y, kept = prog.sparse_prefill(x, lp, cfg, lens,
                                              want_selection=True, bare=True)
                sparse[lid] = (kept[4][0], kept[:3])
            else:
                y, state = prog.lightning_prefill(x, lp, lid, cfg, lens,
                                                  bare=True)
                states[lid] = state[0]
            x = prog.ffn(prog.residual(x, y, cfg), lp, cfg, n)
            xs.append(x[0])
            ys.append(y[0])
        x = jax.lax.dynamic_slice_in_dim(x, n - HEAD_POSITIONS,
                                         HEAD_POSITIONS, axis=1)
        logits = prog.project_logits(params, prog.scaled_hidden(
            prog.rmsnorm(x, params["final_norm"], cfg.norm_eps), cfg))
        return logits[0].astype(F32), xs, ys, sparse, states

    def sparse_step(lp, x, at, k, v, means):
        """One decode step of the sparse mixer for the token at position
        `at` (its layer's input `x` [P, d]), over two lanes of which
        lane 1 holds the request: the K, V and stride pools filled from
        the prompt pass's rows below it, the token's own row landing in
        the tails."""
        stride = cfg.kernel_stride

        def pool(rows, page):
            rows = jnp.pad(rows[0], ((0, -rows.shape[1] % page), (0, 0),
                                     (0, 0)))
            leaf = rows.reshape(-1, page, *rows.shape[1:]).transpose(
                0, 2, 1, 3)
            return jnp.concatenate([jnp.zeros_like(leaf[:1]), leaf])

        pools = (pool(k, JUDGE_PAGE), pool(v, JUDGE_PAGE),
                 pool(means, JUDGE_PAGE // stride))
        maxp = pools[0].shape[0] - 1
        table = jnp.stack([jnp.zeros((maxp,), jnp.int32),
                           jnp.arange(1, maxp + 1, dtype=jnp.int32)])
        pos = jnp.stack([jnp.int32(0), at])
        tail = jnp.zeros((2, k.shape[2], 8, k.shape[3]), cfg.dtype)
        # the keys of the token's stride that lie below it
        rows = jnp.arange(k.shape[1])
        part = (rows >= at // stride * stride) & (rows < at)
        kpart = jnp.sum(jnp.where(part[:, None, None], k[0].astype(F32),
                                  0.0), axis=0).reshape(1, -1)
        y, _, _, chosen = prog.sparse_decode(
            jnp.repeat(row_at(x[None], at), 2, axis=0), lp, pools,
            (tail, tail, tail[:, :, :1]), jnp.repeat(kpart, 2, axis=0),
            table, pos, pos, 0, cfg, want_selection=True)
        return y[1].astype(F32), chosen[1]

    def scan(lp, x, n, lid):
        """The first lightning layer's own q, k, v (x [P, d] its input), what `ssd_scan` makes
        of them for the padded row (in the lane's dtype), and one step of
        `ssm_update` for the token at n - 1 from the state at n - 1, over
        three lanes (the state as handed, an idle lane, half of it)."""
        h = prog.rmsnorm(x[None], lp["norm1"], cfg.norm_eps)
        at = jnp.arange(P)[None, :]
        q, k, v = prog.lightning_inputs(h, lp, cfg, at)
        rates = prog.decay_rates(cfg, lid)

        def state_at(m):
            dt = jnp.broadcast_to((at < m).astype(F32)[..., None], (1, P, H))
            return ssm.ssd_scan(v, dt, -rates, k, q, cfg.lightning_chunk)[
                1].astype(cfg.state_dtype)

        before = state_at(n - 1)[0]
        held = jnp.stack([before, 3.0 * before, 0.5 * before])[None]
        lanes, count = ssm.live_lanes(jnp.asarray([True, False, True]))

        def row(a):       # the token at n - 1, the same for three lanes
            return jnp.repeat(row_at(a, n - 1), 3, axis=0)

        new, y = ssm.ssm_update(
            held, jnp.int32(0), lanes, count, row(v).reshape(3, -1),
            jnp.full((3, H * hd), prog.DT_ONE, F32), row(k), row(q),
            jnp.repeat(jnp.log(rates), hd), jnp.zeros((H * hd,), F32))
        untouched = jnp.all(new[0, 1] == held[0, 1])
        heads = slice(0, None, SCAN_HEADS)
        return (q[0, :, heads], k[0, :, heads], v[0, :, heads],
                state_at(n)[0], held[0], new[0], y, untouched)

    def engine_pass(params, tok, n):
        """The ENGINE'S OWN prompt pass (`serve_prefill`, the body of
        `serve/llm.py`'s prefill program) over one row of the engine's
        bucket: the logits of the last HEAD_POSITIONS rows below n and
        the state the last lightning layer hands the lane."""
        hidden, _, _, state, _ = prog.serve_prefill(
            params, tok, cfg, jnp.reshape(n, (1,)).astype(jnp.int32))
        h = jax.lax.dynamic_slice_in_dim(hidden, n - HEAD_POSITIONS,
                                         HEAD_POSITIONS, axis=1)
        return (prog.project_logits(params, h)[0].astype(F32),
                state["lightning"][-1][0])

    return {"whole": jax.jit(whole), "sparse_step": jax.jit(sparse_step),
            "scan": jax.jit(scan), "engine_pass": jax.jit(engine_pass)}


def lane_state_as_heads(state, H: int):
    """The lane's layout [N, H P] -> the reference's [H, N (k), P (v)]."""
    N = state.shape[0]
    return state.reshape(N, H, -1).transpose(1, 0, 2)


def _scan_reading(fn, lp, x, n: int, lid: int, cfg, model: dict) -> float:
    """Reading (8), on the host's float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import minicpm_sala as ref

    q, k, v, st, held, new, y, untouched = fn["scan"](
        lp, x, jnp.int32(n), jnp.int32(lid))
    if not bool(untouched):
        return float("inf")
    heads = slice(0, None, SCAN_HEADS)
    cpu = jax.devices("cpu")[0]

    def host(a):
        return jax.device_put(np.asarray(a, np.float32), cpu)

    def as_heads(a):
        return np.asarray(lane_state_as_heads(a, cfg.n_heads), np.float32)[
            heads]

    def rel(got, want):
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    q, k, v = (np.asarray(a[:n], np.float32) for a in (q, k, v))
    lam = host(np.asarray(ref.decay(lid, model))[heads])
    step = jax.jit(ref.recurrence)
    errs = [rel(as_heads(st), np.asarray(step(host(q), host(k), host(v),
                                              lam)[1]))]
    for lane in (0, 2):         # one token from what each live lane held
        o, want = step(host(q[n - 1:]), host(k[n - 1:]), host(v[n - 1:]),
                       lam, host(as_heads(held[lane])))
        errs.append(rel(as_heads(new[lane]), np.asarray(want)))
        got = np.asarray(y[lane], np.float32).reshape(cfg.n_heads, -1)[heads]
        errs.append(rel(got, np.asarray(o[0])))
    return max(errs)


def selection_readings(params, tokens: list[int], model: dict,
                       own_infos) -> dict:
    """Readings (2)-(8) and (10) on one sequence: the program's prompt pass (the
    sequence right-padded, its TRUE length passed), every layer against
    the reference's layer FROM THE PROGRAM'S OWN INPUT under the blocks
    the program chose; `own_infos`: the layers' infos of the reference's
    forward under its own selection (for what its scores took)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import minicpm_sala as ref

    F32 = jnp.float32
    n = len(tokens)
    P = -(-(n + 1) // 128) * 128    # the kernels' multiple, and at least
    #                                 one row of padding
    key = (P, tuple(sorted((k, str(v)) for k, v in model.items())))
    if key not in _PROGRAMS:
        cfg = program_config(model, max_seq=P)
        _PROGRAMS[key] = (cfg, _program(cfg, P))
    cfg, fn = _PROGRAMS[key]
    sc = model["sparse_config"]
    nb = -(-n // sc["block_size"])
    # the padding is token ids of its own, not zeros: what is computed
    # past the true length must not reach what is compared
    pad = [(7 * i + 3) % model["vocab_size"] for i in range(P - n)]
    tok = jnp.asarray([list(tokens) + pad], jnp.int32)
    got, xs, ys, sparse, states = fn["whole"](params, tok, jnp.int32(n))

    def positions(got, want, start=0):
        """The relative error a position from `start` on (a position whose
        reference nearly cancels is measured against the median position's
        norm): (the median, the worst)."""
        size = jnp.linalg.norm(want, axis=-1)
        e = (jnp.linalg.norm(got[:want.shape[0]].astype(F32) - want, axis=-1)
             / jnp.maximum(size, jnp.median(size)))[start:]
        return float(jnp.median(e)), float(jnp.max(e))

    def rel(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    c = ref.residual_scale(model)
    out = {"mixer_err": 0.0, "lightning_err": 0.0, "lightning_worst": 0.0,
           "layer_err": 0.0, "decode_err": 0.0, "state_err": 0.0,
           "scan_err": None}
    missed = taken = 0
    at, b = np.arange(n), np.arange(nb)
    forced = (b[None, :] < sc["init_blocks"]) | (
        (b[None, :] >= (at[:, None] - sc["window_size"] + 1)
         // sc["block_size"]) & (b[None, :] <= at[:, None] // sc["block_size"]))
    scored = (at >= sc["dense_len"])[None, :, None] & ~forced[None]
    for lid, lp in enumerate(params["layers"]):
        x_in = xs[lid][:n].astype(F32)
        if lid in sparse:
            chosen, kept = sparse[lid]
            mine = chosen[:, :n, :nb]
            # the last token under the blocks its DECODE step chose (its
            # q comes from another program, a bfloat16 ulp apart: a
            # near-tie at the 64th score falls otherwise, which is no
            # fault and an eighth of the output)
            step, took = fn["sparse_step"](lp, xs[lid], jnp.int32(n - 1),
                                           *kept)
            given = mine.at[:, n - 1].set(took[:, :nb])
            want, x_mid, info = ref.layer(x_in, lp, lid, model, given)
            out["mixer_err"] = max(out["mixer_err"], positions(
                ys[lid][:n - 1], info["y"][:n - 1])[1])
            out["decode_err"] = max(out["decode_err"],
                                    rel(step, info["y"][n - 1]))
            # the blocks the reference's scores took, and of them those
            # the program's selection lacks (queries past dense_len; the
            # window and the first blocks are no choice)
            theirs = np.asarray(own_infos[lid]["chosen"]) & scored
            taken += int(theirs.sum())
            missed += int((theirs & ~np.asarray(mine)).sum())
        else:
            want, x_mid, info = ref.layer(x_in, lp, lid, model)
            med, worst = positions(ys[lid], (x_mid - x_in) / F32(c),
                                   LIGHTNING_WARM)
            out["lightning_err"] = max(out["lightning_err"], med)
            out["lightning_worst"] = max(out["lightning_worst"], worst)
            out["state_err"] = max(out["state_err"], rel(
                lane_state_as_heads(states[lid], cfg.n_heads),
                info["state"]))
            if out["scan_err"] is None:
                out["scan_err"] = _scan_reading(fn, lp, xs[lid], n, lid,
                                                cfg, model)
        out["layer_err"] = max(out["layer_err"], positions(
            xs[lid + 1][:n].astype(F32) - x_in, want - x_in)[0])
    want = np.asarray(ref.head(xs[-1][n - HEAD_POSITIONS:n].astype(F32),
                               params, model))
    got = np.asarray(got)
    out["head_err"] = float(np.max(np.abs(got - want))
                            / np.max(np.abs(want)))
    # (10) the whole pass, through the engine's own program at its bucket
    bucket = 1 << n.bit_length()
    fill = [(7 * i + 3) % model["vocab_size"] for i in range(bucket - n)]
    got, state = fn["engine_pass"](
        params, jnp.asarray([list(tokens) + fill], jnp.int32), jnp.int32(n))
    x_ref, infos = ref.forward(params, tokens, model, {
        lid: chosen[:, :n, :nb] for lid, (chosen, _) in sparse.items()})
    want = np.asarray(ref.head(x_ref[-HEAD_POSITIONS:], params, model))
    out["pass_err"] = float(np.max(np.abs(np.asarray(got) - want))
                            / np.max(np.abs(want)))
    out["pass_state_err"] = rel(lane_state_as_heads(state, cfg.n_heads),
                                infos[max(states)]["state"])
    out.update(missed_share=missed / max(taken, 1), blocks_taken=taken)
    return out


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal: one period, a selection that selects
    within a hundred positions."""
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=128, vocab_size=512,
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        dim_model_base=16, num_hidden_layers=4,
        mixer_types=[SPARSE] + [LIGHTNING] * 3)
    config["assumed"] = dict(config["assumed"], sparse_config=dict(
        block_size=8, kernel_size=4, kernel_stride=2, window_size=32,
        init_blocks=1, topk=4, dense_len=128))


# ---------------------------------------------------------------- counts
def _n(m: dict, kind: str) -> int:
    return m["mixer_types"].count(kind)


def _inner(m: dict) -> int:
    return m["num_attention_heads"] * m["head_dim"]


def _sparse_params(m: dict) -> int:
    """W_q, W_gate, W_o at the query heads' width; W_k, W_v at the kv
    heads'."""
    d = m["hidden_size"]
    return (3 * d * _inner(m)
            + 2 * d * m["num_key_value_heads"] * m["head_dim"])


def _lightning_params(m: dict) -> int:
    """W_q, W_k, W_v, W_gate, W_o."""
    return 5 * m["hidden_size"] * m["lightning_nh"] * m["lightning_head_dim"]


def _ffn_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def matmul_params(m: dict) -> int:
    """Parameters a token's step MULTIPLIES: every matmul weight, the
    head among them (the padded columns are zeros nobody reads); the
    embedding lookup is no matmul."""
    return (_n(m, SPARSE) * _sparse_params(m)
            + _n(m, LIGHTNING) * _lightning_params(m)
            + m["num_hidden_layers"] * _ffn_params(m)
            + m["vocab_size"] * m["hidden_size"])


def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 128) * 128


def param_count(m: dict) -> int:
    """Parameters as the program holds them: the embedding and the head
    apart (the head's table padded to whole lane tiles), two norms a layer
    and a final norm, q and k norms a mixer, an output norm a lightning
    mixer."""
    d, hd = m["hidden_size"], m["head_dim"]
    small = ((2 * m["num_hidden_layers"] + 1) * d
             + 2 * hd * m["num_hidden_layers"] + hd * _n(m, LIGHTNING))
    return (matmul_params(m) + m["vocab_size"] * d + small
            + (padded_vocab(m) - m["vocab_size"]) * d)


def lane_state_bytes(m: dict) -> int:
    """Bytes of ONE lane's state matrices in ONE lightning layer
    (float32)."""
    return 4 * m["lightning_nh"] * m["lightning_head_dim"] ** 2


def _selected_rows(m: dict, context):
    """Rows a query with `context` rows at and below it attends (an int,
    or a numpy array of them: `ops/block_sparse_attention
    .selection_counts`' arithmetic, kept here so that the yardstick does
    not import the program)."""
    import numpy as np

    sc = m["sparse_config"]
    blk = sc["block_size"]
    ctx = np.asarray(context, dtype=np.int64)
    first = np.maximum((ctx - sc["window_size"]) // blk, 0)
    init = np.minimum(sc["init_blocks"], first)
    sel = ((init + np.minimum(sc["topk"], first - init)) * blk
           + ctx - first * blk)
    out = np.where(ctx <= sc["dense_len"], ctx, sel)
    return int(out) if out.ndim == 0 else out


def decode_step_bytes(m: dict, lanes: int = 32) -> float:
    """Bytes a decode step of a FULL batch must stream at the least:
    every matmul weight once (bf16), every live lane's state matrices of
    every lightning layer read and written once, and the K and V rows a
    longdocs-mix lane's selection names (~24.6 k tokens of context) beside
    its stride rows."""
    row = 2 * 2 * m["num_key_value_heads"] * m["head_dim"]
    ctx = 24576
    kv = lanes * _n(m, SPARSE) * (
        _selected_rows(m, ctx) * row
        + ctx // m["sparse_config"]["kernel_stride"] * row // 2)
    return (2.0 * matmul_params(m)
            + 2.0 * lanes * _n(m, LIGHTNING) * lane_state_bytes(m) + kv)


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name."""
    if kernel == "ssm_update":
        return _n(m, LIGHTNING)
    if kernel in ("bsa_index", "bsa_attn", "bsa_prefill"):
        return _n(m, SPARSE)
    return 0


def ssm_update_cost(m: dict, lane_steps: float) -> tuple[float, float]:
    """(flops, bytes) the `ssm_update` calls NEED for `lane_steps` (lane,
    layer, step) triples that were work, at a group a HEAD: the lane's
    state read and written once (float32); x = v (bf16), B = k and C = q
    (bf16, a row a head: 32 x 128 each, not one a layer) and dt (float32,
    a value a column) in and y (float32) out; a state element's decay,
    input and output (two multiply-adds and a multiply)."""
    inner, N = _inner(m), m["lightning_head_dim"]
    nbytes = 2 * lane_state_bytes(m) + 2 * inner + 2 * 2 * inner \
        + 4 * inner + 4 * inner
    return 5.0 * N * inner * lane_steps, float(nbytes) * lane_steps


def _row(m: dict) -> tuple[float, float]:
    """(flops, bytes) of ONE attended row of ONE sparse layer: scored by
    every query head and taken as value (a multiply-add two operations),
    read once a kv head as K and as V (bf16)."""
    hd = m["head_dim"]
    return (4.0 * m["num_attention_heads"] * hd,
            2.0 * 2 * m["num_key_value_heads"] * hd)


def bsa_attn_cost(m: dict, rows: float, lane_steps: float
                  ) -> tuple[float, float]:
    """(flops, bytes) the `bsa_attn` calls of ONE sparse layer NEED to
    attend `rows` selected rows in all (summed over `lane_steps` (lane,
    step) pairs): K and V of each attended row once, q in and o out a
    lane-step."""
    fl, by = _row(m)
    return fl * rows, by * rows + 2.0 * 2 * _inner(m) * lane_steps


def bsa_prefill_cost(m: dict, lens: list[int]) -> tuple[float, float]:
    """(flops, bytes) ONE sparse layer's prompt passes NEED for sequences
    of the given TRUE lengths: every query scores and weighs the rows its
    selection names (`_selected_rows`), whatever implements it; q and o
    once a query head, k and v once a kv head (bf16)."""
    import numpy as np

    fl, _ = _row(m)
    rows = sum(int(_selected_rows(m, np.arange(1, s + 1)).sum())
               for s in lens)
    heads = 2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"]
    return fl * rows, 2.0 * heads * m["head_dim"] * sum(lens)
