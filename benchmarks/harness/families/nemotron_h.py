"""Model family `nemotron_h`: the decoder `ray_tpu/models/nemotron_h.py`
serves (`model_type` `nemotron_h` with `moe_latent_size`, e.g.
NVIDIA-Nemotron-3-Super-120B-A12B: layers that are ONE residual branch
each, by a pattern of three letters: Mamba-2 in several groups of heads,
routed relu**2 experts of two matrices in a latent narrower than the
stream with a shared expert at full width, GQA attention with no position
embedding; this chip holds a range of the experts).

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

_PROGRAM = os.path.join(spec.ROOT, "ray_tpu", "models", "nemotron_h.py")
if not os.path.isfile(_PROGRAM):
    raise SystemExit(
        f"model family nemotron_h: this checkout's program has no {_PROGRAM}"
        " (ray_tpu.models.nemotron_h), so it cannot serve the family")

KEYS = ("attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim",
        "hidden_size", "hybrid_override_pattern", "intermediate_size",
        "layer_norm_epsilon", "mamba_head_dim", "mamba_hidden_act",
        "mamba_num_heads", "mamba_proj_bias", "max_position_embeddings",
        "mlp_bias", "mlp_hidden_act", "model_type", "moe_intermediate_size",
        "moe_latent_size", "moe_shared_expert_intermediate_size",
        "moe_shared_expert_overlap", "mtp_hybrid_override_pattern",
        "n_group", "n_groups", "n_routed_experts", "n_shared_experts",
        "norm_eps", "norm_topk_prob", "num_attention_heads",
        "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
        "num_logits_to_keep", "num_nextn_predict_layers",
        "partial_rotary_factor", "rescale_prenorm_residual",
        "residual_in_fp32", "rope_theta", "routed_scaling_factor",
        "sliding_window", "ssm_state_size", "tie_word_embeddings",
        "time_step_floor", "time_step_max", "time_step_min", "topk_group",
        "use_bias", "use_conv_bias", "use_mamba_kernels", "vocab_size")
MAMBA, MOE, ATTN = "M", "E", "*"

# Serve: `correct` for this family rests on FOUR readings, each with its
# own limit (`Judge` folds them into the one number the harness
# compares).  The first is taken for every sample request, the others for
# the first request a replica judges (they cost a reference pass a block,
# and a run has 345 s).
#
# (1) SERVED TOKENS, end to end: the MEAN teacher-forced gap of a
#     request's served tokens under the plain float32 reference (a routed
#     model's near-ties flip under bfloat16 and cascade, so the worst
#     token of a sound request reads whole units: `lfm2_moe`, `mla_moe`
#     and `glm5_next` hold the mean too).  The one reading taken from the
#     engine's own timed programs (the 1 x 8,192 prefill of the sample's
#     wave, the scatter into the pool and the lane, three decode windows
#     through `ssm_update`, the routed layer at 22 of 512 and
#     `paged_attn`), so it alone sees a row's state scattered into
#     another lane or a carry lost between steps.  REFERENCE_GAP_TOL.
# (2) The PROGRAM'S BLOCKS, one at a time at the served widths, each from
#     the program's OWN input on the request's tokens right-padded with
#     `true_lens` passed: every layer (`models/nemotron_h.layer_prefill`:
#     what the engine's prefill program is made of), the head, the rows a
#     layer hands the lane or the pool, and ONE DECODE STEP of every
#     Mamba layer (`mamba_decode`: the convolution's shift, `ssm_update`
#     over four lanes of which two hold a request, the gated norm by
#     group), against the plain reference's same block on the same input
#     at the true length.  The reading is the relative error (2-norms
#     over the features) of what a block adds to the stream, the worst
#     over blocks and positions; a routed block leaves out the positions
#     whose routing margin is under MARGIN_EPS (the program's router
#     reads bfloat16 rows and flips a near-tie at the 22nd place, which
#     is no fault), and the share left out is bounded by LOOSE_SHARE_MAX.
#     The share is the REFERENCE's own (its margins on the program's
#     input), so it says how much of the routed blocks is judged, not how
#     the program did: 22 of 512 sigmoid scores lie ~0.003 apart at the
#     cut, so about half the positions have a margin under 0.002
#     (expected 1 - exp(-0.002 / 0.0029) = 0.50; read 0.507-0.533, my
#     chip runs, PR 48), where 8 of 128 or 288 leave a quarter; the
#     ~2,000 firm positions a layer are what is held.  BLOCK_ERR_TOL.
# (3) The SCAN'S OWN ARITHMETIC: the chunked scan's state for the padded
#     row against the reference's token-by-token recurrence at the TRUE
#     length (on the host's float32, the first head of every group: on
#     the chip thousands of products of exp() drift ten times the scan's
#     own error; PERF.md section 6, PR 41), both on the program's own
#     materialised operands; and each live lane's state after
#     `ssm_update` against one step of the recurrence from what it held,
#     the idle lanes' and every other layer's state BIT-UNCHANGED.  Sound:
#     float32 rounding; a state kept in bfloat16: 2**-9.  STATE_ERR_TOL.
# (4) The STATE FROM THE LAYER'S INPUT: what the layer hands the lane for
#     the padded row, and lane 1's state after the decode step, against
#     the reference's state at the true length computed by the
#     reference's OWN in_proj, convolution, softplus and recurrence.
#     Reading (3) gives both sides operands the program prepared; this
#     one shares nothing and pays for it with the bfloat16 rounding of
#     the program's operands.  STATE_FROM_X_TOL.
#
# Readings (my chip runs, PR 48; PERF.md section 6): sound = 12 benchmark
# runs on 12 seeds of the weights (46 judged requests, 12 of them with
# blocks); each control a whole benchmark run through run.py of a tree
# that carries the fault (`.bench_ab/controls48.py`), `correct: false`:
#                          sound                 control
#   (1) mean token gap     0.0095 ... 0.142      gated norm over the whole
#       (a sound request's WORST token            row 0.60 ... 0.94 (top-21
#       reads up to 1.04)                         reads 0.047 ... 0.135:
#                                                 only the blocks see it)
#   (2) blocks             0.0151 ... 0.0169     top-21 0.239 (an `E`
#       (always the attention layer; an `M`       layer); norm over the
#       layer <= 0.0086, an `E` layer <= 0.0063)  whole row 0.855 (an `M`
#                                                 layer; its decode step
#                                                 0.31)
#   (3) scan's arithmetic  1.6e-6 ... 3.4e-6     bfloat16 state 1.70e-3
#       (`ssm_update` against one step: 0.0,      (scan) / 1.68e-3 (update)
#       bit for bit)
#   (4) state from input   0.0047 ... 0.0057     (no control aims at it;
#                                                 the limit is glm5_next's
#                                                 room: 5 x)
# and the share of a routed block's positions left out: 0.507 ... 0.533.
# Each limit lies between its two readings with room on both sides: 0.35
# is 2.5 x the sound mean gap and 1.7 x under the control's; 0.05 is 3.0 x
# / 4.8 x; 1e-4 is 30 x / 17 x.
REFERENCE_GAP_TOL = 0.35
BLOCK_ERR_TOL = 0.05
STATE_ERR_TOL = 1e-4
STATE_FROM_X_TOL = 0.03
MARGIN_EPS = 0.002
LOOSE_SHARE_MAX = 0.8
HEAD_POSITIONS = 128
DECODE_LIVE = (False, True, False, True)    # the decode check's four lanes


def _held(config: dict) -> tuple[int, int]:
    ep = config["expert_parallel"]
    n = config["n_routed_experts"]
    return ep["rank"] * n, (ep["rank"] + 1) * n


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run, and what the
    cut adds: `router_experts` (the router's published width),
    `experts_held` (the range this chip holds) and `num_experts` (how many
    that is: the key the shared `engine.moe_experts_hit_pct` reader
    divides by)."""
    m = {k: config[k] for k in KEYS}
    m["router_experts"] = config["published"]["n_routed_experts"]
    m["experts_held"] = list(_held(config))
    m["num_experts"] = config["n_routed_experts"]
    return m


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def program_config(model: dict, max_seq: int, **extra):
    """NemotronHConfig for the published keys: only sizes and scalars
    move.  Refuses what the program does not express."""
    import jax.numpy as jnp

    from ray_tpu.models.nemotron_h import NemotronHConfig

    m = model
    pattern = m["hybrid_override_pattern"]
    refuse = {
        "a bias in attention, the projections or the experts":
            m["attention_bias"] or m["mamba_proj_bias"] or m["mlp_bias"]
            or m["use_bias"],
        "a convolution without bias": not m["use_conv_bias"],
        "a tied head": m["tie_word_embeddings"],
        "another expert activation than relu2":
            m["mlp_hidden_act"] != "relu2",
        "another Mamba activation than silu":
            m["mamba_hidden_act"] != "silu",
        "a routed layer outside a latent": not m["moe_latent_size"],
        "a router with group limits, or weights not normalised":
            m["n_group"] != 1 or m["topk_group"] != 1
            or not m["norm_topk_prob"],
        "more or fewer than one shared expert": m["n_shared_experts"] != 1,
        "an inner width other than expand x hidden_size":
            m["mamba_num_heads"] * m["mamba_head_dim"]
            != m["expand"] * m["hidden_size"],
        "a pattern that does not name num_hidden_layers layers of M, E, *":
            len(pattern) != m["num_hidden_layers"]
            or set(pattern) - {MAMBA, MOE, ATTN},
        "a sliding window": m["sliding_window"] is not None,
        "two epsilons": m["norm_eps"] != m["layer_norm_epsilon"],
        "a residual stream kept in float32": m["residual_in_fp32"],
    }
    bad = [what for what, is_so in refuse.items() if is_so]
    if bad:
        raise ValueError(f"the program does not express {bad}")
    return NemotronHConfig(
        vocab_size=m["vocab_size"], dim=m["hidden_size"], pattern=pattern,
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        ssm_heads=m["mamba_num_heads"], ssm_head_dim=m["mamba_head_dim"],
        ssm_groups=m["n_groups"], ssm_state=m["ssm_state_size"],
        conv_kernel=m["conv_kernel"], ssm_chunk=m["chunk_size"],
        moe_latent=m["moe_latent_size"],
        moe_ffn_dim=m["moe_intermediate_size"],
        shared_ffn_dim=m["moe_shared_expert_intermediate_size"],
        n_experts=m["router_experts"],
        experts_held=tuple(m["experts_held"]),
        top_k=m["num_experts_per_tok"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling=float(m["routed_scaling_factor"]),
        norm_eps=float(m["layer_norm_epsilon"]), max_seq=max_seq,
        dtype=jnp.bfloat16, **extra)


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it.  The bits come from the device's own generator (jax's
    "rbg" keys seeded from the harness's key: the same seed, the same
    weights), as `families/ssm_hybrid.py` found it worth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h

    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    wide = jax.random.wrap_key_data(jnp.concatenate([key, key])[:4],
                                    impl="rbg")
    return nemotron_h.init_params(wide, cfg)


def reference():
    """The judge of a serve cell: `teacher_forced_gaps(params, prompt,
    served, model)` over the plain reference `refs/nemotron_h.py`."""
    return Judge


class Judge:
    """The served tokens' mean gap under the plain reference for every
    request, and for the first one this process judges the three readings
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

        from benchmarks.harness.refs import nemotron_h as ref

        t0 = time.perf_counter()
        gaps = ref.token_gaps(params, prompt, served, model)
        t1 = time.perf_counter()
        mean_gap = sum(gaps) / len(gaps)
        shares = {"token_gap": mean_gap / REFERENCE_GAP_TOL}
        line = {"step": "nemotron_h.judge", "mean_token_gap": mean_gap,
                "worst_token_gap": max(gaps), "limit": REFERENCE_GAP_TOL,
                "tokens": len(prompt) + len(served),
                "token_gaps_s": round(t1 - t0, 2)}
        if not cls._blocks_done:
            cls._blocks_done.append(True)
            b = block_errors(params, list(prompt) + list(served[:-1]),
                             model)
            shares.update(
                block_err=b["block"][0] / BLOCK_ERR_TOL,
                state_err=b["state"][0] / STATE_ERR_TOL,
                state_from_x_err=b["from_x"][0] / STATE_FROM_X_TOL,
                loose_share=b["loose_share"] / LOOSE_SHARE_MAX)
            line.update(
                worst_block_err=b["block"], block_limit=BLOCK_ERR_TOL,
                worst_state_err=b["state"], state_limit=STATE_ERR_TOL,
                worst_state_from_x_err=b["from_x"],
                state_from_x_limit=STATE_FROM_X_TOL,
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


def _program_blocks(cfg, n: int):
    """The program's blocks, each jitted once for a true length n and
    taking the layer's own weights, so that every layer of a kind runs
    the one compiled program."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import nemotron_h as prog
    from ray_tpu.ops import ssm

    F32 = jnp.float32
    live = DECODE_LIVE
    lens_of = lambda m: jnp.reshape(m, (1,)).astype(jnp.int32)  # noqa: E731

    def layer(kind):
        lid = cfg.pattern.index(kind)           # any layer of the kind

        def run(lp, x, m):
            return prog.layer_prefill({"layers": {lid: lp}}, x, lid, cfg,
                                      lens_of(m))[:2]
        return jax.jit(run)

    def scan_inputs(lp, x):
        """Steps 1-4 of a Mamba layer, MATERIALISED (x, dt, B, C): what
        `scan` below and the reference's recurrence are both given."""
        h = prog.rmsnorm(x, lp["norm"], cfg.norm_eps)
        return prog.scan_inputs(h, lp, cfg, lens_of(jnp.int32(n)))[1:5]

    def scan(lp, xs, dt, Bm, Cm):
        return prog.scan_state(xs, dt, Bm, Cm, lp, cfg)[1]

    def decode(lp, x, at, rows, state, i):
        """One decode step of the Mamba layer that is the i-th of its
        kind, for the token at position `at` of x, over FOUR lanes of
        which two hold a request, from the state a prefill handed: lane 1
        holds it as handed, lane 3 half of it, and the idle lanes 0 and 2
        twice and three times it, which the step must leave as they are,
        bit for bit.  Returns (what the mixer adds to the stream in lane
        1, the layer's state after [4 lanes, N, inner], what the lanes
        held, whether the idle lanes and every other layer's state are
        untouched, the step's own scan inputs x, dt before the softplus,
        B)."""
        held = jnp.concatenate([2.0 * state, state, 3.0 * state,
                                0.5 * state])
        shape = (cfg.count(MAMBA), len(live)) + state.shape[1:]
        lane = jnp.zeros(shape, state.dtype).at[i].set(held)
        lanes, count = ssm.live_lanes(jnp.asarray(live))
        x4 = jnp.repeat(jax.lax.dynamic_index_in_dim(
            x, at, axis=1, keepdims=False), len(live), axis=0)
        h4 = prog.rmsnorm(x4, lp["norm"], cfg.norm_eps)
        conv = jnp.repeat(rows, len(live), axis=0)
        d, _, lane = prog.mamba_decode(h4, lp, conv, lane, i, lanes, count,
                                       cfg)
        idle = jnp.asarray([j for j, on in enumerate(live) if not on])
        written = jnp.sum(jnp.any(lane != 0, axis=(1, 2, 3)))
        untouched = (jnp.all(lane[i][idle] == held[idle]) & (written == 1))
        # the step's inputs from the SAME four rows the step was given (a
        # matmul over one row rounds otherwise than over four)
        _, xs, dt, Bv, _, _ = prog.decode_inputs(h4, lp, conv, cfg)
        return (d[1], lane[i], held, untouched, (xs[1], dt[1], Bv[1]))

    def head(params, x):
        return prog.project_logits(params, prog.rmsnorm(
            x, params["final_norm"], cfg.norm_eps))

    return {"embed": jax.jit(lambda params, tok: prog.embed_lookup(
                params["embed"], tok, cfg.dtype)),
            "layer": {kind: layer(kind) for kind in set(cfg.pattern)},
            "scan_inputs": jax.jit(scan_inputs), "scan": jax.jit(scan),
            "decode": jax.jit(decode), "head": jax.jit(head)}


def _comparisons(cfg, n: int):
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

    def step(after, held, x1, dt1, B1, A_log):
        """Each live lane's state after the step against ONE step of the
        recurrence from what the lane held, on the step's own inputs."""
        P, per = cfg.ssm_head_dim, cfg.inner // cfg.ssm_groups
        dt = jnp.repeat(jax.nn.softplus(dt1), P)
        decay = jnp.exp(dt * jnp.repeat(-jnp.exp(A_log.astype(F32)), P))
        fed = (jnp.repeat(B1.astype(F32).T, per, axis=1)
               * (dt * x1.astype(F32))[None])
        return jnp.maximum(*(
            rel(after[j], decay[None] * held[j].astype(F32) + fed)
            for j in (1, 3)))

    return {"cut": jax.jit(cut), "err": jax.jit(err), "rel": jax.jit(rel),
            "step": jax.jit(step),
            "added": jax.jit(lambda after, before, want_after:
                             err(cut(after) - cut(before),
                                 want_after - cut(before)))}


def block_errors(params, tokens: list[int], model: dict) -> dict:
    """Readings (2)-(4) on one sequence, each block from the program's
    own input, the sequence right-padded and its TRUE length passed.
    Returns {"block", "state", "from_x": (the worst reading, where),
    "loose_share": the largest share of a routed block's positions left
    out for a routing margin under MARGIN_EPS, "by_block": [kind, how
    many, median, worst]}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import nemotron_h as ref

    n = len(tokens)
    P = -(-(n + 1) // 128) * 128    # the flash kernel's multiple, and at
    #                                 least one row of padding
    key = (P, n, tuple(sorted((k, str(v)) for k, v in model.items())))
    if key not in _BLOCKS:
        cfg = program_config(model, max_seq=P)
        _BLOCKS[key] = (cfg, _program_blocks(cfg, n), _comparisons(cfg, n))
    cfg, fn, cmp = _BLOCKS[key]
    # the padding is token ids of its own, not zeros: what is computed
    # past the true length must not reach what is compared
    pad = [(7 * i + 3) % model["vocab_size"] for i in range(P - n)]
    tok = jnp.asarray([list(tokens) + pad], jnp.int32)
    last = jnp.int32(n - 1)
    per = cfg.ssm_heads // cfg.ssm_groups       # heads a group
    cpu = jax.devices("cpu")[0]

    block, state, from_x, loose = [], [], [], [0.0]
    x = fn["embed"](params, tok)
    block.append(("embed", np.asarray(cmp["err"](
        cmp["cut"](x), ref.embed(params, tokens)))))
    seen = {MAMBA: 0, MOE: 0, ATTN: 0}
    for lid, (kind, lp) in enumerate(zip(cfg.pattern, params["layers"])):
        nth = jnp.int32(seen[kind])
        seen[kind] += 1
        xc = cmp["cut"](x)
        x_ref, info = ref.layer(xc, lp, kind, model)
        x_next, kept = fn["layer"][kind](lp, x, jnp.int32(n))
        e = np.asarray(cmp["added"](x_next, x, x_ref))
        if kind == MOE:
            firm = np.asarray(info["margin"]) >= MARGIN_EPS
            loose.append(1.0 - float(firm.mean()))
            e = e[firm]
        block.append((f"{lid}.{kind}", e))
        if kind == ATTN:
            for name, rows in zip("kv", kept):
                block.append((f"{lid}.{name}_rows", np.asarray(cmp["err"](
                    cmp["cut"](rows), info[name]))))
        if kind == MAMBA:
            conv_rows, st = kept
            block.append((f"{lid}.conv_rows", np.asarray(cmp["err"](
                conv_rows[0].astype(jnp.float32), info["conv"]))))
            from_x.append((f"{lid}.prefill", float(cmp["rel"](
                st[0], info["state"]))))
            # (3) the chunked scan of the PADDED row against the
            # token-by-token recurrence at the TRUE length, on the host,
            # the first head of every group, both on the program's own
            # materialised operands
            ins = fn["scan_inputs"](lp, x)
            xs, dt, Bm, Cm = (np.asarray(a[0, :n], np.float32) for a in ins)
            A = -np.exp(np.asarray(lp["A_log"], np.float32))
            on_host = [jax.device_put(a, cpu) for a in
                       (xs[:, ::per], dt[:, ::per], A[::per], Bm, Cm)]
            _, want = ref._jitted(model)["recurrence"](*on_host)
            got = np.asarray(fn["scan"](lp, *ins)[0], np.float32).reshape(
                cfg.ssm_state, cfg.ssm_heads, -1)[:, ::per]
            state.append((f"{lid}.scan", float(cmp["rel"](
                got.reshape(cfg.ssm_state, -1), np.asarray(want)))))
            # one decode step from what the program hands at n - 1, in
            # two of four lanes
            _, (rows1, st1) = fn["layer"][kind](lp, x, jnp.int32(n - 1))
            d1, after, held, untouched, step = fn["decode"](
                lp, x, last, rows1, st1, nth)
            state.append((f"{lid}.update", float(cmp["step"](
                after, held, *step, lp["A_log"]))))
            state.append((f"{lid}.idle_lanes",
                          0.0 if bool(untouched) else float("inf")))
            from_x.append((f"{lid}.decode", float(cmp["rel"](
                after[1], info["state"]))))
            block.append((f"{lid}.decode_step", np.asarray(cmp["err"](
                d1[None].astype(jnp.float32), (x_ref - xc)[n - 1:]))))
        x = x_next
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
    for name, e in block + state + from_x:
        kinds.setdefault(name.split(".", 1)[-1], []).append(
            np.atleast_1d(np.asarray(e, np.float64)))
    return {"block": worst_of(block), "state": worst_of(state),
            "from_x": worst_of(from_x), "loose_share": max(loose),
            "by_block": [[kind, len(es),
                          float(np.median([np.median(e) for e in es
                                           if np.size(e)] or [0.0])),
                          float(max([np.max(e) for e in es if np.size(e)]
                                    or [0.0]))]
                         for kind, es in kinds.items()]}


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal: all three kinds of layer, the Mamba and
    the routed ones twice, two groups, half of eight experts held."""
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, mamba_num_heads=4, mamba_head_dim=32, expand=2,
        n_groups=2, ssm_state_size=16, chunk_size=8, moe_latent_size=32,
        moe_intermediate_size=48, intermediate_size=48,
        moe_shared_expert_intermediate_size=96, n_routed_experts=4,
        num_experts_per_tok=3, vocab_size=512, num_hidden_layers=5,
        hybrid_override_pattern="MEM*E")
    config["published"] = dict(config["published"], n_routed_experts=8)
    config["expert_parallel"] = {"chips": 2, "rank": 0}


# ---------------------------------------------------------------- counts
def _n(m: dict, kind: str) -> int:
    return m["hybrid_override_pattern"].count(kind)


def _inner(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def _conv_dim(m: dict) -> int:
    return _inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def _held_experts(m: dict) -> int:
    return m["experts_held"][1] - m["experts_held"][0]


def _expert_params(m: dict) -> int:
    """TWO matrices an expert, in the latent."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def _mamba_params(m: dict) -> int:
    """W_in (z, xBC, dt) and W_out."""
    d = m["hidden_size"]
    return (d * (_inner(m) + _conv_dim(m) + m["mamba_num_heads"])
            + _inner(m) * d)


def _attn_params(m: dict) -> int:
    d, hd = m["hidden_size"], m["head_dim"]
    return (2 * d * m["num_attention_heads"] * hd
            + 2 * d * m["num_key_value_heads"] * hd)


def _moe_rest_params(m: dict) -> int:
    """An `E` layer outside its experts: the router, W_fc1, W_fc2 and the
    shared expert's two matrices."""
    d = m["hidden_size"]
    return (d * m["router_experts"] + 2 * d * m["moe_latent_size"]
            + 2 * d * m["moe_shared_expert_intermediate_size"])


def _non_expert_matmul_params(m: dict) -> int:
    """Every matmul weight but the experts, the head among them; the
    embedding lookup is no matmul."""
    return (_n(m, MAMBA) * _mamba_params(m) + _n(m, ATTN) * _attn_params(m)
            + _n(m, MOE) * _moe_rest_params(m)
            + m["vocab_size"] * m["hidden_size"])


def param_count(m: dict) -> int:
    """Parameters as the program holds them: the embedding and the head
    apart, a norm a layer and a final norm; a Mamba layer's convolution
    taps and bias, dt_bias, A_log, D and gate norm; the HELD experts and
    the router's biases."""
    d = m["hidden_size"]
    small = ((m["num_hidden_layers"] + 1) * d
             + _n(m, MAMBA) * ((m["conv_kernel"] + 1) * _conv_dim(m)
                               + 3 * m["mamba_num_heads"] + _inner(m))
             + _n(m, MOE) * m["router_experts"])
    return (_non_expert_matmul_params(m) + m["vocab_size"] * d + small
            + _n(m, MOE) * _held_experts(m) * _expert_params(m))


def matmul_params(m: dict) -> int:
    """Parameters a token's step MULTIPLIES on this chip: of a routed
    layer the share of the `num_experts_per_tok` selected experts that is
    held here (22 / 4 at a quarter of 512)."""
    active = (m["num_experts_per_tok"] * _held_experts(m)
              / m["router_experts"])
    return int(_non_expert_matmul_params(m)
               + _n(m, MOE) * active * _expert_params(m))


def lane_state_bytes(m: dict) -> int:
    """Bytes of ONE lane's state matrices in ONE Mamba layer (float32)."""
    return 4 * m["ssm_state_size"] * _inner(m)


def expected_experts_hit(m: dict, lanes: int) -> float:
    """The held experts a step of `lanes` live lanes hits in one routed
    layer, if each lane's choice is uniform over the router's width."""
    p = m["num_experts_per_tok"] / m["router_experts"]
    return _held_experts(m) * (1.0 - (1.0 - p) ** lanes)


def decode_step_bytes(m: dict, lanes: int = 64) -> float:
    """Bytes a decode step of a FULL batch must stream at the least:
    every matmul weight outside the experts once (bf16), the experts the
    batch HITS once (an expert nobody chose is never read), every live
    lane's state matrices of every Mamba layer read and written once, and
    the attention layers' K and V rows of a docs-mix lane (~6.4 k
    tokens)."""
    kv = (lanes * 6400 * _n(m, ATTN) * 2 * 2
          * m["num_key_value_heads"] * m["head_dim"])
    return (2.0 * (_non_expert_matmul_params(m)
                   + _n(m, MOE) * expected_experts_hit(m, lanes)
                   * _expert_params(m))
            + 2.0 * lanes * _n(m, MAMBA) * lane_state_bytes(m) + kv)


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name."""
    if kernel == "ssm_update":
        return _n(m, MAMBA)
    if kernel == "moe_gmm":
        return _n(m, MOE)
    return _n(m, ATTN)          # paged_attn, flash_fwd


def ssm_update_cost(m: dict, lane_steps: float) -> tuple[float, float]:
    """(flops, bytes) the `ssm_update` calls NEED for `lane_steps` (lane,
    layer, step) triples that were work: the lane's state read and
    written once (float32, 4.19 MB each way at 128 x 8,192); x (bf16), B
    and C (bf16, a pair a GROUP) and dt (float32, a number a head) in and
    y (float32) out; and a state element's decay, input and output (two
    multiply-adds and a multiply).  A lane that holds no request is no
    work and is not counted."""
    inner, N = _inner(m), m["ssm_state_size"]
    nbytes = (2 * lane_state_bytes(m) + 2 * inner
              + 2 * 2 * m["n_groups"] * N + 4 * m["mamba_num_heads"]
              + 4 * inner)
    return 5.0 * N * inner * lane_steps, float(nbytes) * lane_steps


def moe_gmm_cost(m: dict, assignments: float, experts_hit: float
                 ) -> tuple[float, float]:
    """(flops, bytes) the `moe_gmm` calls NEED: every expert that held a
    row streamed once a layer-step (TWO matrices of latent x
    intermediate, bf16), never an expert nobody hit; each assignment's
    latent row in and out of each of the two matmuls (latent in,
    intermediate out; intermediate in, latent out) and its two
    matmuls."""
    r, f = m["moe_latent_size"], m["moe_intermediate_size"]
    flops = 2.0 * _expert_params(m) * assignments
    nbytes = 2.0 * (_expert_params(m) * experts_hit
                    + 2 * (r + f) * assignments)
    return flops, nbytes
