"""Model family `lfm2_moe`: the decoder `ray_tpu/models/lfm2.py` serves
(LFM2-24B-A2B: gated short convolutions beside GQA attention with
per-head q/k RMSNorm, a few leading dense SwiGLU layers, then routed
experts with sigmoid scores, a selection-only `expert_bias` and
normalised top-k weights; head tied to the embedding).

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

_PROGRAM = os.path.join(spec.ROOT, "ray_tpu", "models", "lfm2.py")
if not os.path.isfile(_PROGRAM):
    raise SystemExit(
        f"model family lfm2_moe: this checkout's program has no {_PROGRAM}"
        " (ray_tpu.models.lfm2), so it cannot serve the family")

KEYS = ("hidden_size", "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
        "conv_L_cache", "conv_bias", "vocab_size", "norm_eps",
        "rope_parameters", "max_position_embeddings")
ATTENTION = "full_attention"

# Serve: `correct` for this family rests on TWO readings of each sample
# request, each with its own limit (`Judge`, below, takes them and folds
# them into the one number the harness compares).
#
# Why two.  Beside bfloat16 rounding (the `llama` family's 0.04-0.06) this
# model has ROUTING NEAR-TIES.  A routed layer selects the top 4 of 64
# scores; the 4th and the 5th lie ~0.02 apart on average and closer than
# the served path's bfloat16 error at some positions, where the served
# path and the float32 reference pick different experts: that layer's FF
# output changes by a quarter of its weight, the next routed layer's input
# moves, and more choices flip (on the CPU at dim 512 the served router's
# input is 0.7 % off the reference's at the first routed layer and 18 %
# at the eighth; 21-23 % of all (layer, position) choices differ).  Two
# sound bfloat16 runs of one model are two different functions at most
# positions; the prompt's flips travel through convolutions and attention,
# so a position's own routing margin says nothing about its gap (chip, PR
# 28: no correlation), and the WORST token of 120 read 0.63-1.71 over 13
# seeds of one sound program.  End to end, only gross faults stand out of
# that.  So:
#
# (1) The SERVED TOKENS, end to end: the mean over a request's 24 tokens
#     of (reference's largest logit - reference's logit of the served
#     token); the harness takes the worst request.  Limit
#     REFERENCE_GAP_TOL.  It sees whatever the engine does to a request
#     (pool, lane state, waves, lanes).  Readings on the chip (my chip
#     runs, PR 28; worst request's mean):
#       sound, 42 seeds of the weights (16 through the engine alone, 26
#       benchmark runs)                       0.071 ... 0.272 (median 0.15)
#       lane state zeroed at admission        0.819, 0.902, 0.956
#                                                            -> not correct
#       every matmul weight through fp8       0.822          -> not correct
#       one of the four selected dropped      0.703, 0.708, 0.809
#                                                            -> not correct
#       a routed layer skipped 0.245-0.300, the experts' weights through
#       fp8 0.156-0.222, `expert_bias` in the weights 0.056-0.130: INSIDE
#       the sound runs.  Those three are what reading (2) is for.
#     0.45 sits near the geometric mean of 0.272 and 0.703.
#
# (2) The PROGRAM'S BLOCKS, one at a time: the two halves of every layer
#     (`models/lfm2.prefill_op`, `ffn`: what the engine's prefill and
#     decode programs are made of) and the head, run on the device on the
#     request's own tokens, each from the program's OWN input, against
#     the plain reference's same half on the same input.  The reading is
#     the relative error of what a block adds to the stream, the worst
#     over blocks and positions.  Nothing travels from block to block, so
#     a routing flip stays at the position whose margin is small: a routed
#     block is held where the reference's margin (by its own scores, on
#     that input) is >= MARGIN_EPS, and the share of positions left out is
#     held to LOOSE_SHARE_MAX.  Limit BLOCK_ERR_TOL.  Readings on the chip
#     (my chip runs, PR 28; 119 positions a request, 19 blocks):
#       sound, 29 seeds (120 requests)  0.0060 ... 0.0086 (median position
#       0.0039-0.0052 in every kind of block; no flip at a held position:
#       positions left out read 0.0048 or 0.60-0.75)
#       `expert_bias` in the weights    0.043 ... 0.057   -> not correct
#       the experts through fp8         0.059 ... 0.062   -> not correct
#       one of the four selected dropped 0.55 ... 0.60    -> not correct
#       every matmul weight through fp8 0.76 ... 0.93     -> not correct
#       a routed layer skipped          1.0               -> not correct
#       lane state zeroed               0.0061 ... 0.0086 (the engine's
#       fault, no block's: reading (1) holds it)
#     0.018 lies between 0.0086 and 0.043, 2.1 x and 2.4 x away.  Each
#     control read `correct: false` through `serve_cell`'s comparison, a
#     whole benchmark run of a tree carrying the fault (PERF.md section
#     6).  Positions left out of a routed block: 10-19 % (worst block of
#     a request) at MARGIN_EPS 0.002, ten times the router's own
#     rounding; the limit on that share is a bound on how much goes
#     unjudged, not a reading of the program.
#
# What neither reading sees: a fault that lives only in how the DECODE
# program composes its operator half (written out in `decode_step_paged`;
# the FF half is shared with prefill) and moves the served tokens by less
# than the flips do.  PERF.md section 7 keeps that.
REFERENCE_GAP_TOL = 0.45
BLOCK_ERR_TOL = 0.018
MARGIN_EPS = 0.002
LOOSE_SHARE_MAX = 0.5


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run (nested
    groups whole)."""
    return {k: config[k] for k in KEYS}


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def program_config(model: dict, max_seq: int, **extra):
    """Lfm2MoeConfig for the published keys: only sizes move.  Refuses
    what the program does not express."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import Lfm2MoeConfig

    if model["conv_bias"]:
        raise ValueError("the program's short convolution has no bias; "
                         f"{model} publishes conv_bias")
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers "
                         "layers")
    if model["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("the program's RoPE is the default type")
    return Lfm2MoeConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        layer_types=tuple(model["layer_types"]),
        n_dense_layers=model["num_dense_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"],
        moe_ffn_dim=model["moe_intermediate_size"],
        n_experts=model["num_experts"], top_k=model["num_experts_per_tok"],
        conv_kernel=model["conv_L_cache"],
        norm_eps=float(model["norm_eps"]),
        rope_theta=float(model["rope_parameters"]["rope_theta"]),
        norm_topk_prob=bool(model["norm_topk_prob"]),
        use_expert_bias=bool(model["use_expert_bias"]),
        routed_scaling=float(model["routed_scaling_factor"]),
        max_seq=max_seq, dtype=jnp.bfloat16, **extra)


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it.  `expert_bias` is drawn N(0, 0.02) (`assumed` in the
    configuration file)."""
    from ray_tpu.models import lfm2

    return lfm2.init_params(key, cfg)


def reference():
    """The judge of a serve cell: `teacher_forced_gaps(params, prompt,
    served, model)` over the plain reference `refs/lfm2_moe.py`."""
    return Judge


class Judge:
    """Two readings for one request, each held to its own limit (the
    reasons and the chip's readings stand above `REFERENCE_GAP_TOL`):

    - the SERVED TOKENS under the plain reference: the mean over the
      request of `refs/lfm2_moe.token_gaps` (limit `REFERENCE_GAP_TOL`).
      This is the engine's own output: pool, lane state, waves, lanes;
    - the PROGRAM'S BLOCKS against the reference's, one at a time and
      each from the program's own input (limit `BLOCK_ERR_TOL`): the two
      halves of every layer (`models/lfm2.prefill_op`, `ffn`: what the
      engine's prefill and decode programs are made of) and the head, run
      on the request's tokens on the device at the served widths, and the
      plain reference's same half on the same input.  No error travels
      from one block to the next, so a routing flip stays where its
      near-tie is: a routed block is held at the positions whose
      reference margin is at least `MARGIN_EPS` (the rest are counted and
      their share held to `LOOSE_SHARE_MAX`).  The reference is given
      activations, never an expert choice: it routes by its own scores.

    The harness compares ONE number with `REFERENCE_GAP_TOL`, the worst
    it finds among the values returned, so each reading is returned as
    its share of its limit times `REFERENCE_GAP_TOL`: 0.0 at a token the
    reference chose too, the request's larger share elsewhere.  Both
    readings and their limits are printed (the replica's log reaches the
    run's output)."""

    @staticmethod
    def teacher_forced_gaps(params, prompt, served, model) -> list[float]:
        import json

        from benchmarks.harness.refs import lfm2_moe as ref

        gaps = ref.token_gaps(params, prompt, served, model)
        mean_gap = sum(gaps) / len(gaps)
        blocks = block_errors(params, list(prompt) + list(served[:-1]),
                              model)
        shares = {"token_gap": mean_gap / REFERENCE_GAP_TOL,
                  "block_err": blocks["worst"] / BLOCK_ERR_TOL,
                  "loose_share": blocks["loose_share"] / LOOSE_SHARE_MAX}
        worst = max(shares.values())
        if any(v != v for v in shares.values()):     # a NaN anywhere
            worst = float("inf")
        print(json.dumps({
            "step": "lfm2_moe.judge", "mean_token_gap": mean_gap,
            "limit": REFERENCE_GAP_TOL, "worst_block_err": blocks["worst"],
            "block_limit": BLOCK_ERR_TOL, "at": blocks["at"],
            "loose_share": blocks["loose_share"],
            "loose_limit": LOOSE_SHARE_MAX,
            "worst_token_gap": max(gaps), "by_block": blocks["by_block"],
            "held_by": max(shares, key=shares.get)}), flush=True)
        reading = worst * REFERENCE_GAP_TOL
        out = [0.0 if g == 0.0 else reading for g in gaps]
        if not any(out):
            out[0] = reading
        return out


def _program_blocks(cfg):
    """The program's halves of layer `lid` and its head, each jitted
    once: (op, ff, head)."""
    import functools

    import jax

    from ray_tpu.models import lfm2

    @functools.lru_cache(maxsize=None)
    def op(lid):
        return jax.jit(lambda x, lp, n: lfm2.prefill_op(x, lp, lid, cfg, n)[0])

    @functools.lru_cache(maxsize=None)
    def ff(lid):
        return jax.jit(lambda x, lp, live: lfm2.ffn(x, lp, lid, cfg, live)[0])

    head = jax.jit(lambda params, x: lfm2.project_logits(
        params, lfm2.rmsnorm(x, params["final_norm"], cfg.norm_eps)))
    return op, ff, head


_BLOCKS: dict = {}


def block_errors(params, tokens: list[int], model: dict) -> dict:
    """Every block of the program against the reference's on one
    sequence, each from the program's own input.  A block's error at a
    position is |program - reference| / |reference| over what the block
    ADDS to the stream (the logits, for the head), 2-norms over the
    features.  Returns
    {"worst": the largest error among the positions held, "at": its
    block, "loose_share": the share of a routed block's positions whose
    reference margin is under MARGIN_EPS, the worst block's,
    "by_block": [name, median, worst held, worst not held]}."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness.refs import lfm2_moe as ref
    from ray_tpu.models import lfm2

    n = len(tokens)
    P = -(-n // 128) * 128          # the flash kernel's multiple
    key = (P, tuple(sorted((k, str(v)) for k, v in model.items())))
    if key not in _BLOCKS:
        cfg = program_config(model, max_seq=P)
        _BLOCKS[key] = (cfg, _program_blocks(cfg))
    cfg, (op, ff, head) = _BLOCKS[key]
    tok = jnp.zeros((1, P), jnp.int32).at[0, :n].set(jnp.asarray(tokens))
    lens = jnp.asarray([n], jnp.int32)
    live = jnp.arange(P)[None, :] < n
    f32 = lambda a: a[0, :n].astype(jnp.float32)       # noqa: E731

    def err(got, want):
        return np.asarray(jnp.linalg.norm(got - want, axis=-1)
                          / jnp.linalg.norm(want, axis=-1))

    rows = []                      # (name, err [n], held [n] bool)
    everywhere = np.ones((n,), bool)
    x = lfm2.embed_lookup(params["embed"], tok, cfg.dtype)
    for lid, lp in enumerate(params["layers"]):
        kind = "attn" if cfg.is_attn(lid) else "conv"
        d = op(lid)(x, lp, lens)
        want = ref.op_half(f32(x), lp, lid, model)
        rows.append((f"{lid}.{kind}", err(f32(d), want - f32(x)),
                     everywhere))
        x = x + d
        d = ff(lid)(x, lp, live)
        want, margin = ref.ff_half(f32(x), lp, lid, model)
        held = everywhere if margin is None \
            else np.asarray(margin) >= MARGIN_EPS
        rows.append((f"{lid}.{'dense' if margin is None else 'routed'}",
                     err(f32(d), want - f32(x)), held))
        x = x + d
    want = ref.head(f32(x), params, model)
    rows.append(("head", err(f32(head(params, x)), want), everywhere))

    worst, at = max((float(np.max(e[held], initial=0.0)), name)
                    for name, e, held in rows)
    if any(not np.all(np.isfinite(e)) for _, e, _ in rows):
        worst = float("nan")
    return {"worst": worst, "at": at,
            "loose_share": max(1.0 - float(np.mean(h)) for _, _, h in rows),
            "by_block": [[name, float(np.median(e)),
                          float(np.max(e[held], initial=0.0)),
                          float(np.max(e[~held], initial=0.0))]
                         for name, e, held in rows]}


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal: every kind of layer once."""
    config.update(hidden_size=128, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=256,
                  moe_intermediate_size=128, num_experts=8,
                  num_experts_per_tok=2, vocab_size=512,
                  num_hidden_layers=3, num_dense_layers=1,
                  layer_types=["conv", ATTENTION, "conv"])


# ---------------------------------------------------------------- counts
def _attn_layers(m: dict) -> int:
    return m["layer_types"].count(ATTENTION)


def _routed_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["num_dense_layers"]


def _expert_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def _non_expert_matmul_params(m: dict) -> int:
    """Matmul parameters outside the experts' matrices: attention,
    convolution projections, dense SwiGLU, routers, the (tied) head."""
    d = m["hidden_size"]
    hd = d // m["num_attention_heads"]
    attn = (2 * d * m["num_attention_heads"] * hd
            + 2 * d * m["num_key_value_heads"] * hd)
    conv = 3 * d * d + d * d
    n_attn = _attn_layers(m)
    return (n_attn * attn + (m["num_hidden_layers"] - n_attn) * conv
            + m["num_dense_layers"] * 3 * d * m["intermediate_size"]
            + _routed_layers(m) * d * m["num_experts"]
            + m["vocab_size"] * d)


def param_count(m: dict) -> int:
    """Parameters as the program holds them: the embedding once (tied
    head), two norms a layer and a final norm, q/k norms, the
    convolution's taps, every expert, the expert biases."""
    d = m["hidden_size"]
    n_attn = _attn_layers(m)
    small = ((2 * m["num_hidden_layers"] + 1) * d
             + n_attn * 2 * (d // m["num_attention_heads"])
             + (m["num_hidden_layers"] - n_attn) * m["conv_L_cache"] * d
             + _routed_layers(m) * m["num_experts"])
    return (_non_expert_matmul_params(m) + small
            + _routed_layers(m) * m["num_experts"] * _expert_params(m))


def matmul_params(m: dict) -> int:
    """Parameters a token's step MULTIPLIES: a routed layer counts the
    `num_experts_per_tok` experts a token is sent to, not all of them;
    the tied head counts (a matmul), the embedding lookup does not."""
    return (_non_expert_matmul_params(m)
            + _routed_layers(m) * m["num_experts_per_tok"]
            * _expert_params(m))


def decode_step_bytes(m: dict) -> float:
    """Bytes a decode step of a FULL batch must stream at the least:
    every matmul weight once, bf16, all experts among them (a batch of
    64 lanes x 4 hits ~63 of 64; `moe_gmm_cost` counts the experts a
    window really hit)."""
    return 2.0 * (_non_expert_matmul_params(m)
                  + _routed_layers(m) * m["num_experts"]
                  * _expert_params(m))


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name: the attention
    kernels run in the attention layers only, `moe_gmm` in the routed
    ones."""
    if kernel == "moe_gmm":
        return _routed_layers(m)
    return _attn_layers(m)


def moe_gmm_cost(m: dict, assignments: float, experts_hit: float
                 ) -> tuple[float, float]:
    """(flops, bytes) the `moe_gmm` calls NEED, summed over layer-steps:
    `assignments` token-expert pairs computed and `experts_hit` experts
    that held at least one row (each summed over the routed layers and
    steps).  An assignment multiplies one expert's three matrices
    (2 ops a multiply-add) and moves its rows in and out (d in, 2f out;
    f in, d out; bf16); an expert that was hit is streamed once a
    layer-step, one that was not is never read."""
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * _expert_params(m) * assignments
    nbytes = 2.0 * (_expert_params(m) * experts_hit
                    + (2 * d + 3 * f) * assignments)
    return flops, nbytes
