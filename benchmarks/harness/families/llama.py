"""Model family `llama`: the dense decoder `ray_tpu/models/llama.py` runs
(RMSNorm, RoPE, GQA, SwiGLU, untied head, no bias, `head_dim =
hidden/heads`), which Mistral-7B and Codestral-22B publish.  A
configuration file without a `family` key means this one.

A family file is everything the harness knows about one architecture
(`benchmarks/README.md`, "A model family", holds the contract): which keys
of the configuration file are the model, the program's config object and
its weights, the plain reference and the tolerances it is held to, the
parameter and byte counts, and how many layers call each Pallas kernel.
Nothing here imports `jax` at load: the driver process loads the family
and never initializes a backend.
"""
from __future__ import annotations

from benchmarks.harness import flops

HF_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
           "num_key_value_heads", "head_dim", "intermediate_size",
           "vocab_size", "rope_theta", "rms_norm_eps",
           "max_position_embeddings")

# Serve: teacher-forced logit gap a served token may show under the
# float32 reference.  The served path computes in bfloat16 (8 bits of
# mantissa): a rounding of 2**-9 relative in each of ~10
# matmul-and-residual stages per layer gives the final hidden state a
# relative error of some 1e-2, and logits of random weights have unit
# scale, so near-ties flip at gaps of a few 1e-2.  On the chip the
# largest gap read was 0.0616 (mistral d16, 32 runs) and 0.0397
# (codestral d8, 22 runs), and 93-120 of 120 scored tokens were the
# reference's own choice (my chip runs, PR 24).  A skipped layer or fp8
# weights (3 bits of mantissa, 32x the rounding) move logits by tenths to
# whole units: a served token is then about 4 below the reference's
# maximum, far outside.
REFERENCE_GAP_TOL = 0.15

# Train (`train_loop.judge` compares; `train_loop.py` says what).  At
# random init the MEAN loss sits at ln(vocab) + ~0.5 whatever the layers
# compute (the final RMSNorm fixes the logit scale), so it proves little
# alone.  Read on the CPU at hidden 256-1024, 4-16 layers, against the
# float32 reference (PR 24; the chip's readings are in PERF.md):
#
#   the program's          mean loss   gradient norm   log-prob, rms
#   bfloat16 as it is      4e-5..1e-4  5e-4            0.013-0.014
#   weights through fp8    1e-4..1e-3  5e-3..1e-2      0.12-0.28
#   one layer skipped      1e-3..1e-2  3e-2..1e-1      0.40-1.5
#   no attention (wo = 0)  7e-3..2e-2  1.0-2.4         1.3-1.5
#
# So the per-position log-probabilities carry the forward check; the
# gradient norm carries the backward one, but the step reports it in
# bfloat16 (`optax.global_norm` over bfloat16 gradients: 2**-8 = 0.4 % of
# rounding in the number itself), so its bound cannot go under ~1 % and
# sees a lost collective, a dropped layer's gradients or a wrong scale,
# not an fp8 backward.
LOGPROB_RMS_TOL = 0.08
GRAD_NORM_RTOL = 1.5e-2
LOSS_RTOL = 2e-3


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run."""
    return {k: config[k] for k in HF_KEYS}


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def program_config(model: dict, max_seq: int, **extra):
    """LlamaConfig for a Mistral-family dict: the same decoder equations,
    so only sizes move.  Refuses a head_dim the program cannot express."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if model["hidden_size"] != model["num_attention_heads"] * model["head_dim"]:
        raise ValueError("the program fixes head_dim = hidden/heads; "
                         f"{model} publishes another")
    return LlamaConfig(
        vocab_size=model["vocab_size"], dim=model["hidden_size"],
        n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"],
        ffn_dim=model["intermediate_size"], max_seq=max_seq,
        rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=jnp.bfloat16, **extra)


def init_params(key, cfg):
    """Every weight from one PRNG key, in the dtype it is served in; the
    caller jits it (one program, made on the device)."""
    from ray_tpu.models import llama

    return llama.init_params(key, cfg)


def reference():
    """The plain reference's module: `teacher_forced_gaps(params, prompt,
    served, model)` and `loss_and_gradient(params, inputs, targets,
    model)`."""
    from benchmarks.harness.refs import decoder

    return decoder


def rehearsal(config: dict) -> None:
    """Shrink the model keys of a configuration IN MEMORY to debug-sized
    shapes for the CPU rehearsal."""
    config.update(hidden_size=128, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=32,
                  intermediate_size=256, vocab_size=512,
                  num_hidden_layers=2)


# ---------------------------------------------------------------- counts
def param_count(m: dict) -> int:
    """Parameters of the decoder as the program holds them (untied head,
    two norms a layer and a final norm)."""
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = flops.head_dim(m)
    per_layer = (d * m["num_attention_heads"] * hd          # wq
                 + 2 * d * m["num_key_value_heads"] * hd    # wk, wv
                 + m["num_attention_heads"] * hd * d        # wo
                 + 3 * d * f                                # gate, up, down
                 + 2 * d)                                   # norms
    return 2 * v * d + m["num_hidden_layers"] * per_layer + d


def matmul_params(m: dict) -> int:
    """Parameters a token's step multiplies: all but the embedding table
    (a lookup) and the norms.  Every layer is dense, so all are active."""
    d = m["hidden_size"]
    return (param_count(m) - m["vocab_size"] * d
            - (2 * m["num_hidden_layers"] + 1) * d)


def decode_step_bytes(m: dict) -> float:
    """Bytes a decode step must stream at the least: every matmul weight
    once, bf16 (the KV read comes on top)."""
    return 2.0 * matmul_params(m)


def kernel_layers(m: dict, kernel: str) -> int:
    """How many layers call the Pallas kernel of that name (`flash_fwd`,
    `flash_bwd_dq`, `flash_bwd_dkv`, `paged_attn`): every layer of this
    decoder is an attention layer."""
    return m["num_hidden_layers"]
