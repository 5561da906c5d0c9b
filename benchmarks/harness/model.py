"""From a configuration file's published keys to the program's model
config (a copy of the pattern in chip_smoke.py, which later PRs may
change and the yardstick may not depend on)."""
from __future__ import annotations

HF_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
           "num_key_value_heads", "head_dim", "intermediate_size",
           "vocab_size", "rope_theta", "rms_norm_eps",
           "max_position_embeddings")


def published(config: dict) -> dict:
    """The model keys of a configuration file, as it is run."""
    return {k: config[k] for k in HF_KEYS}


def llama_config(model: dict, max_seq: int, **extra):
    """LlamaConfig for a Mistral-family dict: the same decoder equations
    (RMSNorm, RoPE, GQA, SwiGLU, untied head, no bias), so only sizes
    move.  Refuses a head_dim the program cannot express."""
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
