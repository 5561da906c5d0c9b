"""A second model family, as a later PR would add one: a stand-in for the
tests and the README's worked example.  It is the Llama decoder under
another name and under other key spellings (`norm_eps`,
`rope_parameters.rope_theta`, a `layer_types` list, no `head_dim`), so the
program it hands out is one the repo can run; its tolerances, its tiny
shapes and its layer counts are its own, so a test can tell which family
the harness asked.  A real family file stands in `harness/families/`.
"""
from __future__ import annotations

from benchmarks.harness import spec

KEYS = ("hidden_size", "num_hidden_layers", "layer_types",
        "num_attention_heads", "num_key_value_heads", "intermediate_size",
        "vocab_size", "norm_eps", "rope_parameters",
        "max_position_embeddings")
ATTENTION = "full_attention"
# each with its written reason in a real file; here only not Llama's
REFERENCE_GAP_TOL = 0.25
LOGPROB_RMS_TOL = 0.09
GRAD_NORM_RTOL = 2e-2
LOSS_RTOL = 3e-3
CALLS: list[str] = []       # what the harness asked of this family


def _llama():
    return spec.load_family("llama")


def _as_llama(m: dict) -> dict:
    """The same decoder in the Llama family's spelling."""
    return dict(
        {k: m[k] for k in ("hidden_size", "num_hidden_layers",
                           "num_attention_heads", "num_key_value_heads",
                           "intermediate_size", "vocab_size",
                           "max_position_embeddings")},
        head_dim=m["hidden_size"] // m["num_attention_heads"],
        rope_theta=m["rope_parameters"]["rope_theta"],
        rms_norm_eps=m["norm_eps"])


def published(config: dict) -> dict:
    return {k: config[k] for k in KEYS}     # nested groups whole


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


def program_config(model: dict, max_seq: int, **extra):
    CALLS.append("program_config")
    return _llama().program_config(_as_llama(model), max_seq, **extra)


def init_params(key, cfg):
    CALLS.append("init_params")
    return _llama().init_params(key, cfg)


class _Reference:
    """A real family names a file of its own under `harness/refs/`."""

    @staticmethod
    def teacher_forced_gaps(params, prompt, served, model):
        CALLS.append("teacher_forced_gaps")
        return _llama().reference().teacher_forced_gaps(
            params, prompt, served, _as_llama(model))

    @staticmethod
    def loss_and_gradient(params, inputs, targets, model):
        CALLS.append("loss_and_gradient")
        return _llama().reference().loss_and_gradient(
            params, inputs, targets, _as_llama(model))


def reference():
    return _Reference


def rehearsal(config: dict) -> None:
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, intermediate_size=96,
                  vocab_size=256, num_hidden_layers=2,
                  layer_types=["conv", ATTENTION])


def param_count(m: dict) -> int:
    return _llama().param_count(_as_llama(m))


def matmul_params(m: dict) -> int:
    return _llama().matmul_params(_as_llama(m))


def decode_step_bytes(m: dict) -> float:
    return 2.0 * matmul_params(m)


def kernel_layers(m: dict, kernel: str) -> int:
    """Only the layers `layer_types` calls attention layers call an
    attention kernel: not the depth."""
    return m["layer_types"].count(ATTENTION)
