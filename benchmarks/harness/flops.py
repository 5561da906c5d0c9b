"""Operations and bytes the algorithm REQUIRES, from shapes alone.

Every function takes the model as the plain dict of its published keys
(`hidden_size`, `num_hidden_layers`, ...).  Causal attention is counted
once: a query at position i attends i+1 keys, so a sequence of s tokens
needs s(s+1)/2 query-key pairs, not s*s.  Recomputation (remat) is never
counted: MFU is about required work.  A multiply-add is 2 operations.
"""
from __future__ import annotations


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def param_count(m: dict) -> int:
    """Parameters of the decoder as the program holds them (untied head,
    two norms a layer and a final norm)."""
    d, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    hd = head_dim(m)
    per_layer = (d * m["num_attention_heads"] * hd          # wq
                 + 2 * d * m["num_key_value_heads"] * hd    # wk, wv
                 + m["num_attention_heads"] * hd * d        # wo
                 + 3 * d * f                                # gate, up, down
                 + 2 * d)                                   # norms
    return 2 * v * d + m["num_hidden_layers"] * per_layer + d


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matmul for every token: all but the
    embedding table (a lookup) and the norms."""
    d = m["hidden_size"]
    return (param_count(m) - m["vocab_size"] * d
            - (2 * m["num_hidden_layers"] + 1) * d)


def causal_pairs(s: int) -> int:
    """Query-key pairs of one causal sequence of s tokens."""
    return s * (s + 1) // 2


def attn_flops_fwd(pairs: int, m: dict) -> float:
    """Forward attention over `pairs` query-key pairs, per layer, all
    query heads: QK^T and PV, 2 ops per multiply-add each."""
    return 4.0 * pairs * m["num_attention_heads"] * head_dim(m)


def train_flops_per_step(m: dict, batch: int, seq: int) -> float:
    """Forward + backward of one optimizer step: 6 ops per matmul
    parameter per token, plus causal attention forward (x1) and backward
    (x2: dq, dk, dv need two matmul pairs)."""
    tokens = batch * seq
    attn = 3.0 * attn_flops_fwd(batch * causal_pairs(seq), m) \
        * m["num_hidden_layers"]
    return 6.0 * matmul_params(m) * tokens + attn


def flash_fwd_cost(m: dict, lens: list[int]) -> tuple[float, float]:
    """(flops, bytes) one flash-forward kernel call of one layer needs for
    causal sequences of the given TRUE lengths: q and o read/written once
    per query head, k and v once per kv head, bf16."""
    hd = head_dim(m)
    flops = sum(attn_flops_fwd(causal_pairs(s), m) for s in lens)
    rows = sum(lens)
    nbytes = 2.0 * rows * hd * (2 * m["num_attention_heads"]
                                + 2 * m["num_key_value_heads"])
    return flops, nbytes


def flash_bwd_cost(m: dict, batch: int, seq: int) -> tuple[float, float]:
    """(flops, bytes) of the flash backward of one layer (both kernels
    together): five matmuls over the causal pairs where the forward has
    two (S is recomputed: S, dP, dV, dK, dQ), reading q, k, v, o, do and
    writing dq, dk, dv."""
    hd = head_dim(m)
    flops = 2.5 * attn_flops_fwd(batch * causal_pairs(seq), m)
    rows = batch * seq
    nbytes = 2.0 * rows * hd * (4 * m["num_attention_heads"]
                                + 4 * m["num_key_value_heads"])
    return flops, nbytes


def paged_attn_cost(m: dict, ctx_lens: list[int]) -> tuple[float, float]:
    """(flops, bytes) of one paged decode-attention call of one layer:
    every lane reads its whole context of k and v once (bf16) and does
    one query row against it.  Bandwidth bounds it."""
    hd = head_dim(m)
    ctx = sum(ctx_lens)
    flops = 4.0 * ctx * m["num_attention_heads"] * hd
    nbytes = 2.0 * ctx * 2 * m["num_key_value_heads"] * hd
    return flops, nbytes


def decode_step_bytes(m: dict) -> float:
    """Bytes a decode step must stream at the least: every matmul weight
    once, bf16 (the KV read comes on top)."""
    return 2.0 * matmul_params(m)
