"""Operations and bytes the algorithm REQUIRES, from shapes alone: the
arithmetic that holds for any model family (attention by heads and
`head_dim`).  What depends on the architecture (how many parameters a
token's step multiplies, how many layers call a kernel) is the family's
(`families/<name>.py`).

Every function takes the model as the plain dict of its published keys
(`num_attention_heads`, `num_key_value_heads`, ...).  Causal attention
is counted once: a query at position i attends i+1 keys, so a sequence
of s tokens needs s(s+1)/2 query-key pairs, not s*s.  Recomputation
(remat) is never counted: MFU is about required work.  A multiply-add is
2 operations.
"""
from __future__ import annotations


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def causal_pairs(s: int) -> int:
    """Query-key pairs of one causal sequence of s tokens."""
    return s * (s + 1) // 2


def attn_flops_fwd(pairs: int, m: dict) -> float:
    """Forward attention over `pairs` query-key pairs, per layer, all
    query heads: QK^T and PV, 2 ops per multiply-add each."""
    return 4.0 * pairs * m["num_attention_heads"] * head_dim(m)


def train_flops_per_step(family, m: dict, batch: int, seq: int) -> float:
    """Forward + backward of one optimizer step: 6 ops per parameter a
    token's step multiplies, per token, plus causal attention forward
    (x1) and backward (x2: dq, dk, dv need two matmul pairs) in each
    layer that calls the flash kernel."""
    tokens = batch * seq
    attn = 3.0 * attn_flops_fwd(batch * causal_pairs(seq), m) \
        * family.kernel_layers(m, "flash_fwd")
    return 6.0 * family.matmul_params(m) * tokens + attn


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
