"""The contract every served family's test file holds its model to, in
ONE place (imported rootdir-relative, like `serving_reference`): a
family's file declares its `CFG`, its reference, its prompts and its
controls, keeps the cases that are its own (a kernel against a masked
softmax, a ring written in place, a walk against a gather), and reads
the shared cases off what this module ran ONCE:

  - `served_run`: one `LLMEngine` a family, started once over prompts
    under, at and past each bucket and window, with every logit its
    programs computed noted through `jax.debug.callback`, its stats, its
    lane state around the first request and its lowered decode program.
    An engine's programs are `jax.jit` of closures made in its
    constructor, so a second engine of the same configuration compiles
    every one of them again: the cases of a file READ this run.
  - `serving_reference.Seam`: the model's serving seam with its three
    programs jitted as the engine jits them.  One `Seam` kept by a file
    is one compile a shape for every sound case; a control, whose patch
    has to be traced, makes its own, on a cut of the model that keeps
    the layer it changes.  `prefill_rows`: several true lengths as the
    rows of one prompt pass.
  - `one_length`: a reference's logits computed at ONE padded length (the
    references jit their pieces by shape: every new length is a compile
    of each).

What a whole tier-1 run may cost is a budget (ROADMAP D18): a new
family's file uses these and adds no engine of its own without a
comment saying what it needs of it."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention
from ray_tpu.serve.llm import LLMEngine


def gap(got, want) -> float:
    """The largest difference of two arrays of logits as a share of the
    reference's largest."""
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def one_length(logits, length):
    """`logits(params, seq) -> [len(seq), vocab]` computed on `seq`
    right-padded to `length` (the models are causal: the padding cannot
    reach a true position) and kept by sequence, so that a file compiles
    its reference for ONE length and computes each sequence once."""
    kept: dict = {}

    def at(params, seq, last=None):
        seq = [int(t) for t in seq]
        assert len(seq) <= length, (len(seq), length)
        if tuple(seq) not in kept:
            padded = seq + [0] * (length - len(seq))
            kept[tuple(seq)] = np.asarray(logits(params, padded))[:len(seq)]
        rows = kept[tuple(seq)]
        return rows if last is None else rows[-last:]

    return at


def prefill_rows(seam, params, rows, lens):
    """ONE prompt pass for several true lengths: `rows` (token arrays,
    right-padded here to the longest) are the rows of one program, their
    true lengths `lens` beside them, as the engine pads a wave.  Returns
    (the padded tokens, the hidden rows [len(rows), width, d])."""
    width = max(len(r) for r in rows)
    toks = np.stack([np.pad(r, (0, width - len(r))) for r in rows])
    return toks, seam.serve_prefill(params, jnp.asarray(toks),
                                    jnp.asarray(lens, jnp.int32))[0]


def served_run(module, cfg, params, *, prompts, new, lanes, kv_pages,
               page=16, k=4, max_len=96, first=(9, 1, 9)) -> dict:
    """ONE engine run for a family's file.  `first` (length, seed, new
    tokens) is served alone on a fresh engine of `lanes` lanes whose lane
    state was marked (+1.0) beforehand: the state before and after it
    says which lanes a request writes.  Then `prompts` (token lists) are
    submitted at once, more of them than lanes, so lanes are reused.
    Returned: every logit the engine's own programs computed, by (input
    token, position); the outputs; the stats after `first` and at the
    end; the decode program lowered for its donations."""
    seen = []

    def note(toks, pos, live, logits):
        for t, p, ok, lg in zip(*map(np.asarray, (toks, pos, live, logits))):
            if ok:
                seen.append((int(t), int(p), lg))

    step, prefill = module.serve_decode_step, module.serve_prefill

    def decode_step(params, pages, tails, state, tokens, pos, ts, j, table,
                    cfg, lora=None, plan=None):
        out = step(params, pages, tails, state, tokens, pos, ts, j, table,
                   cfg, lora, plan)
        jax.debug.callback(note, tokens, pos,
                           paged_attention.lanes_live(table), out[0])
        return out

    def prefill_rows(params, tokens, cfg, true_lens, lora=None):
        out = prefill(params, tokens, cfg, true_lens, lora)
        rows = jnp.arange(tokens.shape[0])
        last = out[0][rows, true_lens - 1]
        jax.debug.callback(
            note, tokens[rows, true_lens - 1], true_lens - 1,
            jnp.ones_like(true_lens, bool),
            module.project_logits(params, last).astype(jnp.float32))
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(module, "serve_decode_step", decode_step)
        mp.setattr(module, "serve_prefill", prefill_rows)
        eng = LLMEngine(cfg, params, max_batch=lanes, max_len=max_len,
                        page_size=page, kv_pages=kv_pages, steps_per_sync=k)
        marked = jax.tree.map(lambda a: a + 1.0, eng.cache["state"])
        eng.cache = {**eng.cache, "state": marked}
        before = jax.tree.map(np.asarray, marked)
        lowered = eng._decode_fns[k].lower(
            eng.params, eng.cache, eng._cur_dev, jnp.zeros((lanes,)),
            eng._table_dev, jnp.zeros((lanes,), jnp.int32),
            jnp.zeros((lanes,), jnp.int32), None)
        eng.start()
        try:
            n, seed, n_new = first
            first_prompt = tokens(n, seed).tolist()
            first_out = eng.generate(first_prompt, max_new_tokens=n_new)
            after_one = jax.tree.map(np.asarray, eng.cache["state"])
            jax.effects_barrier()
            first_stats = eng.stats()
            futs = [eng.submit(p, max_new_tokens=new) for p in prompts]
            outs = [f.result(timeout=300) for f in futs]
            jax.effects_barrier()
            st = eng.stats()
        finally:
            eng.stop()
    by_key: dict = {}
    for t, p, lg in seen:
        by_key.setdefault((t, p), []).append(lg)
    return {"prompts": list(prompts), "outs": outs, "logits": by_key,
            "stats": st, "first": first_out, "first_prompt": first_prompt,
            "first_stats": first_stats, "state": (before, after_one),
            "lowered": lowered}


def engine_gap(served, i, want_rows) -> float:
    """Request `i` of a `served_run`: the largest gap between the logits
    the engine's own programs computed at each served position and
    `want_rows` (the reference's rows for those positions, the prompt's
    last first).  A position whose logits were never noted fails."""
    prompt, out = served["prompts"][i], served["outs"][i]
    seq = prompt + out["tokens"]
    assert len(want_rows) == len(out["tokens"])
    worst = 0.0
    for j, row in enumerate(want_rows):
        p = len(prompt) - 1 + j
        got = served["logits"].get((seq[p], p), [])
        assert got, (len(prompt), j)
        worst = max(worst, min(gap(g, row) for g in got))
    return worst


def lanes_written(served, leaf) -> list[int]:
    """The lanes whose `leaf` of the lane state the first request of a
    `served_run` changed; `leaf(state)` picks arrays indexed [lane, ...]
    out of the state (one or a list of them)."""
    before, after = (leaf(s) for s in served["state"])
    if not isinstance(before, (list, tuple)):
        before, after = [before], [after]
    lanes = range(before[0].shape[0])
    return sorted({i for b, a in zip(before, after) for i in lanes
                   if not (a[i] == b[i]).all()})
