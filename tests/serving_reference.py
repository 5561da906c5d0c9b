"""What the serving tests compare with, and the driver that walks a
model's serving seam (`ray_tpu.models.serving_model`) by hand: shared by
the llama, LFM2 and latent-attention tests (imported rootdir-relative, like the other
helpers in this directory)."""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp
import numpy as np


def reference_greedy(params, cfg, prompt, n_new):
    """Greedy tokens of `llama.forward`, the plain full forward the train
    step differentiates, on the whole context at every step — the
    slow-but-sure decoder, independent of every serving program.  The
    context sits in one buffer of its final length (one compile): the
    forward is causal, so what lies past a position cannot reach it."""
    from ray_tpu.models import llama

    n = len(prompt)
    toks = np.zeros((1, n + n_new), np.int32)
    toks[0, :n] = prompt
    fwd = jax.jit(lambda p, t: llama.forward(p, t, cfg))
    for i in range(n, n + n_new):
        toks[0, i] = int(jnp.argmax(fwd(params, jnp.asarray(toks))[0, i - 1]))
    return toks[0, n:].tolist()


class Seam:
    """`module`'s serving seam as `served_logits` walks it, each of its
    three programs under ONE `jax.jit` (as the engine runs them), the
    module's function looked up at the trace.  Kept by a file (one a
    configuration and form), the sound cases share its compiles: a case
    that differs from another in a true length, and not in a shape,
    compiles nothing.  Made inside a control, after the patch, it traces
    the patch."""

    def __init__(self, module, cfg):
        self._of = (module, cfg)
        self.project_logits = module.project_logits
        self.init_paged_cache = module.init_paged_cache
        self.serve_prefill = jax.jit(
            lambda p, t, n: module.serve_prefill(p, t, cfg, n))
        self.serve_scatter = jax.jit(
            lambda c, *a: module.serve_scatter(c, *a))
        self.decode_step = jax.jit(
            lambda *a: module.serve_decode_step(*a, cfg))

    def retraced(self, *names):
        """A copy whose named programs are traced anew, at their first
        call (under a patch that reaches those and no other), and whose
        other programs are this seam's compiles."""
        fresh, new = Seam(*self._of), copy.copy(self)
        for name in names:
            setattr(new, name, getattr(fresh, name))
        return new


def served_logits(seam: Seam, params, cfg, prompt, follow, bucket, *,
                  page=16, k=4):
    """Logits of the served path at every position from the prompt's last
    on: the prompt padded to `bucket` in a wave of two rows (the other a
    longer prompt), scattered into a page pool and lane 1, then
    teacher-forced paged decode in windows of `k` over `follow`, the
    tails merged into the pages between windows as the engine does."""
    from ray_tpu.ops.paged_attention import merge_tail_pages

    n = len(prompt)
    other = np.random.default_rng(99).integers(0, cfg.vocab_size, bucket)
    toks = np.zeros((2, bucket), np.int32)
    toks[0], toks[1, :n] = other, prompt
    true_lens = jnp.asarray([bucket, n], jnp.int32)
    h, ks, vs, state, _ = seam.serve_prefill(params, jnp.asarray(toks),
                                             true_lens)
    out = [seam.project_logits(params, h[1, n - 1])]
    maxp = 4
    cache = seam.init_paged_cache(cfg, 2, 1 + 2 * maxp, page)
    table = np.arange(1, 1 + 2 * maxp, dtype=np.int32).reshape(2, maxp)
    cols = np.arange(bucket) // page
    cache = seam.serve_scatter(
        cache, ks, vs, state, jnp.asarray(table[:, cols]),
        jnp.tile(jnp.arange(bucket) % page, (2, 1)), jnp.arange(2),
        true_lens)
    table = jnp.asarray(table)
    follow = list(follow)
    for w0 in range(0, len(follow), k):
        ts = cache["pos"]
        # the pool by the leaves the model gave it, as the engine's
        # decode program takes it (serve/llm.py `_pool`)
        pages = {name: leaves for name, leaves in cache.items()
                 if name not in ("pos", "state")}
        # a leaf's rows cover `page // rows` positions each (1 but for
        # a pooled index key): its tail holds what k positions complete
        tails = jax.tree.map(
            lambda p: jnp.zeros((2, p.shape[1],
                                 -(-k // (page // p.shape[2])), p.shape[3]),
                                p.dtype), pages)
        st, pos = cache["state"], ts
        for j, t in enumerate(follow[w0:w0 + k]):
            lg, tails, st, _ = seam.decode_step(
                params, pages, tails, st, jnp.asarray([1, t], jnp.int32),
                pos, ts, j, table)
            out.append(lg[1])
            pos = pos + 1
        cache = {**jax.tree.map(
            lambda p, t: merge_tail_pages(p, t, table, ts, k,
                                          page // p.shape[2]),
            pages, tails),
            "pos": ts + k, "state": st}
    return jnp.stack(out)
