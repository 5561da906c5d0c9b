"""Plain reference of the Mistral-family decoder, float32 `jax.numpy`.

Follows the published description (Mistral 7B, arXiv:2310.06825, and the
`modeling_mistral.py` of transformers): pre-norm residual blocks, RMSNorm,
rotary position embedding on q and k (theta from the config, the
"rotate-half" pairing of dimension i with i + head_dim/2), grouped-query
attention with a causal mask, SwiGLU feed-forward, untied output head, no
biases.  No kernels, no cache, no batching tricks; one layer at a time.

Departures from the published model, each forced or harmless:
- the sliding window is left out: Mistral-7B-v0.3 and Codestral-22B-v0.1
  publish `sliding_window: null`;
- parameters arrive in the layout of the program under test (`embed`
  [V, d], per-layer matrices stacked on a leading axis, input-major, so
  y = x @ W) and in the dtype it serves them in; they are cast to float32
  here, layer by layer, so the reference never holds a second copy of the
  model;
- matmuls run under `default_matmul_precision("highest")`: on a TPU a
  float32 matmul is otherwise done in bfloat16 passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x [s, heads, hd]; position p rotates the pair (i, i + hd/2) by
    p * theta**(-2i/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]     # [s, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, lp, *, n_heads: int, n_kv_heads: int, theta: float,
          eps: float):
    """One decoder layer on x [s, d] (float32); lp = that layer's slices."""
    s, d = x.shape
    hd = d // n_heads
    h = _rmsnorm(x, lp["attn_norm"], eps)
    q = (h @ lp["wq"].astype(F32)).reshape(s, n_heads, hd)
    k = (h @ lp["wk"].astype(F32)).reshape(s, n_kv_heads, hd)
    v = (h @ lp["wv"].astype(F32)).reshape(s, n_kv_heads, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    group = n_heads // n_kv_heads          # query heads per kv head
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, n_heads * hd)
    x = x + o @ lp["wo"].astype(F32)
    h = _rmsnorm(x, lp["mlp_norm"], eps)
    gate = jax.nn.silu(h @ lp["w_gate"].astype(F32))
    return x + (gate * (h @ lp["w_up"].astype(F32))) @ lp["w_down"].astype(F32)


def head(x, final_norm, lm_head, eps):
    return _rmsnorm(x, final_norm, eps) @ lm_head.astype(F32)


def _sizes(model: dict) -> dict:
    return dict(n_heads=model["num_attention_heads"],
                n_kv_heads=model["num_key_value_heads"],
                theta=float(model["rope_theta"]),
                eps=float(model["rms_norm_eps"]))


def _layer_slice(layers: dict, i: int) -> dict:
    return jax.tree.map(lambda a: a[i], layers)


def logits(params: dict, tokens, model: dict):
    """tokens [s] int -> logits [s, vocab] float32, one jitted layer
    called once per layer (bounded memory, one compile)."""
    kw = _sizes(model)
    with jax.default_matmul_precision("highest"):
        layer_fn = jax.jit(lambda x, lp: layer(x, lp, **kw))
        head_fn = jax.jit(lambda x, n, w: head(x, n, w, kw["eps"]))
        x = params["embed"][jnp.asarray(tokens)].astype(F32)
        n_layers = params["layers"]["wq"].shape[0]
        for i in range(n_layers):
            x = layer_fn(x, _layer_slice(params["layers"], i))
        return head_fn(x, params["final_norm"], params["lm_head"])


def cross_entropy(lg, targets):
    """Mean next-token cross entropy of logits [s, V] against targets [s]."""
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, jnp.asarray(targets)[:, None], axis=-1))


def teacher_forced_gaps(params: dict, prompt: list[int], served: list[int],
                        model: dict) -> list[float]:
    """For each served token: the reference's largest logit at that
    position minus the reference's logit OF the served token (0 when the
    reference would have chosen it too), given the prompt and the served
    tokens before it."""
    seq = list(prompt) + list(served[:-1])
    lg = logits(params, seq, model)[len(prompt) - 1:]
    top = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, jnp.asarray(served)[:, None], -1)[:, 0]
    return [float(g) for g in (top - got)]


def _square_sum(tree) -> jax.Array:
    return sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(tree))


def loss_and_gradient(params: dict, inputs, targets, model: dict) -> dict:
    """One training example batch scored the plain way: inputs, targets
    [b, s] int -> {"loss": mean next-token cross entropy over all b*s
    positions, "logprobs": [b, s] log-probability of each target,
    "grad_norm": global 2-norm of d loss / d parameters}, all float32.

    The backward pass is written out: the layers' inputs are kept, the
    head is differentiated, then each layer's vector-Jacobian product is
    taken from the last layer to the first and the embedding rows gather
    what is left.  Only one layer's float32 weights and gradients exist
    at a time, so a model that fills the chip in bfloat16 still fits."""
    kw = _sizes(model)
    inputs, targets = jnp.asarray(inputs), jnp.asarray(targets)

    def layers_fn(x, lp):                       # x [b, s, d]
        return jax.vmap(lambda xi: layer(xi, lp, **kw))(x)

    def head_loss(x, norm, w):
        lg = jax.vmap(lambda xi: head(xi, norm, w, kw["eps"]))(x)
        logp = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                                   targets[..., None], axis=-1)[..., 0]
        return -jnp.mean(logp), logp

    def f32(tree):
        return jax.tree.map(lambda a: a.astype(F32), tree)

    @jax.jit
    def layer_backward(x, lp, g):
        _, vjp = jax.vjp(layers_fn, x, f32(lp))
        gx, glp = vjp(g)
        return gx, _square_sum(glp)

    @jax.jit
    def embed_backward(g):
        rows = jnp.zeros(params["embed"].shape, F32)
        return _square_sum(rows.at[inputs].add(g))

    with jax.default_matmul_precision("highest"):
        forward = jax.jit(layers_fn)
        xs = [params["embed"][inputs].astype(F32)]
        n_layers = params["layers"]["wq"].shape[0]
        for i in range(n_layers):
            xs.append(forward(xs[-1], _layer_slice(params["layers"], i)))
        (loss, logp), grads = jax.jit(jax.value_and_grad(
            head_loss, argnums=(0, 1, 2), has_aux=True))(
                xs.pop(), f32(params["final_norm"]), f32(params["lm_head"]))
        g, squares = grads[0], _square_sum(grads[1:])
        for i in reversed(range(n_layers)):
            g, sq = layer_backward(xs.pop(),
                                   _layer_slice(params["layers"], i), g)
            squares = squares + sq
        squares = squares + embed_backward(g)
    return {"loss": float(loss), "logprobs": logp,
            "grad_norm": float(jnp.sqrt(squares))}
