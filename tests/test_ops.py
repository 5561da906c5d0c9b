"""Kernel correctness: flash attention (Pallas, interpret mode on CPU) and
ring attention (8-device virtual mesh) against the XLA reference
implementation.  Mirrors the reference's fake-backend testing trick
(ray: MockNcclGroup, python/ray/experimental/channel/conftest.py:58)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import attention, xla_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.ring import ring_attention_gspmd


def _qkv(b=2, s=256, hq=4, hkv=2, d=128, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hq, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, s, hkv, d), dtype)
    return q, k, v


class TestFlashAttention:
    def test_forward_matches_xla(self):
        q, k, v = _qkv()
        o = flash_attention(q, k, v, causal=True)
        o_ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(o, o_ref, atol=2e-2, rtol=1e-2)

    def test_backward_matches_xla(self):
        q, k, v = _qkv()
        d = q.shape[-1]

        def loss(att):
            def f(q, k, v):
                return (att(q, k, v) * jnp.arange(d)).sum()
            return f

        g = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            rel = jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)
            assert rel < 5e-3, f"grad rel err {rel}"

    def test_mqa_single_kv_head(self):
        q, k, v = _qkv(hq=4, hkv=1)
        o = flash_attention(q, k, v, causal=True)
        o_ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(o, o_ref, atol=2e-2, rtol=1e-2)

    @pytest.mark.parametrize("axes,hq,hkv", [
        (dict(data=2, fsdp=2, tensor=2), 4, 2),    # q and k/v heads split
        (dict(data=2, fsdp=2, tensor=2), 4, 1),    # MQA: q split, k/v whole
        (dict(data=2, tensor=4), 8, 2),            # kv heads don't divide:
        (dict(data=2, fsdp=2, tensor=2), 12, 3),   #   heads whole on all three
    ], ids=["gqa-split", "mqa-kv-whole", "gqa-8q2kv-tensor4",
            "gqa-12q3kv-tensor2"])
    def test_flash_runs_per_shard_under_a_mesh(self, axes, hq, hkv):
        """Under an ambient multi-device mesh the dispatcher makes the
        kernel call per shard (GSPMD cannot partition a Mosaic kernel):
        batch over data x fsdp, heads over tensor only where every GQA
        group stays on one shard (parallel/sharding.attention_shard_specs).
        Values and gradients match the unsharded XLA reference."""
        from ray_tpu.parallel.mesh import MeshConfig, create_mesh

        mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:8])
        q, k, v = _qkv(b=4, s=128, hq=hq, hkv=hkv)

        def loss(att):
            return lambda q, k, v: (att(q, k, v) ** 2).sum()

        flash = lambda q, k, v: attention(q, k, v, impl="flash")  # noqa: E731
        with jax.set_mesh(mesh):
            txt = jax.jit(flash).lower(q, k, v).as_text()
            o = jax.jit(flash)(q, k, v)
            g = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        assert "shard_map" in txt or "manual" in txt.lower()
        np.testing.assert_allclose(o, xla_attention(q, k, v), atol=2e-2,
                                   rtol=1e-2)
        g_ref = jax.grad(loss(xla_attention), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            rel = jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9)
            assert rel < 5e-3, f"grad rel err {rel}"

    @pytest.mark.parametrize("axes,hq,hkv,b,want", [
        (dict(fsdp=2, tensor=2), 32, 8, 4,
         (("data", "fsdp"), "tensor", "tensor")),          # llama3-8b
        (dict(tensor=8), 16, 4, 4,
         (("data", "fsdp"), None, None)),                  # bench-350m
        (dict(tensor=8), 64, 8, 4, (("data", "fsdp"), "tensor", "tensor")),
        (dict(data=2, tensor=4), 8, 1, 4, (("data", "fsdp"), "tensor", None)),
        (dict(data=2, tensor=4), 6, 1, 4, (("data", "fsdp"), None, None)),
        (dict(data=4, tensor=2), 4, 2, 2, (None, "tensor", "tensor")),
    ], ids=["split", "kv-indivisible", "kv-divides", "mqa", "q-indivisible",
            "batch-indivisible"])
    def test_attention_shard_specs_decide_heads_once(self, axes, hq, hkv, b,
                                                     want):
        """q heads split over the tensor axis only when k/v heads split
        with them (or there is one kv head); otherwise all three stay
        whole.  Returned as (batch entry, q heads entry, kv heads entry)."""
        from ray_tpu.parallel.mesh import MeshConfig, create_mesh
        from ray_tpu.parallel.sharding import attention_shard_specs

        mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:8])
        _, auto, q_spec, kv_spec = attention_shard_specs(
            (b, 128, hq, 128), (b, 128, hkv, 128), mesh=mesh)
        assert auto == set(mesh.axis_names)
        assert q_spec[0] == kv_spec[0]
        assert (q_spec[0], q_spec[2], kv_spec[2]) == want
        one = create_mesh(MeshConfig(), devices=jax.devices()[:1])
        assert attention_shard_specs((b, 128, hq, 128), (b, 128, hkv, 128),
                                     mesh=one) is None

    def test_dispatcher_fallback_short_seq(self):
        # s=64 not a multiple of 128 → XLA path; just must run + match.
        q, k, v = _qkv(s=64, d=64)
        o = attention(q, k, v, causal=True)
        o_ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(o, o_ref, atol=1e-5)

    def test_nondividing_seq_halves_blocks(self):
        # s=640: the 512/1024 defaults don't divide it — the dispatcher
        # must halve to 128 and still cover every query row (the old code
        # floor-divided the grid and silently dropped the tail).
        q, k, v = _qkv(s=640)
        o = flash_attention(q, k, v, causal=True)
        o_ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(o, o_ref, atol=2e-2, rtol=1e-2)

    def test_remat_policy_saves_flash_residuals(self):
        """jax.checkpoint with the model's remat policy over the flash
        path: grads must match the uncheckpointed ones (i.e. the saved
        'flash_o'/'flash_lse' names line up between the kernel and the
        policy — renaming either side alone breaks this)."""
        from ray_tpu.models.llama import remat_policy

        q, k, v = _qkv()
        d = q.shape[-1]

        def f(q, k, v):
            return (flash_attention(q, k, v, causal=True)
                    * jnp.arange(d)).sum()

        f_remat = jax.checkpoint(f, policy=remat_policy())
        g = jax.grad(f_remat, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(a, b, atol=1e-5)
        # The policy must actually shortcut the fwd-kernel re-run: the
        # remat backward must contain STRICTLY fewer pallas calls (fwd +
        # dq + dkv = 3) than a nothing-saveable backward (those + the
        # fwd re-run = 4).  Renaming 'flash_o'/'flash_lse' on either
        # side alone silently reverts to the recompute and fails here.
        txt_flash = jax.make_jaxpr(
            jax.grad(f_remat, argnums=(0, 1, 2)))(q, k, v).pretty_print()
        f_nothing = jax.checkpoint(
            f, policy=jax.checkpoint_policies.nothing_saveable)
        txt_nothing = jax.make_jaxpr(
            jax.grad(f_nothing, argnums=(0, 1, 2)))(q, k, v).pretty_print()
        n_flash = txt_flash.count("pallas_call")
        n_nothing = txt_nothing.count("pallas_call")
        assert 0 < n_flash < n_nothing, (n_flash, n_nothing)

    # (products a layer's backward recomputes, Pallas calls in the whole
    # step): `nothing` re-runs the forward kernel and recomputes six
    # products a layer (q, k, v, wo, gate, up; w_down's is dead);
    # `flash_resid` keeps the kernel's (o, lse) and the block's output,
    # which takes the forward kernel and wo's product away.
    @pytest.mark.parametrize("mode,recomputed_products,pallas_calls", [
        ("nothing", 6, 4), ("flash_resid", 5, 3), ("dots", 0, 4),
        ("flash_dots", 0, 3)])
    def test_remat_modes_match_the_unremat_step(self, monkeypatch, mode,
                                                recomputed_products,
                                                pallas_calls):
        """A two-layer llama step through the interpreted kernel under
        every `remat_mode`: loss and every gradient leaf are the
        un-checkpointed step's, and the backward holds the products and
        kernel calls the mode's kept set leaves (the scanned layer is
        ONE body in the jaxpr, so a difference is per layer)."""
        import dataclasses

        from ray_tpu.models import llama

        monkeypatch.setattr(llama, "attention",
                            functools.partial(attention, impl="flash"))
        base = llama.LlamaConfig(
            dim=256, n_layers=2, n_heads=2, n_kv_heads=1, ffn_dim=512,
            vocab_size=256, max_seq=128, dtype=jnp.float32, remat=False)
        params = llama.init_params(jax.random.PRNGKey(0), base)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (2, 129), 0, base.vocab_size)}

        def step(**kw):
            cfg = dataclasses.replace(base, **kw)
            return jax.value_and_grad(
                lambda p: llama.loss_fn(p, batch, cfg))

        remat = step(remat=True, remat_mode=mode)
        loss, grads = jax.jit(remat)(params)
        loss_ref, grads_ref = jax.jit(step())(params)
        np.testing.assert_allclose(loss, loss_ref, atol=1e-5)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)):
            np.testing.assert_allclose(a, b, atol=1e-5)
        names = [e.primitive.name
                 for j in _jaxprs_in(jax.make_jaxpr(remat)(params).jaxpr,
                                     skip=("pallas_call",))
                 for e in j.eqns]
        # un-checkpointed: 7 forward + 14 backward a layer, 3 at the head
        assert names.count("dot_general") == 24 + recomputed_products
        assert names.count("pallas_call") == pallas_calls


def _jaxprs_in(jaxpr, skip=()):
    """jaxpr and every jaxpr nested in its equations' params (but not in
    those of the primitives named in `skip`)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in skip:
            continue
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _jaxprs_in(sub, skip)


def _flash_fwd_calls(fn, *args):
    return [e for j in _jaxprs_in(jax.make_jaxpr(fn)(*args).jaxpr)
            for e in j.eqns if e.primitive.name == "pallas_call"
            and e.params["name"] == "flash_fwd"]


# a length by where it falls around a query block's edge
_LENGTHS = {"one": lambda bq, s: 1, "under": lambda bq, s: bq - 1,
            "on": lambda bq, s: bq, "over": lambda bq, s: bq + 1,
            "full": lambda bq, s: s}


class TestFlashLengths:
    """`lengths`: the true lengths of right-padded rows.  The forward
    kernel walks only the (row, query block, key block) triples that are
    under the diagonal and inside a row's length."""

    @pytest.mark.parametrize("lens", [
        ("one",), ("under",), ("on",), ("over",), ("full",),
        ("one", "on", "over"), ("under", "full", "over")],
        ids="-".join)
    @pytest.mark.parametrize("n_rep", [1, 4], ids=["mha", "gqa4"])
    @pytest.mark.parametrize("width", ["128", "64-padded", "192-128"])
    def test_true_rows_stay_and_padded_blocks_are_zero(self, width, n_rep,
                                                       lens):
        b, hq = len(lens), 4
        d, dv = {"128": (128, 128), "64-padded": (64, 64),
                 "192-128": (192, 128)}[width]
        if width == "64-padded":
            # through the dispatcher, which pads to 128 lanes and takes
            # the default blocks: 512 / 512 at 1536 rows
            s, bq = 1536, 512
            run = functools.partial(attention, impl="flash")
        else:
            s, bq = 512, 128
            run = functools.partial(flash_attention, block_q=bq,
                                    block_k=256)
        key = jax.random.PRNGKey(3)
        q = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hq, d))
        k = jax.random.normal(jax.random.fold_in(key, 2),
                              (b, s, hq // n_rep, d))
        v = jax.random.normal(jax.random.fold_in(key, 3),
                              (b, s, hq // n_rep, dv))
        lengths = np.array([_LENGTHS[n](bq, s) for n in lens], np.int32)
        o = np.asarray(run(q, k, v, lengths=jnp.asarray(lengths)))
        whole = np.asarray(run(q, k, v))
        ref = np.asarray(xla_attention(q, k, v))
        assert o.shape == (b, s, hq, dv) and np.isfinite(o).all()
        for r, n in enumerate(lengths):
            # the scale is where it was: the same bytes as without lengths
            np.testing.assert_array_equal(o[r, :n], whole[r, :n])
            np.testing.assert_allclose(o[r, :n], ref[r, :n], atol=2e-2,
                                       rtol=1e-2)
            assert not o[r, -(-n // bq) * bq:].any()

    def test_lengths_split_with_the_rows_under_a_mesh(self):
        from ray_tpu.parallel.mesh import MeshConfig, create_mesh

        mesh = create_mesh(MeshConfig(data=2, fsdp=2, tensor=2),
                           devices=jax.devices()[:8])
        q, k, v = _qkv(b=4, s=1024, hq=4, hkv=2)
        lengths = jnp.array([1024, 1, 513, 512], jnp.int32)
        with jax.set_mesh(mesh):
            o = jax.jit(lambda *a: attention(*a[:3], impl="flash",
                                             lengths=a[3]))(q, k, v, lengths)
        ref = xla_attention(q, k, v)
        for r, n in enumerate(np.asarray(lengths)):
            np.testing.assert_allclose(o[r, :n], ref[r, :n], atol=2e-2,
                                       rtol=1e-2)
        assert not np.asarray(o[1, 512:]).any()     # blocks of 512 rows

    def test_a_differentiated_call_with_lengths_raises(self):
        q, k, v = _qkv(s=128)
        lengths = jnp.array([128, 5], jnp.int32)
        with pytest.raises(TypeError, match="no lengths"):
            jax.grad(lambda q: flash_attention(
                q, k, v, lengths=lengths).sum())(q)
        with pytest.raises(ValueError, match="causal"):
            flash_attention(q, k, v, causal=False, lengths=lengths)

    def test_lse_is_an_output_only_of_the_call_that_keeps_it(self):
        """The forward-only kernel writes o alone; the vjp's forward
        writes the log-sum-exp the backward kernels read beside it.
        Both are named `flash_fwd`."""
        q, k, v = _qkv(s=128)
        lengths = jnp.array([128, 5], jnp.int32)
        for fn, args in [
                (flash_attention, (q, k, v)),
                (lambda q, k, v, n: flash_attention(q, k, v, lengths=n),
                 (q, k, v, lengths)),
                (flash_attention, (q, k, v[..., :64]))]:
            (call,) = _flash_fwd_calls(fn, *args)
            assert len(call.outvars) == 1
        (call,) = _flash_fwd_calls(
            lambda *a: jax.vjp(flash_attention, *a)[0], q, k, v)
        assert len(call.outvars) == 2
        assert call.outvars[1].aval.dtype == jnp.float32


class TestRingAttention:
    @pytest.fixture
    def mesh(self):
        devs = np.array(jax.devices()[:8]).reshape(2, 4)
        return Mesh(devs, ("data", "seq"))

    def test_matches_full_attention(self, mesh):
        q, k, v = _qkv(s=512, d=64)
        sh = NamedSharding(mesh, P("data", "seq", None, None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        with jax.set_mesh(mesh):
            o = jax.jit(ring_attention_gspmd)(qs, ks, vs)
        o_ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_grad_matches(self, mesh):
        q, k, v = _qkv(s=256, d=64)
        d = q.shape[-1]
        sh = NamedSharding(mesh, P("data", "seq", None, None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

        with jax.set_mesh(mesh):
            g = jax.jit(jax.grad(
                lambda q, k, v: (ring_attention_gspmd(q, k, v)
                                 * jnp.arange(d)).sum(),
                argnums=(0, 1, 2)))(qs, ks, vs)
        g_ref = jax.grad(
            lambda q, k, v: (xla_attention(q, k, v) * jnp.arange(d)).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            rel = jnp.abs(np.asarray(a) - np.asarray(b)).max() / \
                (jnp.abs(b).max() + 1e-9)
            assert rel < 1e-4, f"ring grad rel err {rel}"

    def test_noncausal(self, mesh):
        q, k, v = _qkv(s=256, d=64)
        sh = NamedSharding(mesh, P("data", "seq", None, None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        with jax.set_mesh(mesh):
            o = jax.jit(lambda q, k, v: ring_attention_gspmd(
                q, k, v, causal=False))(qs, ks, vs)
        o_ref = xla_attention(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=1e-4, rtol=1e-4)
