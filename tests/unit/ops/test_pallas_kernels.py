"""Pallas kernels vs XLA reference implementations (interpret mode on CPU).

Mirrors the reference's per-kernel unit tests (``tests/unit/ops/transformer``,
``tests/unit/ops/quantizer``): numerical parity of the hand-written kernel
against the plain composed implementation, forward and backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu.ops as ops
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas import register_all

register_all()


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("S", [16, 100])
    @pytest.mark.parametrize("gqa", [False, True])
    def test_forward_matches_xla(self, S, gqa):
        B, H, D = 2, 4, 8
        Hkv = 2 if gqa else H
        q = _rand(0, (B, S, H, D))
        k = _rand(1, (B, S, Hkv, D))
        v = _rand(2, (B, S, Hkv, D))
        ref = ops.causal_attention(q, k, v, impl="xla")
        out = ops.dispatch("causal_attention", "pallas")(q, k, v, block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_padding_mask(self):
        B, S, H, D = 2, 24, 2, 8
        q, k, v = _rand(0, (B, S, H, D)), _rand(1, (B, S, H, D)), _rand(2, (B, S, H, D))
        mask = jnp.asarray(np.random.default_rng(0).integers(0, 2, (B, S)), jnp.int32).at[:, 0].set(1)
        ref = ops.causal_attention(q, k, v, mask=mask, impl="xla")
        out = ops.dispatch("causal_attention", "pallas")(q, k, v, mask=mask, block_q=8, block_k=8)
        # compare only rows whose own position is kept (masked-out query rows
        # are don't-care: xla fills them from masked softmax, pallas zeros)
        keep = np.asarray(mask, bool)
        np.testing.assert_allclose(np.asarray(out)[keep], np.asarray(ref)[keep], atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("scale", [None, 0.71])  # a softmax scale of the caller's own (YaRN's latent attention)
    @pytest.mark.parametrize("gqa", [False, True])
    def test_grads_match_xla(self, gqa, scale):
        B, S, H, D = 2, 32, 4, 8
        Hkv = 2 if gqa else H
        q = _rand(0, (B, S, H, D))
        k = _rand(1, (B, S, Hkv, D))
        v = _rand(2, (B, S, Hkv, D))

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))

            return f

        ref_fn = loss(lambda q, k, v: ops.causal_attention(q, k, v, impl="xla", softmax_scale=scale))
        pl_fn = loss(lambda q, k, v: ops.causal_attention(q, k, v, impl="pallas", softmax_scale=scale,
                                                          block_q=16, block_k=16))
        if scale is not None:  # it is the scores' scale: D^-0.5 said outright changes nothing, another value does
            np.testing.assert_allclose(ops.causal_attention(q, k, v, impl="xla", softmax_scale=D ** -0.5),
                                       ops.causal_attention(q, k, v, impl="xla"), atol=1e-6)
            assert float(jnp.abs(ref_fn(q, k, v) - loss(lambda q, k, v: ops.causal_attention(q, k, v, impl="xla"))(
                q, k, v))) > 1e-3
            np.testing.assert_allclose(pl_fn(q, k, v), ref_fn(q, k, v), rtol=2e-5)
        ref_grads = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
        pl_grads = jax.grad(pl_fn, argnums=(0, 1, 2))(q, k, v)
        for rg, pg in zip(ref_grads, pl_grads):
            np.testing.assert_allclose(np.asarray(pg), np.asarray(rg), atol=5e-5, rtol=5e-5)

    @pytest.mark.parametrize("bq,bk", [(8, 8), (16, 8)])  # squashed + dense grids
    def test_masked_grads_match_xla(self, bq, bk):
        """Backward with a padding mask (the masked branches of both bwd
        kernels). The loss reads only kept-query outputs so masked rows are
        genuinely don't-care and gradients must match everywhere."""
        B, S, H, D = 2, 24, 2, 8
        q, k, v = _rand(0, (B, S, H, D)), _rand(1, (B, S, H, D)), _rand(2, (B, S, H, D))
        mask = jnp.asarray(np.random.default_rng(1).integers(0, 2, (B, S)), jnp.int32).at[:, 0].set(1)
        keep = mask.astype(jnp.float32)[:, :, None, None]

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(keep * out * jnp.cos(out.astype(jnp.float32)))
            return f

        ref_fn = loss(lambda q, k, v: ops.causal_attention(q, k, v, mask=mask, impl="xla"))
        pl_fn = loss(lambda q, k, v: ops.dispatch("causal_attention", "pallas")(
            q, k, v, mask=mask, block_q=bq, block_k=bk))
        ref_grads = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
        pl_grads = jax.grad(pl_fn, argnums=(0, 1, 2))(q, k, v)
        for rg, pg in zip(ref_grads, pl_grads):
            np.testing.assert_allclose(np.asarray(pg), np.asarray(rg), atol=5e-5, rtol=5e-5)

    def test_unequal_blocks_dense_grid(self):
        """block_q != block_k routes through the dense (non-squashed) causal
        grid — keep that branch covered: fwd + all three gradients."""
        B, S, H, D = 2, 32, 2, 8
        q, k, v = _rand(0, (B, S, H, D)), _rand(1, (B, S, H, D)), _rand(2, (B, S, H, D))

        def f(fn):
            def g(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))
            return g

        pallas = ops.dispatch("causal_attention", "pallas")
        ref = ops.causal_attention(q, k, v, impl="xla")
        out = pallas(q, k, v, block_q=16, block_k=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
        ref_grads = jax.grad(f(lambda q, k, v: ops.causal_attention(q, k, v, impl="xla")),
                             argnums=(0, 1, 2))(q, k, v)
        pl_grads = jax.grad(f(lambda q, k, v: pallas(q, k, v, block_q=16, block_k=8)),
                            argnums=(0, 1, 2))(q, k, v)
        for rg, pg in zip(ref_grads, pl_grads):
            np.testing.assert_allclose(np.asarray(pg), np.asarray(rg), atol=5e-5, rtol=5e-5)

    # (16, 16) squashed triangle grid; (16, 8) dense grid — both split branches
    @pytest.mark.parametrize("k_splits,bq,bk", [(2, 16, 16), (2, 16, 8), (4, 16, 16)])
    def test_k_splits_matches_unsplit(self, k_splits, bq, bk):
        """k_splits sub-chunked online softmax (MXU/VPU overlap restructuring)
        matches the unsplit kernel: fwd + all three gradients, with a padding
        mask so the masked sub-chunk slicing is exercised too."""
        B, S, H, D = 2, 32, 2, 8
        q, k, v = _rand(0, (B, S, H, D)), _rand(1, (B, S, H, D)), _rand(2, (B, S, H, D))
        mask = jnp.ones((B, S), jnp.int32).at[1, 20:].set(0)

        def f(fn):
            def g(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))
            return g

        pallas = ops.dispatch("causal_attention", "pallas")
        base = pallas(q, k, v, mask=mask, block_q=bq, block_k=bk)
        out = pallas(q, k, v, mask=mask, block_q=bq, block_k=bk, k_splits=k_splits)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=2e-6, rtol=2e-6)
        base_grads = jax.grad(f(lambda q, k, v: pallas(q, k, v, mask=mask, block_q=bq, block_k=bk)),
                              argnums=(0, 1, 2))(q, k, v)
        pl_grads = jax.grad(f(lambda q, k, v: pallas(q, k, v, mask=mask, block_q=bq,
                                                     block_k=bk, k_splits=k_splits)),
                            argnums=(0, 1, 2))(q, k, v)
        for rg, pg in zip(base_grads, pl_grads):
            np.testing.assert_allclose(np.asarray(pg), np.asarray(rg), atol=5e-6, rtol=5e-6)


    def test_kernel_kwargs_forwarded_to_pallas_and_dropped_on_xla(self):
        """The attn_kwargs plumbing (TransformerConfig -> causal_attention ->
        kernel): scheduling knobs must reach the pallas kernel (identical
        math, different blocking) and be silently DROPPED when dispatch
        resolves to the XLA path — an autotuned block config must never make
        the fallback path raise TypeError."""
        B, S, H, D = 2, 32, 2, 8
        q, k, v = _rand(0, (B, S, H, D)), _rand(1, (B, S, H, D)), _rand(2, (B, S, H, D))
        kw = dict(block_q=16, block_k=16, k_splits=2)
        ref = ops.causal_attention(q, k, v, impl="xla")
        # xla impl has no blocking params: kwargs must be dropped, not passed
        out_xla = ops.causal_attention(q, k, v, impl="xla", **kw)
        np.testing.assert_allclose(np.asarray(out_xla), np.asarray(ref), rtol=1e-6)
        # pallas impl must actually honor them (reject an impossible block)
        out_pl = ops.causal_attention(q, k, v, impl="pallas", **kw)
        np.testing.assert_allclose(np.asarray(out_pl), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        # model-level: TransformerConfig freezes the dict hashable for jit
        from deepspeed_tpu.models import TransformerConfig

        cfg = TransformerConfig(vocab_size=32, hidden_size=16,
                                intermediate_size=32, num_layers=1,
                                num_heads=2, max_seq_len=32, attn_kwargs=kw)
        assert cfg.attn_kwargs == tuple(sorted(kw.items()))
        assert hash(cfg.attn_kwargs) is not None


class TestFlashOnePassBackward:
    """While a head's dq fits VMEM, ONE kernel makes dq, dk and dv from one
    set of scores (``_one_pass_fits``: from S and D alone); past that the dq
    kernel and the dkv kernel each form the scores. The test reaches the pair
    at small shapes by shrinking the module's budget, never by an option."""

    CASES = {"plain": {}, "gqa": dict(Hkv=2), "mask": dict(masked=True),
             "alibi": dict(alibi=True), "gqa-mask-alibi": dict(Hkv=2, masked=True, alibi=True)}

    @staticmethod
    def _grads(q, k, v, **kw):
        def loss(q, k, v):
            out = fa.flash_causal_attention(q, k, v, **kw)
            return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("k_splits", [1, 2])
    @pytest.mark.parametrize("bq,bk", [(8, 8), (16, 8)])  # squashed + dense grids
    @pytest.mark.parametrize("case", list(CASES))
    def test_one_pass_gradients_are_the_two_pass_kernels(
            self, case, bq, bk, k_splits, monkeypatch):
        """Every block forms s, p, dp and ds by the same expressions in all
        three kernels, and a dq row block's terms arrive in the order ki = 0,
        1, ..., qi (sub-chunk by sub-chunk) under the wedge as under the
        triangle. So dq, dk and dv agree TO THE BIT wherever one compiler
        builds both sides alike: on the chip in bf16 at the cells' shapes
        (PERF.md, PR 32) and here at XLA's default level in all 20 cases. This
        harness compiles at ``--xla_backend_optimization_level=1`` (conftest),
        where one case of the 20 contracts a multiply-add in one kernel's
        fusion and not in the other's: dq off by one ulp (4.8e-7), which is
        the tolerance. An order of summation gone wrong costs far more."""
        from deepspeed_tpu.models.transformer import alibi_slopes
        c = self.CASES[case]
        B, S, H, D = 2, 40, 4, 8  # 40 pads to 48 under (16, 8)
        q, k, v = (_rand(i, (B, S, h, D)) for i, h in enumerate((H, c.get("Hkv", H), c.get("Hkv", H))))
        kw = dict(block_q=bq, block_k=bk, k_splits=k_splits)
        if c.get("masked"):
            kw["mask"] = jnp.asarray(
                np.random.default_rng(2).integers(0, 2, (B, S)), jnp.int32).at[:, 0].set(1)
        if c.get("alibi"):
            kw["alibi_slopes"] = alibi_slopes(H)
        one = self._grads(q, k, v, **kw)
        monkeypatch.setattr(fa, "_ONE_PASS_DQ_BYTES", 0)
        pair = self._grads(q, k, v, **kw)
        for name, a, b in zip(("dq", "dk", "dv"), one, pair):
            assert np.abs(np.asarray(b)).max() > 0, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6,
                                       err_msg=name)

    def test_one_pass_takes_a_block_of_512_whole(self, monkeypatch):
        """Since the dkv kernel forms its scores transposed the one pass takes
        a key block whole (in chunks of 256 columns it lost 5% on the chip,
        where the old form gained as much and chunked by itself); ``k_splits``
        is still the caller's, and asks the pair for the same chunks."""
        q, k, v, do = (_rand(i, (1, 1, 512, 8)) for i in range(4))
        mask, slopes = jnp.ones((1, 1, 512), jnp.int32), jnp.zeros((1, 1, fa._LANES))
        out, lse = fa._flash_fwd(q, k, v, mask, slopes, 512, 512, True, False, False)
        bwd = lambda k_splits: fa._flash_bwd(  # noqa: E731
            q, k, v, mask, slopes, out, lse, do, 512, 512, True, False, False, k_splits)
        # five products a block, in the diagonal's and the plain block's code; a chunk each when asked
        assert str(jax.make_jaxpr(lambda: bwd(1))()).count("dot_general") == 5 * 2
        assert str(jax.make_jaxpr(lambda: bwd(2))()).count("dot_general") == 5 * 2 * 2
        one = bwd(2)
        monkeypatch.setattr(fa, "_ONE_PASS_DQ_BYTES", 0)
        for a, b in zip(one, bwd(2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6)

    def test_bf16_one_pass_matches_the_pair_and_xla(self, monkeypatch):
        B, S, H, D = 1, 64, 2, 16
        q, k, v = (_rand(i, (B, S, H, D), jnp.bfloat16) for i in range(3))
        one = self._grads(q, k, v, block_q=16, block_k=16)
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        ref = jax.grad(lambda q, k, v: jnp.sum((o := ops.causal_attention(q, k, v, impl="xla")) * jnp.cos(o)),
                       argnums=(0, 1, 2))(f32(q), f32(k), f32(v))
        monkeypatch.setattr(fa, "_ONE_PASS_DQ_BYTES", 0)
        pair = self._grads(q, k, v, block_q=16, block_k=16)
        for a, b, r in zip(one, pair, ref):
            np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(f32(b)), rtol=2e-2, atol=2e-2)
            np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(r), atol=0.06, rtol=0.06)

    @pytest.mark.parametrize("S,D,fits", [
        (2048, 64, True), (2048, 128, True),    # the two train cells
        (8192, 128, True), (8192, 64, True),    # head_dim 64 pads to 128 lanes: the same slab
        (8704, 128, False), (4096, 256, True), (4608, 256, False),
    ])
    def test_the_rule_reads_the_shapes_alone(self, S, D, fits):
        """Both sides of the rule, in the lowered program: under the budget
        ``flash_bwd_dkv`` and no ``flash_bwd_dq``; over it, both."""
        import re

        assert fa._one_pass_fits(S, D) is fits
        x = jax.ShapeDtypeStruct((1, S, 1, D), jnp.bfloat16)
        text = jax.jit(jax.grad(lambda q: fa.flash_causal_attention(q, q, q).astype(jnp.float32).sum())
                       ).lower(x).as_text(debug_info=True)
        has = lambda name: bool(re.search(r"[/(]%s[/)]" % name, text))  # noqa: E731
        assert has("flash_fwd") and has("flash_bwd_dkv")
        assert has("flash_bwd_dq") is (not fits)


class TestFlashRowStatistics:
    """lse and delta cross the kernels' boundary as ``[B, H, 1, S]`` rows, the
    sequence on the lanes (a ``[B, H, S, 8]`` column is stored 128 lanes wide
    on the chip, and the train scan stacks it). The dkv kernel forms its
    scores transposed to read them so; the dq kernel turns a query block's
    rows into columns once. At the cells' blocks of 512, in interpret mode."""

    CASES = {
        "d64-causal": dict(D=64),
        "d128-causal": dict(D=128),
        "d64-mask": dict(D=64, masked=True),
        "d128-alibi": dict(D=128, alibi=True),
        "d64-gqa": dict(D=64, H=4, Hkv=2),
        # four heads' steepest slope is 1/4: at 2,048 keys the bias itself rounds by more than the tolerance
        "d128-gqa-mask-alibi": dict(D=128, S=1024, H=4, Hkv=2, masked=True, alibi=True),
        # past _one_pass_fits: flash_bwd_dq turns the rows into columns, flash_bwd_dkv makes dk and dv
        "d128-two-pass": dict(D=128, S=8704, H=1),
        "d64-two-pass-mask-alibi": dict(D=64, two_pass=True, masked=True, alibi=True),
    }

    @pytest.mark.parametrize("case", list(CASES))
    def test_lse_rows_and_gradients_match_the_reference(self, case, monkeypatch):
        from deepspeed_tpu.models.transformer import alibi_slopes

        c = self.CASES[case]
        B, S, D, H = 1, c.get("S", 2048), c["D"], c.get("H", 2)
        Hkv = c.get("Hkv", H)
        q, k, v = (_rand(i, (B, S, h, D)) for i, h in enumerate((H, Hkv, Hkv)))
        mask = (jnp.asarray(np.random.default_rng(4).integers(0, 2, (B, S)), jnp.int32).at[:, 0].set(1)
                if c.get("masked") else None)
        slopes = alibi_slopes(H) if c.get("alibi") else None
        if c.get("two_pass"):  # the pair at S = 2,048, by the module's budget and no option
            monkeypatch.setattr(fa, "_ONE_PASS_DQ_BYTES", 0)
        assert fa._one_pass_fits(S, D) is not (c.get("two_pass") or S > 8192)

        # the forward's statistic, as the kernel hands it over (base 2, [B, H, 1, S])
        scale = D ** -0.5
        heads = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
        keep3 = (jnp.ones((B, S), jnp.int32) if mask is None else mask)[:, None, :]
        slopes2 = jnp.broadcast_to(((jnp.zeros((H,)) if slopes is None else slopes) * fa._LOG2E)[:, None, None],
                                   (H, 1, fa._LANES)).astype(jnp.float32)
        _, lse = fa._flash_fwd(heads(q) * (scale * fa._LOG2E), heads(k), heads(v), keep3, slopes2,
                               512, 512, True, mask is not None, slopes is not None)
        assert lse.shape == (B, H, 1, S) and lse.dtype == jnp.float32
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Hkv, axis=2),
                            precision="highest") * scale
        if slopes is not None:
            scores = scores + slopes[None, :, None, None] * jnp.arange(S, dtype=jnp.float32)
        visible = jnp.tril(jnp.ones((S, S), bool))[None, None]
        if mask is not None:
            visible = visible & (mask[:, None, None, :] > 0)
        ref_lse = jax.nn.logsumexp(jnp.where(visible, scores, -jnp.inf), axis=-1)
        np.testing.assert_allclose(np.asarray(lse[:, :, 0] * fa._LN2), np.asarray(ref_lse),
                                   atol=2e-5, rtol=2e-5)

        keepq = 1.0 if mask is None else mask.astype(jnp.float32)[:, :, None, None]

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(keepq * out * jnp.cos(out))
            return f

        ref = jax.grad(loss(lambda q, k, v: ops.causal_attention(
            q, k, v, mask=mask, alibi_slopes=slopes, impl="xla")), argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(loss(lambda q, k, v: fa.flash_causal_attention(
            q, k, v, mask=mask, alibi_slopes=slopes)), argnums=(0, 1, 2))(q, k, v)
        for name, r, g in zip(("dq", "dk", "dv"), ref, got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5, rtol=5e-5, err_msg=name)

    @pytest.mark.parametrize("Hkv,two_pass", [(4, False), (2, False), (4, True)], ids=["heads", "grouped", "two-pass"])
    def test_gradients_leave_the_kernels_in_their_final_form(self, Hkv, two_pass, monkeypatch):
        """dq times the softmax scale and dk times ln2 are applied in fp32
        inside the kernels and rounded once there: no fp32 ``[B, H, S, D]``
        gradient crosses HBM to be scaled, transposed and cast by XLA. With
        grouped heads dk and dv stay fp32 per query head until the sum over
        the group. The results agree with the float32 kernels' at bf16's tolerance."""
        B, S, H, D = 1, 64, 4, 16
        q, k, v, do = (_rand(i, (B, h, S, D), jnp.bfloat16) for i, h in enumerate((H, Hkv, Hkv, H)))
        mask, slopes = jnp.ones((B, 1, S), jnp.int32), jnp.zeros((H, 1, fa._LANES))
        if two_pass:
            monkeypatch.setattr(fa, "_ONE_PASS_DQ_BYTES", 0)
        out, lse = fa._flash_fwd(q, k, v, mask, slopes, 16, 16, True, False, False)
        bwd = lambda do: fa._flash_bwd(q, k, v, mask, slopes, out, lse, do, 16, 16, True, False, False,  # noqa: E731
                                       softmax_scale=0.3)
        calls = [e for e in jax.make_jaxpr(bwd)(do).eqns if e.primitive.name == "pallas_call"]
        made = [str(o.aval.dtype) for e in calls for o in e.outvars]
        per_head = "bfloat16" if Hkv == H else "float32"
        assert made == ["bfloat16", per_head, per_head], made
        dq, dk, dv = bwd(do)
        assert {g.dtype for g in (dq, dk, dv)} == {jnp.dtype(jnp.bfloat16)}
        assert (dq.shape, dk.shape, dv.shape) == (q.shape, k.shape, v.shape)
        # the same quantities in fp32, scaled and rounded outside the kernels
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        rq, rk, rv = fa._flash_bwd(f32(q), f32(k), f32(v), mask, slopes, f32(out), lse, f32(do), 16, 16,
                                   True, False, False, softmax_scale=0.3)
        for name, got, ref in (("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
            np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(ref), rtol=2e-2, atol=2e-2, err_msg=name)

    def test_the_serving_statistic_is_the_row_in_natural_units(self):
        """``flash_causal_attention_lse`` (EVA's prefill) gives the kernel's row
        back as ``[B, S, H]``, a length off the blocks padded and cut."""
        B, S, H, D = 2, 100, 2, 16
        q, k, v = (_rand(i, (B, S, H, D)) for i in range(3))
        out, lse = fa.flash_causal_attention_lse(q, k, v, block_q=32, block_k=32)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * D ** -0.5
        ref = jax.nn.logsumexp(jnp.where(jnp.tril(jnp.ones((S, S), bool)), scores, -jnp.inf), axis=-1)
        assert lse.shape == (B, S, H)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(ref.transpose(0, 2, 1)), atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ops.causal_attention(q, k, v, impl="xla")),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("reading,kernels", [
    ("fwd", ["flash_fwd"]),
    ("bwd", ["flash_fwd", "flash_bwd_dkv"]),
    ("bwd_pair", ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
])
def test_flash_kernel_bench_runs_each_reading(reading, kernels):
    """``tools/flash_kernel_bench.py`` (PERF.md reads its chip runs) at a toy
    shape in interpret mode: every reading runs, times the kernels it says,
    and counts blocks and the roofline's FLOPs as the benchmark does. The
    times themselves mean nothing here; ``main`` refuses to run off a chip."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "flash_kernel_bench.py")
    spec = importlib.util.spec_from_file_location("flash_kernel_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    got = bench.measure(reading, (1, 32, 2, 16), block=8, calls=2, repeats=1,
                        device_kind="TPU v5 lite")
    assert got["kernels"] == kernels and got["finite"]
    assert got["blocks"] == 2 * 4 * 5 // 2  # heads x the causal triangle of 4 x 4 blocks
    assert got["us_per_block"] == pytest.approx(1e3 * got["ms_per_call"] / got["blocks"])
    assert got["roofline_pct"] == pytest.approx(100.0 * got["least_ms"] / got["ms_per_call"])
    assert set(bench.SHAPES.values()) == {(2, 2048, 16, 64), (1, 2048, 16, 128)}
    # no Mosaic call in interpret mode: the stored bytes are read from a chip's compiled text alone
    assert got["stat_bytes_per_call"] == {} and got["stat_numbers_bytes"] == 4 * 1 * 2 * 32


@pytest.mark.parametrize("stat,tile,stored", [
    ("f32[2,16,1,2048]{3,2,1,0", "T(1,128)", 2 * 16 * 2048 * 4),          # a row: its numbers
    ("f32[2,16,2048,8]{3,2,1,0", "T(8,128)", 2 * 16 * 2048 * 128 * 4),    # a column of 8: sixteen-fold
])
def test_flash_kernel_bench_reads_the_statistics_as_the_program_stores_them(stat, tile, stored):
    """``stat_bytes``: the float32 statistics on a kernel's own line of a
    compiled program (results inline, operands by the lines that make them),
    each padded as its layout's tile says; the gradients, q, k, v and the
    mask are not statistics."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "flash_kernel_bench.py")
    spec = importlib.util.spec_from_file_location("flash_kernel_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    big = "2,16,2048,64]{3,2,1,0:T(8,128)"
    text = "\n".join([
        f"  %fusion.1 = {stat}:{tile}S(1)}} fusion(%p.1), kind=kLoop",
        f"  %gte.2 = {stat}:{tile}}} get-tuple-element(%p.2), index=1",
        f"  %do.3 = bf16[{big}(2,1)}} parameter(3)",
        f"  %flash_fwd.4 = (bf16[{big}(2,1)S(1)}}, {stat}:{tile}S(1)}}) custom-call(%do.3, %do.3), "
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/flash_fwd/pallas_call"}',
        f"  %flash_bwd_dkv.5 = (f32[{big}}}, f32[{big}}}) custom-call(%do.3, %gte.2, %fusion.1), "
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/flash_bwd_dkv/pallas_call"}',
    ])
    assert bench.stat_bytes(text, (2, 2048, 16, 64)) == {"flash_fwd": stored, "flash_bwd_dkv": 2 * stored}


def _train_step_tool():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "train_step_for_described_chip.py")
    spec = importlib.util.spec_from_file_location("train_step_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def test_train_step_tool_counts_a_body_of_gathers_and_reduce_scatters_without_a_ring():
    """Since PR 45 the layer scan's backward body holds no
    ``collective-permute``: the tool finds it by the flash kernel alone, and
    its census counts every computation's collectives by kind and MB, a
    gather once though three ``async_collective_fusion``s (start, step, end)
    each hold its instruction, a ``-done`` with its ``-start``."""
    tool = _train_step_tool()
    shard, whole = "bf16[512,16,128]{2,0,1:T(8,128)(2,1)S(1)}", "bf16[2048,16,128]{2,0,1:T(8,128)(2,1)}"
    gather = f"  %all-gather.7 = {whole} all-gather(%p), channel_id=103, dimensions={{0}}, " \
             'metadata={op_name="a/layers/attn/wq/shard_map/zero_gather/all_gather"}'
    text = "\n".join([
        "HloModule jit_train_step", "",
        *(line for i in (1, 2, 3) for line in (f"%async_collective_fusion.{i} (p: {shard}) -> {whole} {{", gather, "}", "")),
        "%backward.2 (p: bf16[8]) -> bf16[8] {",
        "  %p = bf16[8]{0} parameter(0)",
        *(f"  %fusion.{i} = {whole} fusion(%p), kind=kCustom, calls=%async_collective_fusion.{i}" for i in (1, 2, 3)),
        "  %flash_bwd_dkv.1 = (bf16[1,16,2048,128]{3,2,1,0}, bf16[1,16,2048,128]{3,2,1,0}) custom-call(%p), "
        'custom_call_target="tpu_custom_call", metadata={op_name="a/flash_bwd_dkv/pallas_call"}',
        f"  %reduce_scatter.61 = {shard} reduce-scatter(%p), channel_id=1, dimensions={{0}}, "
        'metadata={op_name="a/layers/attn/wk/shard_map/zero_scatter/reduce_scatter"}',
        f"  %reduce_scatter.63 = {shard} reduce-scatter(%p), channel_id=1, dimensions={{0}}, "
        'metadata={op_name="a/layers/attn/wq/shard_map/zero_scatter/reduce_scatter"}',
        "  %all-reduce-start.4 = bf16[2048]{0} all-reduce-start(%p), channel_id=13",
        "  %all-reduce-done.4 = bf16[2048]{0} all-reduce-done(%all-reduce-start.4)",
        "}",
    ])
    rows = tool.backward_body(text)
    assert [r[0] for r in rows][3:6] == ["flash_bwd_dkv.1", "reduce_scatter.61", "reduce_scatter.63"]
    assert [name for name, _ in tool.collectives(rows)] == [
        "flash_bwd_dkv.1", "reduce_scatter.61", "reduce_scatter.63", "all-reduce-start.4", "all-reduce-done.4"]
    found = tool.census(text)
    assert list(found) == ["backward.2"]  # a fusion's computation is counted with its caller
    assert tool.by_kind(found["backward.2"]) == {
        "all-gather": (1, 8.4), "all-reduce": (1, 0.0), "reduce-scatter": (2, 4.2)}
    assert found["backward.2"][0][2].endswith("wq/shard_map/zero_gather/all_gather")
    assert tool.array_mb("(bf16[8192,1,512]{2,0,1}, f32[4]{0}, u32[]{:S(2)})") == pytest.approx(8.388608)


def test_train_step_tool_reads_the_backward_body_s_collectives():
    """``tools/train_step_for_described_chip.py`` (ROADMAP S4 reads its
    output): of a compiled text, the computation that holds the flash backward
    kernel, its collectives in schedule order, and the memory space of each
    one's buffers (``S(1)`` is the chip's fast memory)."""
    tool = _train_step_tool()
    big = "bf16[8192,1,512]{2,0,1:T(8,128)(2,1)"
    text = "\n".join([
        "HloModule jit_train_step", "",
        "%forward.1 (p: bf16[8]) -> bf16[8] {",
        f"  %collective-permute-start.1 = ({big}}}, {big}}}) collective-permute-start(%p)",
        "}", "",
        "%backward.2 (p: bf16[8]) -> bf16[8] {",
        "  %p = bf16[8]{0} parameter(0)",
        f"  %collective-permute-start.23 = ({big}S(1)}}, {big}}}) collective-permute-start(%p), "
        'metadata={op_name="jit(train_step)/while/body/layers/mlp/w_down/dot_general"}',
        "  %flash_bwd_dkv.1 = (bf16[1,16,2048,128]{3,2,1,0}, bf16[1,16,2048,128]{3,2,1,0}) custom-call(%p), "
        'custom_call_target="tpu_custom_call", metadata={op_name="a/flash_bwd_dkv/pallas_call"}',
        f"  %fusion.5 = {big}}} fusion(%p), kind=kOutput",
        f"  %collective-permute-done.23 = {big}}} collective-permute-done(%collective-permute-start.23)",
        "  %all-reduce.101 = bf16[2048]{0:T(1024)(128)(2,1)S(1)} all-reduce(%p)",
        "}",
    ])
    rows = tool.backward_body(text)
    assert [r[0] for r in rows] == ["collective-permute-start.23", "flash_bwd_dkv.1", "fusion.5",
                                    "collective-permute-done.23", "all-reduce.101"]
    assert rows[0][3] == "mlp/w_down/dot_general"
    assert tool.collectives(rows) == [("collective-permute-start.23", "VH"), ("flash_bwd_dkv.1", "HH"),
                                      ("collective-permute-done.23", "H"), ("all-reduce.101", "V")]
    with pytest.raises(ValueError):
        tool.backward_body(text.replace("flash_bwd_dkv", "other_kernel"))


class TestNorms:
    def test_rms_norm(self):
        x = _rand(0, (4, 12, 64))
        scale = 1.0 + 0.1 * _rand(1, (64,))
        ref = ops.rms_norm(x, scale, impl="xla")
        out = ops.dispatch("rms_norm", "pallas")(x, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

    def test_rms_norm_grad(self):
        x = _rand(0, (8, 32))
        scale = 1.0 + 0.1 * _rand(1, (32,))

        def f(fn):
            return lambda x, s: jnp.sum(jnp.sin(fn(x, s)))

        ref = jax.grad(f(lambda x, s: ops.rms_norm(x, s, impl="xla")), argnums=(0, 1))(x, scale)
        out = jax.grad(f(lambda x, s: ops.dispatch("rms_norm", "pallas")(x, s)), argnums=(0, 1))(x, scale)
        for r, o in zip(ref, out):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5, rtol=1e-5)

    def test_layer_norm(self):
        x = _rand(0, (4, 12, 64))
        scale = 1.0 + 0.1 * _rand(1, (64,))
        bias = 0.1 * _rand(2, (64,))
        ref = ops.layer_norm(x, scale, bias, impl="xla")
        out = ops.dispatch("layer_norm", "pallas")(x, scale, bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

    def test_layer_norm_grad(self):
        x = _rand(0, (8, 32))
        scale = 1.0 + 0.1 * _rand(1, (32,))
        bias = 0.1 * _rand(2, (32,))

        def f(fn):
            return lambda x, s, b: jnp.sum(jnp.sin(fn(x, s, b)))

        ref = jax.grad(f(lambda x, s, b: ops.layer_norm(x, s, b, impl="xla")), argnums=(0, 1, 2))(x, scale, bias)
        out = jax.grad(f(lambda x, s, b: ops.dispatch("layer_norm", "pallas")(x, s, b)), argnums=(0, 1, 2))(x, scale, bias)
        for r, o in zip(ref, out):
            np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5, rtol=1e-5)


class TestQuantizer:
    @pytest.mark.parametrize("n", [64, 1000, 4096])
    def test_roundtrip_error_bounded(self, n):
        x = _rand(0, (n,))
        vals, scales = ops.quantize_int8(x, block_size=256, impl="pallas")
        assert vals.dtype == jnp.int8
        back = ops.dequantize_int8(vals, scales, (n,), dtype=jnp.float32, block_size=256, impl="pallas")
        err = np.abs(np.asarray(back) - np.asarray(x))
        bound = np.asarray(scales).max() * 0.51 + 1e-6
        assert err.max() <= bound

    def test_pallas_matches_xla(self):
        x = _rand(0, (512,))
        v_p, s_p = ops.quantize_int8(x, block_size=128, impl="pallas")
        v_x, s_x = ops.quantize_int8(x, block_size=128, impl="xla")
        np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_x))
        np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x), rtol=1e-6)


def test_attention_pair_bias_and_alibi(devices):
    """Evoformer-style additive pair bias + bloom-style alibi slopes
    (reference csrc/deepspeed4science/evoformer_attn + the alibi softmax
    path). Biased forms ride the differentiable XLA path."""
    import numpy as np
    from deepspeed_tpu.ops import causal_attention

    B, S, H, D = 2, 16, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks[:3])
    bias = jax.random.normal(ks[3], (H, S, S)) * 0.5

    # manual reference with the bias folded into masked scores
    def ref(q, k, v, extra):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D)) + extra
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -1e9)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    got = causal_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(q, k, v, bias[None])),
                               rtol=2e-5, atol=2e-5)

    # pair bias is differentiable (evoformer trains through it)
    gb = jax.grad(lambda b: (causal_attention(q, k, v, bias=b) ** 2).sum())(bias)
    assert np.abs(np.asarray(gb)).sum() > 0 and np.isfinite(np.asarray(gb)).all()

    # alibi == bias of slopes * key-position
    from deepspeed_tpu.models.transformer import alibi_slopes

    slopes = alibi_slopes(H)
    ali = causal_attention(q, k, v, alibi_slopes=slopes)
    want = ref(q, k, v, (slopes[:, None, None] *
                         jnp.arange(S, dtype=jnp.float32)[None, None, :])[None])
    np.testing.assert_allclose(np.asarray(ali), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_alibi_slopes_match_hf_formula(devices):
    import numpy as np
    from deepspeed_tpu.models.transformer import alibi_slopes

    # power-of-2 head count: geometric sequence from 2^(-8/n)
    s8 = np.asarray(alibi_slopes(8))
    np.testing.assert_allclose(s8, [2 ** (-(i + 1)) for i in range(8)], rtol=1e-6)
    # non-power-of-2 (6 heads): 4 base slopes then 2 odd-power extras,
    # appended (NOT sorted) exactly as HF build_alibi_tensor orders them
    s6 = np.asarray(alibi_slopes(6))
    np.testing.assert_allclose(
        s6, [0.25, 0.0625, 0.015625, 0.00390625, 0.5, 0.125], rtol=1e-6)


def test_evoformer_attention_bidirectional_with_pair_bias(devices):
    """DS4Science evoformer coverage (reference csrc/deepspeed4science/
    evoformer_attn): bidirectional + pair bias + mask, d(pair_bias) flows."""
    B, S, H, D = 2, 12, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in ks[:3])
    bias = jax.random.normal(ks[3], (H, S, S)) * 0.3
    mask = jnp.asarray(np.array([[1] * 12, [1] * 9 + [0] * 3]), jnp.int32)

    def ref(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D)) + bias[None]
        s = jnp.where(mask[:, None, None, :] > 0, s, -1e9)  # NO causal mask
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    got = ops.evoformer_attention(q, k, v, pair_bias=bias, mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    # genuinely bidirectional: differs from the causal-masked form
    c = ops.causal_attention(q, k, v, mask=mask, bias=bias)
    assert np.abs(np.asarray(got - c)).max() > 1e-3

    gb = jax.grad(lambda b: (ops.evoformer_attention(q, k, v, pair_bias=b,
                                                     mask=mask) ** 2).sum())(bias)
    assert np.isfinite(np.asarray(gb)).all() and np.abs(np.asarray(gb)).sum() > 0


class TestFlashAlibi:
    """ALiBi fused into the flash kernels (slope * column iota in all three
    kernels) — bloom-style training keeps the flash path instead of the XLA
    fallback."""

    def _qkv(self, B=2, S=32, H=4, D=8, Hkv=None):
        from deepspeed_tpu.models.transformer import alibi_slopes

        Hkv = Hkv or H
        return (_rand(0, (B, S, H, D)), _rand(1, (B, S, Hkv, D)),
                _rand(2, (B, S, Hkv, D)), alibi_slopes(H))

    @pytest.mark.parametrize("bq,bk", [(8, 8), (16, 8)])  # squashed + dense grids
    def test_forward_matches_xla(self, bq, bk):
        q, k, v, slopes = self._qkv()
        ref = ops.causal_attention(q, k, v, impl="xla", alibi_slopes=slopes)
        out = ops.dispatch("causal_attention", "pallas")(
            q, k, v, block_q=bq, block_k=bk, alibi_slopes=slopes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("gqa,bq,bk", [
        (False, 8, 8),   # squashed grid
        (False, 16, 8),  # dense grid (incl. the _DEC_DENSE_KQ dkv decoder)
        (True, 8, 8),    # GQA: slope indexed by query head h, k/v by h//G
    ])
    def test_grads_match_xla(self, gqa, bq, bk):
        q, k, v, slopes = self._qkv(Hkv=2 if gqa else None)

        def loss(fn):
            def f(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out * jnp.cos(out.astype(jnp.float32)))
            return f

        ref = jax.grad(loss(lambda q, k, v: ops.causal_attention(
            q, k, v, impl="xla", alibi_slopes=slopes)), argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(loss(lambda q, k, v: ops.dispatch("causal_attention", "pallas")(
            q, k, v, block_q=bq, block_k=bk, alibi_slopes=slopes)), argnums=(0, 1, 2))(q, k, v)
        for r, g in zip(ref, got):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5, rtol=5e-5)

    def test_gqa_forward_matches_xla(self):
        q, k, v, slopes = self._qkv(Hkv=2)
        ref = ops.causal_attention(q, k, v, impl="xla", alibi_slopes=slopes)
        out = ops.dispatch("causal_attention", "pallas")(
            q, k, v, block_q=8, block_k=8, alibi_slopes=slopes)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)

    def test_masked_forward_matches_xla(self):
        q, k, v, slopes = self._qkv(S=24)
        mask = jnp.asarray(np.random.default_rng(2).integers(0, 2, (2, 24)), jnp.int32).at[:, 0].set(1)
        ref = ops.causal_attention(q, k, v, mask=mask, impl="xla", alibi_slopes=slopes)
        out = ops.dispatch("causal_attention", "pallas")(
            q, k, v, mask=mask, block_q=8, block_k=8, alibi_slopes=slopes)
        keep = np.asarray(mask, bool)
        np.testing.assert_allclose(np.asarray(out)[keep], np.asarray(ref)[keep],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("lengths", [(0,), (1,), (15,), (16,), (17,), (37,), (64,), (37, 64), (16, 0), (1, 50), (48, 17)],
                         ids=lambda a: "-".join(map(str, a)))
def test_the_forward_told_its_rows_lengths_is_the_kernel_s_own_on_live_rows_and_zeros_on_pads(lengths):
    """``_flash_fwd(lengths=)`` under the causal mask alone (``tests/unit/ops/test_swa.py`` has the band's cases
    and the helper): blocks of 16 in rows of 64, GQA 4:1, one row and two rows of different lengths."""
    from tests.unit.ops.test_swa import live_rows_are_the_kernel_s_own_and_pads_are_zeros

    live_rows_are_the_kernel_s_own_and_pads_are_zeros(lengths, window=None)


@pytest.mark.parametrize("lengths", [(1,), (16,), (37,), (48, 17)], ids=lambda a: "-".join(map(str, a)))
def test_nan_in_the_blocks_past_a_row_s_last_token_is_never_read(lengths):
    from tests.unit.ops.test_swa import live_rows_are_the_kernel_s_own_and_pads_are_zeros

    live_rows_are_the_kernel_s_own_and_pads_are_zeros(lengths, window=None, plant=jnp.nan)


def test_the_dense_grid_told_its_rows_lengths_zeroes_the_pads_after():
    """Past ``_MAX_SQUASHED_CELLS`` the dense grid runs: it computes the pads' cells as ever and their rows are
    zeroed outside the kernel, so a caller reads the same thing from either grid."""
    from unittest import mock

    from tests.unit.ops.test_swa import flash_fwd_told

    with mock.patch.object(fa, "_MAX_SQUASHED_CELLS", 0):
        (out, lse), (whole, whole_lse) = flash_fwd_told((37, 16), None)
    for b, n in enumerate((37, 16)):
        assert np.array_equal(out[b, :, :n], whole[b, :, :n]) and np.array_equal(lse[b, :, :, :n], whole_lse[b, :, :, :n])
        assert not np.asarray(out[b, :, n:]).any() and (np.asarray(lse[b, :, :, n:]) == fa._NEG_INF).all()


@pytest.mark.parametrize("window", [None, 24], ids=["flash_fwd", "swa_flash_fwd"])
def test_flash_kernel_bench_times_the_prefill_s_two_forms(window):
    """``tools/flash_kernel_bench.py``'s ``prefill`` reading at a toy shape in interpret mode: both forms run, the
    live form's live rows are the parent form's with the pads zeros, and the cells are the kernels' own count."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "tools", "flash_kernel_bench.py")
    spec = importlib.util.spec_from_file_location("flash_kernel_bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    toy = dict(shape=(64, 4, 1, 16), block=16, calls=2, repeats=1, device_kind="TPU v5 lite")
    parent = bench.measure_prefill(window, (64,), "parent", **toy)
    live = bench.measure_prefill(window, (64, 33), "live", **toy)
    cells = 10 if window is None else 9  # of a row of four blocks: the triangle's 1 + 2 + 3 + 4, the band's 1 + 2 + 3 + 3
    assert (parent["kernel"], parent["cells_live"], parent["cells_grid"]) == (live["kernel"], cells, cells)
    assert (live["cells_live"], live["cells_grid"]) == (cells + 6, 2 * cells)  # 33 tokens: three blocks, 1 + 2 + 3
    assert live["live_rows_equal"] and live["pads_zero"] and parent["live_rows_equal"]
    assert live["roofline_pct"] == pytest.approx(100.0 * live["least_ms"] / live["ms_per_call"])
    assert bench.PREFILL_SHAPE == (16384, 128, 8, 128) and bench.PREFILL_LENGTHS == (8192, 12288, 16384)
