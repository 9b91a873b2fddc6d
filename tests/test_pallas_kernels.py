"""Pallas kernel tier numerics vs XLA reference compositions
(interpret mode on the CPU test backend; same kernels compile on TPU).

Reference analogs: paddle/phi/kernels/fusion/gpu/* fused kernels and the
flash-attn dynload path (paddle/phi/kernels/gpu/flash_attn_kernel.cu);
test strategy per SURVEY §4 (OpTest numeric checking vs reference impl).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.nn.pallas import flash_attn as pfa
from paddle_tpu.incubate.nn.pallas import norms as pnorms


def _ref_attention(q, k, v, causal):
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    s = qh.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if causal:
        m = jnp.tril(jnp.ones((logits.shape[-2], logits.shape[-1]), bool))
        logits = jnp.where(m, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", w, vh), 1, 2)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward(self, causal):
        rng = np.random.RandomState(0)
        b, s, h, d = 1, 256, 2, 64
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        out = pfa.flash_attention(q, k, v, causal=causal)
        ref = _ref_attention(q, k, v, causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads(self, causal):
        rng = np.random.RandomState(1)
        b, s, h, d = 1, 256, 2, 64
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        g = jax.grad(loss(lambda q, k, v: pfa.flash_attention(
            q, k, v, causal=causal)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: _ref_attention(
            q, k, v, causal)), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)

    def test_gqa(self):
        rng = np.random.RandomState(2)
        b, s, hq, hkv, d = 1, 256, 4, 2, 64
        q = jnp.asarray(rng.randn(b, s, hq, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, hkv, d), jnp.float32)
        out = pfa.flash_attention(q, k, v, causal=True)
        kr = jnp.repeat(k, 2, axis=2)
        vr = jnp.repeat(v, 2, axis=2)
        ref = _ref_attention(q, kr, vr, True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_kv_longer_than_q(self):
        """Bottom-right-aligned causal mask (chunked prefill): must match
        the XLA fallback's tril(..., sk - sq) alignment."""
        rng = np.random.RandomState(4)
        b, h, d = 1, 2, 64
        sq, sk = 128, 256
        q = jnp.asarray(rng.randn(b, sq, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, sk, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, sk, h, d), jnp.float32)
        out = pfa.flash_attention(q, k, v, causal=True)
        qh = jnp.swapaxes(q, 1, 2)
        kh = jnp.swapaxes(k, 1, 2)
        vh = jnp.swapaxes(v, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * d ** -0.5
        mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        logits = jnp.where(mask, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        ref = jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", w, vh), 1, 2)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # grads flow through the offset mask too
        g = jax.grad(lambda q, k, v: (pfa.flash_attention(
            q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        assert all(np.isfinite(np.asarray(x)).all() for x in g)

    def test_bf16(self):
        rng = np.random.RandomState(3)
        b, s, h, d = 1, 128, 2, 128
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        out = pfa.flash_attention(q, k, v, causal=True)
        ref = _ref_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(jnp.float32), ref,
                                   atol=3e-2, rtol=3e-2)


class TestPallasNorms:
    def test_rms_norm(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(6, 96, 256), jnp.float32)
        w = jnp.asarray(rng.randn(256), jnp.float32)
        out = pnorms.rms_norm(x, w)
        ref = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * w
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_rms_norm_bias_grad(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(4, 256), jnp.float32)
        w = jnp.asarray(rng.randn(256), jnp.float32)
        b = jnp.asarray(rng.randn(256), jnp.float32)
        out = pnorms.rms_norm(x, w, b)
        ref = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * w + b
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        g = jax.grad(lambda x: pnorms.rms_norm(x, w, b).sum())(x)
        gr = jax.grad(lambda x: (((x / jnp.sqrt(
            jnp.mean(x * x, -1, keepdims=True) + 1e-6)) * w) + b).sum())(x)
        np.testing.assert_allclose(g, gr, atol=1e-5, rtol=1e-5)

    def test_layer_norm(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(8, 128), jnp.float32)
        w = jnp.asarray(rng.randn(128), jnp.float32)
        b = jnp.asarray(rng.randn(128), jnp.float32)
        out = pnorms.layer_norm(x, w, b)
        mu = x.mean(-1, keepdims=True)
        xc = x - mu
        ref = xc / jnp.sqrt((xc * xc).mean(-1, keepdims=True) + 1e-5) * w + b
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


class TestFlashUnderMesh:
    def test_mesh_mapped_flash_matches_xla(self, monkeypatch):
        """Under an active dp x mp mesh the flash kernel is mapped over
        the mesh with shard_map (GSPMD cannot partition a Mosaic kernel:
        on four real chips the train step failed to lower). Same values
        and grads as the XLA composition."""
        import paddle_tpu as pt
        from paddle_tpu.distributed.auto_parallel.process_mesh import (
            ProcessMesh, get_mesh, set_mesh)
        import importlib

        # (the package re-exports a function under the module's name)
        fa = importlib.import_module(
            "paddle_tpu.incubate.nn.functional.flash_attention")
        rng = np.random.RandomState(0)
        q, k, v = (rng.randn(2, 128, 4, 64).astype(np.float32)
                   for _ in range(3))
        ref_loss, ref_g = jax.value_and_grad(
            lambda a, b, c: (fa._xla_attention(a, b, c, True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)

        # the kernel runs interpreted here; only the routing is forced
        monkeypatch.setattr(fa, "attention_impl", lambda *a: "pallas")
        mesh = ProcessMesh(np.arange(4).reshape(2, 1, 2),
                           dim_names=["dp", "sp", "mp"])

        def loss(a, b, c):
            prev = get_mesh()
            set_mesh(mesh)
            try:
                out, _ = fa.flash_attention(pt.to_tensor(a), pt.to_tensor(b),
                                            pt.to_tensor(c), causal=True)
            finally:
                set_mesh(prev)
            return (out._data ** 2).sum()

        jitted = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        assert "shard_map" in str(jitted.trace(q, k, v).jaxpr)
        got_loss, got_g = jitted(q, k, v)
        np.testing.assert_allclose(got_loss, ref_loss, rtol=1e-4)
        for a, b in zip(got_g, ref_g):
            np.testing.assert_allclose(a, b, atol=2e-4, rtol=2e-4)


class TestFusedOpsDispatch:
    def test_fused_rms_norm_pallas_path(self):
        import paddle_tpu as pt
        from paddle_tpu.incubate.nn.functional import fused_ops
        from paddle_tpu.incubate.nn.functional import fused_rms_norm

        x = pt.to_tensor(np.random.RandomState(0).randn(2, 8, 256)
                         .astype(np.float32))
        w = pt.to_tensor(np.ones(256, np.float32))
        xn = x.numpy()
        ref = xn / np.sqrt((xn * xn).mean(-1, keepdims=True) + 1e-6)
        # exercise BOTH branches: forced Pallas dispatch and XLA fallback
        fused_ops._FORCE_PALLAS = True
        try:
            out_pallas = fused_rms_norm(x, w)
        finally:
            fused_ops._FORCE_PALLAS = False
        out_xla = fused_rms_norm(x, w)
        np.testing.assert_allclose(out_pallas.numpy(), ref, atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(out_xla.numpy(), ref, atol=1e-5,
                                   rtol=1e-5)

    def test_fused_rms_norm_residual(self):
        import paddle_tpu as pt
        from paddle_tpu.incubate.nn.functional import fused_rms_norm

        rng = np.random.RandomState(1)
        x = pt.to_tensor(rng.randn(2, 4, 128).astype(np.float32))
        r = pt.to_tensor(rng.randn(2, 4, 128).astype(np.float32))
        w = pt.to_tensor(np.ones(128, np.float32))
        out, new_resid = fused_rms_norm(x, w, residual=r)
        s = x.numpy() + r.numpy()
        np.testing.assert_allclose(new_resid.numpy(), s, atol=1e-6)
        ref = s / np.sqrt((s * s).mean(-1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(out.numpy(), ref, atol=1e-5, rtol=1e-5)


class TestFusedFlashBackward:
    """Single-pass fused backward (VERDICT r4 next #8): dk/dv/dq from
    one (j, i) sweep sharing the s and dp matmuls; must bit-match the
    two-kernel split in interpret mode and respect the scratch cap."""

    def _grads(self, fn, s, bq, bk, causal, d=64, bh=2, seed=0):
        import jax.numpy as jnp

        from paddle_tpu.incubate.nn.pallas import flash_attn as F

        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((bh, s, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((bh, s, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((bh, s, d)), jnp.float32)
        do = jnp.asarray(rng.standard_normal((bh, s, d)), jnp.float32)
        scale = d ** -0.5
        out, lse = F._flash_fwd(q, k, v, causal, scale, bq, bk, True)
        return fn(q, k, v, out, lse, do, causal, scale, bq, bk,
                  s // bq, s // bk, True)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("s,bq,bk", [(256, 128, 128), (256, 128, 64),
                                         (512, 256, 128)])
    def test_fused_matches_split(self, causal, s, bq, bk):
        from paddle_tpu.incubate.nn.pallas import flash_attn as F

        fused = self._grads(F._flash_bwd_fused, s, bq, bk, causal)
        split = self._grads(F._flash_bwd_split, s, bq, bk, causal)
        for name, a, b in zip("dq dk dv".split(), fused, split):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=name)

    def test_scratch_cap_falls_back_to_split(self):
        """Sequences whose dq scratch would blow VMEM use the split
        path; cross-length (sq != sk) always does."""
        import jax.numpy as jnp

        from paddle_tpu.incubate.nn.pallas import flash_attn as F

        old = F._FUSED_BWD_MAX_SEQ_D
        try:
            F._FUSED_BWD_MAX_SEQ_D = 0     # force the fallback
            rng = np.random.default_rng(1)
            q = jnp.asarray(rng.standard_normal((2, 256, 64)),
                            jnp.float32)
            do = jnp.asarray(rng.standard_normal((2, 256, 64)),
                             jnp.float32)
            scale = 64 ** -0.5
            out, lse = F._flash_fwd(q, q, q, True, scale, 128, 128, True)
            got = F._flash_bwd(q, q, q, out, lse, do, True, scale,
                               128, 128, True)
            F._FUSED_BWD_MAX_SEQ_D = old
            want = F._flash_bwd(q, q, q, out, lse, do, True, scale,
                                128, 128, True)
            for a, b in zip(got, want):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-5)
        finally:
            F._FUSED_BWD_MAX_SEQ_D = old
