"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode runs
the exact TPU kernel body on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.fast_maxvol import fast_maxvol_pallas
from repro.kernels.projection_sweep import projection_sweep_pallas
from repro.kernels.rwkv_scan import rwkv_scan_pallas


class TestFastMaxvolKernel:
    @pytest.mark.parametrize("K,R,rank", [
        (16, 4, 4), (64, 16, 16), (128, 32, 8), (256, 64, 64),
        (100, 12, 12), (33, 7, 5),
    ])
    def test_matches_ref(self, rng, K, R, rank):
        V = jnp.asarray(rng.normal(size=(K, R)).astype(np.float32))
        piv_k, lv_k = fast_maxvol_pallas(V, rank, interpret=True)
        piv_r, lv_r = ref.fast_maxvol_ref(V, rank)
        assert np.array_equal(np.asarray(piv_k), np.asarray(piv_r))
        np.testing.assert_allclose(float(lv_k), float(lv_r), rtol=1e-5)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16])
    def test_dtype_sweep(self, rng, dtype):
        V = jnp.asarray(rng.normal(size=(64, 8)).astype(dtype))
        piv_k, _ = fast_maxvol_pallas(V, 8, interpret=True)
        piv_r, _ = ref.fast_maxvol_ref(V.astype(jnp.float32), 8)
        assert np.array_equal(np.asarray(piv_k), np.asarray(piv_r))

    def test_vmem_budget_guard(self, rng):
        V = jnp.zeros((4096, 1024), jnp.float32)      # 16 MB > budget
        with pytest.raises(ValueError, match="VMEM"):
            fast_maxvol_pallas(V, 16, interpret=True)


class TestProjectionSweepKernel:
    @pytest.mark.parametrize("d,R", [(32, 4), (50, 16), (512, 32),
                                     (2048, 64), (999, 13)])
    def test_matches_ref(self, rng, d, R):
        G = jnp.asarray(rng.normal(size=(d, R)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(d,)).astype(np.float32))
        e_k = projection_sweep_pallas(G, g, interpret=True)
        e_r = ref.projection_sweep_ref(G, g)
        np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r), atol=1e-5)

    def test_monotone(self, rng):
        G = jnp.asarray(rng.normal(size=(128, 24)).astype(np.float32))
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        errs = np.asarray(projection_sweep_pallas(G, g, interpret=True))
        assert np.all(np.diff(errs) <= 1e-5)


class TestRwkvScanKernel:
    @pytest.mark.parametrize("BH,T,D,chunk", [
        (1, 32, 16, 8), (4, 64, 32, 16), (2, 128, 64, 32), (3, 96, 48, 32),
    ])
    def test_matches_ref(self, rng, BH, T, D, chunk):
        r = rng.normal(size=(BH, T, D)).astype(np.float32) * 0.3
        k = rng.normal(size=(BH, T, D)).astype(np.float32) * 0.3
        v = rng.normal(size=(BH, T, D)).astype(np.float32) * 0.3
        w = (0.4 + 0.59 * rng.random(size=(BH, T, D))).astype(np.float32)
        u = rng.normal(size=(BH, D)).astype(np.float32) * 0.1
        o_k = rwkv_scan_pallas(*map(jnp.asarray, (r, k, v, w, u)),
                               chunk=chunk, interpret=True)
        o_r = np.stack([np.asarray(ref.rwkv_chunk_ref(
            jnp.asarray(r[i]), jnp.asarray(k[i]), jnp.asarray(v[i]),
            jnp.asarray(w[i]), jnp.asarray(u[i]))) for i in range(BH)])
        np.testing.assert_allclose(np.asarray(o_k), o_r, atol=2e-4)

    def test_chunk_invariance(self, rng):
        """Output must not depend on the chunk size (state carried exactly)."""
        BH, T, D = 2, 64, 32
        args = (rng.normal(size=(BH, T, D)).astype(np.float32) * 0.3,
                rng.normal(size=(BH, T, D)).astype(np.float32) * 0.3,
                rng.normal(size=(BH, T, D)).astype(np.float32) * 0.3,
                (0.5 + 0.49 * rng.random((BH, T, D))).astype(np.float32),
                rng.normal(size=(BH, D)).astype(np.float32) * 0.1)
        outs = [np.asarray(rwkv_scan_pallas(*map(jnp.asarray, args),
                                            chunk=c, interpret=True))
                for c in (8, 16, 64)]
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
        np.testing.assert_allclose(outs[0], outs[2], atol=1e-5)

    def test_indivisible_chunk_raises(self, rng):
        with pytest.raises(ValueError, match="divisible"):
            rwkv_scan_pallas(jnp.zeros((1, 30, 8)), jnp.zeros((1, 30, 8)),
                             jnp.zeros((1, 30, 8)), jnp.ones((1, 30, 8)),
                             jnp.zeros((1, 8)), chunk=16, interpret=True)


class TestOpsDispatch:
    def test_ops_cpu_uses_interpret(self, rng):
        V = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
        piv = ops.fast_maxvol(V, 8)
        piv_r, _ = ref.fast_maxvol_ref(V, 8)
        assert np.array_equal(np.asarray(piv), np.asarray(piv_r))

    def test_graft_select_with_pallas_kernels(self, rng):
        """GraftConfig(use_pallas=True) must agree with the jnp path."""
        from repro.core import graft
        from repro.core.features import svd_features
        K, d = 32, 24
        A = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32))
        G = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
        gb = jnp.asarray(np.asarray(G).mean(1))
        V = svd_features(A, 8)
        cfg_j = graft.GraftConfig(rset=(2, 4, 8), eps=0.3, use_pallas=False)
        cfg_p = graft.GraftConfig(rset=(2, 4, 8), eps=0.3, use_pallas=True)
        s_j = graft.graft_select(cfg_j, V, G, gb, jnp.int32(0))
        s_p = graft.graft_select(cfg_p, V, G, gb, jnp.int32(0))
        assert np.array_equal(np.asarray(s_j.pivots), np.asarray(s_p.pivots))
        assert int(s_j.rank) == int(s_p.rank)
        np.testing.assert_allclose(float(s_j.last_error),
                                   float(s_p.last_error), atol=1e-5)


def _qkv(rng, q_shape, kv_shape, dtype):
    return tuple(jnp.asarray(rng.normal(size=s).astype(np.float32)).astype(dtype)
                 for s in (q_shape, kv_shape, kv_shape))


def _assert_matches_oracle(o, o_ref, dtype):
    """f32 inputs: 2e-5. bf16 inputs, against the f32 oracle of the same
    values: one bf16 ulp of each output (its rounding) plus 1e-5 for f32
    accumulation and p's two bf16 terms; p as one bf16 term misses by 5e-4."""
    if dtype == "float32":
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5)
    else:
        assert o.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(o_ref, np.float32),
                                   rtol=2.0 ** -7, atol=1e-5)


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize(
        "BH,S,Dh,bq,bk,causal,window,softcap,group,fwd", [
            (2, 256, 64, 128, 128, True, None, None, 1, None),
            (1, 256, 128, 64, 64, True, None, 50.0, 1, None),  # gemma2 softcap
            (3, 128, 32, 64, 32, True, 48, None, 1, None),     # sliding window
            (2, 256, 64, 128, 64, False, None, None, 1, None),  # bidirectional
            (1, 128, 64, 128, 128, True, None, None, 1, None),  # single tile
            # GQA, with forward q tiles larger than their K tiles; a window
            # wide enough that tiles between its edge and the diagonal
            # take the unmasked path
            (2, 256, 64, 64, 64, True, 160, None, 2, (64, 32)),
            (1, 256, 128, 64, 64, True, 96, 30.0, 4, (256, 32)),
        ])
    def test_matches_dense_oracle(self, rng, dtype, BH, S, Dh, bq, bk, causal,
                                  window, softcap, group, fwd):
        from repro.kernels.flash_attention import flash_attention_pallas
        q, k, v = _qkv(rng, (BH * group, S, Dh), (BH, S, Dh), dtype)
        fbq, fbk = fwd or (None, None)
        o_k = flash_attention_pallas(q, k, v, block_q=bq, block_k=bk,
                                     causal=causal, window=window,
                                     softcap=softcap, group=group,
                                     fwd_block_q=fbq, fwd_block_k=fbk,
                                     interpret=True)
        f32 = lambda x: jnp.repeat(x.astype(jnp.float32), group, axis=0)
        o_r = ref.flash_attention_ref(q.astype(jnp.float32), f32(k), f32(v),
                                      causal=causal, window=window,
                                      softcap=softcap)
        _assert_matches_oracle(o_k, o_r, dtype)

    def test_block_size_invariance(self, rng):
        from repro.kernels.flash_attention import flash_attention_pallas
        q = jnp.asarray(rng.normal(size=(2, 256, 64)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 256, 64)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 256, 64)).astype(np.float32))
        outs = [np.asarray(flash_attention_pallas(
            q, k, v, block_q=bq, block_k=bk, interpret=True))
            for bq, bk in ((256, 256), (128, 64), (64, 128))]
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
        np.testing.assert_allclose(outs[0], outs[2], atol=1e-5)

    def test_vmem_budget_guard(self):
        from repro.kernels.flash_attention import flash_attention_pallas
        big = jnp.zeros((1, 65536, 128), jnp.float32)
        with pytest.raises(ValueError, match="VMEM"):
            flash_attention_pallas(big, big, big, interpret=True)

    def test_bf16_inputs(self, rng):
        from repro.kernels.flash_attention import flash_attention_pallas
        q, k, v = _qkv(rng, (1, 128, 64), (1, 128, 64), jnp.bfloat16)
        o_k = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                     interpret=True)
        o_r = ref.flash_attention_ref(*(x.astype(jnp.float32)
                                        for x in (q, k, v)))
        _assert_matches_oracle(o_k, o_r, "bfloat16")

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("fwd", [None, (128, 32)])
    def test_fully_masked_rows_are_zero(self, rng, dtype, fwd):
        """Regression for the masked-tile bug: a window that masks EVERY
        key for a q row must yield exactly 0, not exp(-1e30 − (−1e30)) = 1
        renormalized into the mean of V (the pre-fix garbage)."""
        from repro.kernels.flash_attention import flash_attention_pallas
        q, k, v = _qkv(rng, (2, 128, 32), (2, 128, 32), dtype)
        fbq, fbk = fwd or (None, None)
        o = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                   causal=True, window=0, fwd_block_q=fbq,
                                   fwd_block_k=fbk, interpret=True)
        assert np.array_equal(np.asarray(o, np.float32),
                              np.zeros(o.shape, np.float32))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("fwd", [None, (128, 32)])
    @pytest.mark.parametrize("causal,window", [
        (True, None), (True, 48), (True, 160)])
    def test_bounded_loop_bit_parity(self, rng, causal, window, fwd, dtype):
        """The causal/window KV loop bound must be a pure skip: every tile
        it skips is fully masked, so bounded vs exhaustive is BITWISE
        identical (skipped tiles contribute alpha=1, p=0 exactly)."""
        from repro.kernels.flash_attention import flash_attention_pallas
        q, k, v = _qkv(rng, (2, 256, 32), (2, 256, 32), dtype)
        fbq, fbk = fwd or (None, None)
        kw = {"block_q": 64, "block_k": 64, "causal": causal,
              "window": window, "fwd_block_q": fbq, "fwd_block_k": fbk,
              "interpret": True}
        o_b = flash_attention_pallas(q, k, v, bound_loop=True, **kw)
        o_u = flash_attention_pallas(q, k, v, bound_loop=False, **kw)
        assert np.array_equal(np.asarray(o_b, np.float32),
                              np.asarray(o_u, np.float32))

    @pytest.mark.parametrize("group", [2, 4])
    def test_gqa_matches_repeated_kv(self, rng, group):
        """group > 1 folds GQA into the BH axis (kv stream = bh // group)
        without materializing repeated K/V — must match the repeat."""
        from repro.kernels.flash_attention import flash_attention_pallas
        BHkv, S, Dh = 2, 128, 32
        q = jnp.asarray(
            rng.normal(size=(BHkv * group, S, Dh)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(BHkv, S, Dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(BHkv, S, Dh)).astype(np.float32))
        o_g = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                     group=group, interpret=True)
        o_r = ref.flash_attention_ref(q, jnp.repeat(k, group, axis=0),
                                      jnp.repeat(v, group, axis=0))
        np.testing.assert_allclose(np.asarray(o_g), np.asarray(o_r),
                                   atol=2e-5)

    @pytest.mark.parametrize("fwd", [None, (128, 32)])
    @pytest.mark.parametrize("window,softcap,group", [
        (None, None, 1), (48, None, 1), (None, 30.0, 1), (48, 30.0, 2),
    ])
    def test_grad_matches_ref(self, rng, window, softcap, group, fwd):
        """custom_vjp backward (recompute dq/dk/dv kernels) vs autodiff
        through the dense oracle."""
        from repro.kernels.flash_attention import flash_attention_pallas
        BHkv, S, Dh = 2, 128, 32
        q = jnp.asarray(
            rng.normal(size=(BHkv * group, S, Dh)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(BHkv, S, Dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(BHkv, S, Dh)).astype(np.float32))
        dout = jnp.asarray(
            rng.normal(size=(BHkv * group, S, Dh)).astype(np.float32))

        fbq, fbk = fwd or (None, None)

        def loss_k(q, k, v):
            o = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                       window=window, softcap=softcap,
                                       group=group, fwd_block_q=fbq,
                                       fwd_block_k=fbk, interpret=True)
            return jnp.sum(o * dout)

        def loss_r(q, k, v):
            kk = jnp.repeat(k, group, axis=0)
            vv = jnp.repeat(v, group, axis=0)
            o = ref.flash_attention_ref(q, kk, vv, window=window,
                                        softcap=softcap)
            return jnp.sum(o * dout)

        g_k = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_k, g_r, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4,
                err_msg=f"{name} mismatch (window={window}, "
                        f"softcap={softcap}, group={group})")

    def test_dynamic_window_matches_static(self, rng):
        """window as a TRACED int (the model's scan-carried is_local) must
        match the python-int window bit for bit."""
        from repro.kernels.flash_attention import flash_attention_pallas
        q = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(2, 128, 32)).astype(np.float32))

        @jax.jit
        def dyn(q, k, v, w):
            return flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                          window=w, interpret=True)

        o_d = dyn(q, k, v, jnp.int32(48))
        o_s = flash_attention_pallas(q, k, v, block_q=64, block_k=64,
                                     window=48, interpret=True)
        assert np.array_equal(np.asarray(o_d), np.asarray(o_s))
