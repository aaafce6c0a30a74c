"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
output shapes + no NaNs; decode parity; chunked-path equivalence; MoE
routing invariants."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex_core

from repro import configs
from repro.models import decode as Dec
from repro.models import layers as L
from repro.models import model as M

ARCHS = list(configs.CANONICAL_IDS)


def make_batch(cfg, rng, B=4, Seq=32):
    if cfg.family == "audio":
        return {
            "frame_embeds": jnp.asarray(
                rng.normal(size=(B, Seq, cfg.d_model)).astype(np.float32)),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, Seq)),
                                  dtype=jnp.int32)}
    if cfg.family == "vlm":
        st = Seq - cfg.num_patches
        return {
            "patch_embeds": jnp.asarray(
                rng.normal(size=(B, cfg.num_patches, cfg.d_model)).astype(np.float32)),
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, st)),
                                  dtype=jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, st)),
                                  dtype=jnp.int32)}
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, Seq)), dtype=jnp.int32)
    return {"tokens": toks, "labels": toks}


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_loss_grad(self, arch, rng):
        cfg = configs.get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, rng)
        loss, aux = jax.jit(lambda p, b: M.loss_fn(cfg, p, b))(params, batch)
        assert np.isfinite(float(loss))
        grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch)[0])(params)
        for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
            assert np.isfinite(np.asarray(g)).all(), path

    def test_full_config_exactness(self, arch):
        """The registered full config carries the exact published dims."""
        cfg = configs.get_config(arch)
        expected = {
            "stablelm-12b": (40, 5120, 32, 8, 13824, 100352),
            "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
            "qwen1.5-32b": (64, 5120, 40, 40, 27392, 152064),
            "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
            "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
            "kimi-k2-1t-a32b": (61, 7168, 64, 8, 2048, 163840),
            "rwkv6-7b": (32, 4096, 64, 64, 14336, 65536),
            "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
            "internvl2-26b": (48, 6144, 48, 8, 16384, 92553),
            "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        }[arch]
        got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
               cfg.d_ff, cfg.vocab_size)
        assert got == expected

    def test_pooled_features_and_per_example_loss(self, arch, rng):
        cfg = configs.get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, rng)
        f = M.pooled_features(cfg, params, batch)
        pel = M.per_example_loss(cfg, params, batch)
        assert f.shape == (4, cfg.d_model) and pel.shape == (4,)
        assert np.isfinite(np.asarray(f)).all()

    def test_params_logical_structure_matches(self, arch):
        cfg = configs.get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        lg = M.params_logical(cfg, params)
        flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
        is_lg = lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
        flat_l = jax.tree_util.tree_flatten_with_path(lg, is_leaf=is_lg)[0]
        assert len(flat_p) == len(flat_l)
        for (pp, leaf), (_lp, logical) in zip(flat_p, flat_l):
            assert len(logical) == leaf.ndim, (pp, logical, leaf.shape)


class TestDecodeParity:
    @pytest.mark.parametrize("arch", ["stablelm-12b", "gemma2-27b",
                                      "rwkv6-7b", "hymba-1.5b"])
    def test_decode_matches_teacher_forcing(self, arch, rng):
        cfg = configs.get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(1))
        B, Seq, P = 2, 24, 8
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, Seq)),
                             dtype=jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}
        h, _ = M.forward_hiddens(cfg, params, batch)
        ref = M.logits_from_hiddens(cfg, params, h)[:, P - 1:, :]
        logits_p, cache = Dec.prefill(
            cfg, params, {"tokens": tokens[:, :P], "labels": tokens[:, :P]},
            max_seq=Seq)
        outs = [logits_p[:, 0]]
        step = jax.jit(lambda p, c, t: Dec.decode_step(cfg, p, c, t))
        for t in range(P, Seq):
            lg, cache = step(params, cache, tokens[:, t:t + 1])
            outs.append(lg[:, 0])
        dec = jnp.stack(outs, axis=1)
        err = float(jnp.max(jnp.abs(dec.astype(jnp.float32) -
                                    ref.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32))))
        assert err < 0.02 * max(scale, 1.0) + 1e-3, (arch, err, scale)

    def test_moe_decode_dropless(self, rng):
        """Single-token decode is batching-invariant (dropless capacity)."""
        cfg = configs.get_smoke_config("qwen3-moe-235b-a22b",
                                       moe_capacity_factor=4.0)
        params = M.init_params(cfg, jax.random.PRNGKey(1))
        B, Seq, P = 2, 16, 8
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, Seq)),
                             dtype=jnp.int32)
        h, _ = M.forward_hiddens(cfg, params, {"tokens": tokens, "labels": tokens})
        ref = M.logits_from_hiddens(cfg, params, h)[:, P - 1:, :]
        logits_p, cache = Dec.prefill(
            cfg, params, {"tokens": tokens[:, :P], "labels": tokens[:, :P]},
            max_seq=Seq)
        outs = [logits_p[:, 0]]
        for t in range(P, Seq):
            lg, cache = Dec.decode_step(cfg, params, cache, tokens[:, t:t + 1])
            outs.append(lg[:, 0])
        dec = jnp.stack(outs, axis=1)
        err = float(jnp.max(jnp.abs(dec.astype(jnp.float32) -
                                    ref.astype(jnp.float32))))
        assert err < 0.1, err


class TestChunkedPaths:
    @pytest.mark.parametrize("arch", ["stablelm-12b", "gemma2-27b"])
    def test_chunked_attention_and_loss_match_dense(self, arch, rng):
        cfg0 = configs.get_smoke_config(arch, param_dtype="float32")
        cfg1 = dataclasses.replace(cfg0, attn_chunk=16, loss_chunk=16)
        params = M.init_params(cfg0, jax.random.PRNGKey(0))
        batch = make_batch(cfg0, rng, B=2, Seq=64)
        l0, _ = M.loss_fn(cfg0, params, batch)
        l1, _ = M.loss_fn(cfg1, params, batch)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
        g0 = jax.grad(lambda p: M.loss_fn(cfg0, p, batch)[0])(params)
        g1 = jax.grad(lambda p: M.loss_fn(cfg1, p, batch)[0])(params)
        num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)))
        den = sum(float(jnp.sum(a ** 2)) for a in jax.tree_util.tree_leaves(g0))
        assert (num / den) ** 0.5 < 1e-4

    def test_sliding_window_chunked(self, rng):
        """Window masking must survive the chunked path (gemma2 local layers)."""
        cfg0 = configs.get_smoke_config("gemma2-27b", param_dtype="float32",
                                        sliding_window=8)
        cfg1 = dataclasses.replace(cfg0, attn_chunk=16)
        params = M.init_params(cfg0, jax.random.PRNGKey(0))
        batch = make_batch(cfg0, rng, B=2, Seq=64)
        h0, _ = M.forward_hiddens(cfg0, params, batch)
        h1, _ = M.forward_hiddens(cfg1, params, batch)
        np.testing.assert_allclose(np.asarray(h0), np.asarray(h1),
                                   atol=1e-4, rtol=1e-4)


class TestAttnBackends:
    """``attn_backend`` routing: the Pallas flash path (interpret on CPU)
    must agree with the jnp chunked/dense paths in forward AND gradient,
    fall back cleanly on shapes the kernel refuses, and dispatch exactly
    one ``pallas_call`` per layer when it does run."""

    @staticmethod
    def _grad_rel_err(cfg0, cfg1, params, batch):
        g0 = jax.grad(lambda p: M.loss_fn(cfg0, p, batch)[0])(params)
        g1 = jax.grad(lambda p: M.loss_fn(cfg1, p, batch)[0])(params)
        num = sum(float(jnp.sum((a - b) ** 2)) for a, b in zip(
            jax.tree_util.tree_leaves(g0), jax.tree_util.tree_leaves(g1)))
        den = sum(float(jnp.sum(a ** 2))
                  for a in jax.tree_util.tree_leaves(g0))
        return (num / den) ** 0.5

    @pytest.mark.parametrize("arch,extra", [
        ("stablelm-12b", {}),                        # GQA, no window
        ("gemma2-27b", {"sliding_window": 8}),       # GQA + local/global
    ])                                               #   pattern + softcap
    @pytest.mark.parametrize("backend", ["flash", "chunked"])
    def test_backend_matches_dense_fwd_and_grad(self, arch, extra, backend,
                                                rng):
        cfg_d = configs.get_smoke_config(arch, param_dtype="float32",
                                         attn_backend="dense", **extra)
        cfg_b = dataclasses.replace(cfg_d, attn_backend=backend,
                                    attn_chunk=16)
        params = M.init_params(cfg_d, jax.random.PRNGKey(0))
        batch = make_batch(cfg_d, rng, B=2, Seq=64)
        l_d, _ = M.loss_fn(cfg_d, params, batch)
        l_b, _ = M.loss_fn(cfg_b, params, batch)
        np.testing.assert_allclose(float(l_d), float(l_b), rtol=2e-5)
        assert self._grad_rel_err(cfg_d, cfg_b, params, batch) < 5e-4

    def test_non_divisible_seq_falls_back_to_jnp(self, rng):
        """S=60 fits no kernel block size — explicit flash must silently
        take the jnp path and match dense EXACTLY (same code path)."""
        cfg_d = configs.get_smoke_config("stablelm-12b",
                                         param_dtype="float32",
                                         attn_backend="dense",
                                         attn_chunk=None)
        cfg_f = dataclasses.replace(cfg_d, attn_backend="flash")
        assert L.resolve_attn_backend(cfg_f, 60, 60) == "dense"
        params = M.init_params(cfg_d, jax.random.PRNGKey(0))
        batch = make_batch(cfg_d, rng, B=2, Seq=60)
        l_d, _ = M.loss_fn(cfg_d, params, batch)
        l_f, _ = M.loss_fn(cfg_f, params, batch)
        assert float(l_d) == float(l_f)

    def test_routing_dispatch_counts(self, rng):
        """flash traces to exactly one pallas_call per layer; auto stays on
        the jnp paths off-TPU (zero pallas_call on CPU)."""
        def count_pallas(fn, *args):
            n = 0
            def walk(jp):
                nonlocal n
                for eqn in jp.eqns:
                    n += eqn.primitive.name == "pallas_call"
                    for v in eqn.params.values():
                        for sub in (v if isinstance(v, (list, tuple))
                                    else [v]):
                            if isinstance(sub, jex_core.ClosedJaxpr):
                                walk(sub.jaxpr)
                            elif isinstance(sub, jex_core.Jaxpr):
                                walk(sub)
            walk(jax.make_jaxpr(fn)(*args).jaxpr)
            return n

        def mk(backend):
            return M.ModelConfig(
                family="dense", num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
                param_dtype="float32", scan_layers=False,
                attn_backend=backend)

        cfg = mk("flash")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        batch = make_batch(cfg, rng, B=2, Seq=32)
        assert count_pallas(
            lambda p, b: M.loss_fn(cfg, p, b)[0], params, batch) == 2
        cfg_auto = mk("auto")
        n_auto = count_pallas(
            lambda p, b: M.loss_fn(cfg_auto, p, b)[0], params, batch)
        assert n_auto == (2 if jax.default_backend() == "tpu" else 0)

    def test_chunked_fully_masked_row_is_zero(self, rng):
        """Regression for the masked-tile bug in the jnp online softmax: a
        query row whose ENTIRE mask row is false must produce exactly 0,
        not the renormalized mean of V."""
        cfg = configs.get_smoke_config("stablelm-12b", attn_chunk=8)
        B, S, Hkv, g, Dh = 1, 16, 2, 2, 8
        qg = jnp.asarray(rng.normal(size=(B, S, Hkv, g, Dh)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(B, S, Hkv, Dh)).astype(np.float32))
        mask = np.tril(np.ones((S, S), bool))[None]
        mask[:, 0, :] = False                       # row 0: no visible keys
        out = np.asarray(L._chunked_attention(cfg, qg, k, v,
                                              jnp.asarray(mask)))
        assert np.array_equal(out[0, 0], np.zeros_like(out[0, 0]))
        assert np.isfinite(out).all()
        assert np.abs(out[0, 1:]).max() > 0


class TestMoEInvariants:
    def _setup(self, rng, cf=8.0):
        cfg = configs.get_smoke_config("qwen3-moe-235b-a22b",
                                       moe_capacity_factor=cf)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        p = jax.tree_util.tree_map(lambda x: x[0], params["blocks"]["moe"])
        x = jnp.asarray(rng.normal(size=(2, 32, cfg.d_model)).astype(np.float32))
        return cfg, p, x

    def test_combine_mass_conservation_no_drops(self, rng):
        """With generous capacity, Σ_e,c combine[t] == 1 for every token."""
        cfg, p, x = self._setup(rng, cf=8.0)
        B, S, D = x.shape
        E, k = cfg.num_experts, cfg.num_experts_per_tok
        gs = min(cfg.moe_group_size, B * S)
        xt = x.reshape(-1, gs, D)
        logits = jnp.einsum("gsd,de->gse", xt, p["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        gate, ids = jax.lax.top_k(probs, k)
        # run the layer and check output is a convex-ish combination: use the
        # public API — mass conservation shows as output magnitude stability
        out = L.moe(cfg, p, x.astype(cfg.dtype))
        assert np.isfinite(np.asarray(out)).all()
        out_dropless = L.moe(cfg, p, x.astype(cfg.dtype), dropless=True)
        np.testing.assert_allclose(np.asarray(out).astype(np.float32),
                                   np.asarray(out_dropless).astype(np.float32),
                                   atol=2e-2)

    def test_capacity_drops_reduce_output(self, rng):
        """Tiny capacity must drop tokens (outputs differ from dropless)."""
        cfg, p, x = self._setup(rng, cf=0.25)
        out_small = np.asarray(L.moe(cfg, p, x.astype(cfg.dtype))).astype(np.float32)
        out_free = np.asarray(L.moe(cfg, p, x.astype(cfg.dtype),
                                    dropless=True)).astype(np.float32)
        assert np.abs(out_small - out_free).max() > 1e-4


class TestLayerPatterns:
    def test_gemma2_local_global_pattern(self):
        cfg = configs.get_config("gemma2-27b")
        pat = cfg.is_local_pattern()
        assert pat[0] and not pat[1] and pat[2] and len(pat) == 46

    def test_hymba_global_islands(self):
        cfg = configs.get_config("hymba-1.5b")
        pat = cfg.is_local_pattern()
        assert not pat[0] and not pat[15] and not pat[31]
        assert pat[1] and pat[30]

    def test_sliding_window_blocks_long_range(self, rng):
        """A token beyond the window must not influence a local-only model."""
        cfg = configs.get_smoke_config(
            "gemma2-27b", layer_pattern=("local",), sliding_window=4,
            param_dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = rng.integers(2, cfg.vocab_size, (1, 24)).astype(np.int32)
        toks2 = toks.copy()
        toks2[0, 0] = (toks2[0, 0] + 1) % cfg.vocab_size   # perturb far past
        h1, _ = M.forward_hiddens(cfg, params, {"tokens": jnp.asarray(toks),
                                                "labels": jnp.asarray(toks)})
        h2, _ = M.forward_hiddens(cfg, params, {"tokens": jnp.asarray(toks2),
                                                "labels": jnp.asarray(toks2)})
        # with 2 local layers of window 4, position 23 sees back to ~16 > 0
        np.testing.assert_allclose(np.asarray(h1)[0, -1], np.asarray(h2)[0, -1],
                                   atol=1e-5)
