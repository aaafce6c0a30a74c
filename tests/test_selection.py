"""Selection engine: registry resolution, sampler contracts, vmapped
multi-batch == single-batch loop, shard_map data-parallel == single-device
reference, core.graft compatibility shim."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import run_forced_devices
from repro.launch.mesh import make_mesh
from repro.selection import (GraftConfig, Sampler, SelectionInputs,
                             SelectionState, available, engine, get_sampler,
                             init_state, register)

CFG = GraftConfig(rset=(2, 4, 8), eps=0.25)


def _inputs(rng, K=32, d=24, r=8):
    V = jnp.asarray(rng.normal(size=(K, r)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
    return V, G, jnp.mean(G, axis=1)


class TestRegistry:
    def test_default_samplers_registered(self):
        names = available()
        for expected in ("graft", "random", "loss_topk", "full",
                         "el2n", "gradmatch", "craig", "glister"):
            assert expected in names

    def test_resolution_returns_sampler(self):
        smp = get_sampler("graft")
        assert isinstance(smp, Sampler) and smp.name == "graft"
        # a Sampler instance passes through unchanged
        assert get_sampler(smp) is smp

    def test_unknown_sampler_error_lists_available(self):
        with pytest.raises(KeyError, match="unknown sampler 'bogus'"):
            get_sampler("bogus")
        with pytest.raises(KeyError, match="graft"):
            get_sampler("bogus")

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register(get_sampler("graft"))

    def test_custom_registration(self):
        def fn(cfg, inputs, step):
            return init_state(cfg, inputs.V.shape[0])._replace(step=step)
        try:
            register(Sampler("custom_test_only", fn))
            st, _ = engine.select_batch(CFG, "custom_test_only",
                                        *_inputs(np.random.default_rng(0)))
            assert int(st.rank) == CFG.r_max
        finally:
            from repro.selection import registry as reg
            reg._REGISTRY.pop("custom_test_only", None)


class TestSamplerContracts:
    @pytest.mark.parametrize("name", ["graft", "random", "loss_topk", "full",
                                      "el2n", "gradmatch", "craig", "glister",
                                      "streaming_graft"])
    def test_state_invariants(self, rng, name):
        K = 32
        V, G, gb = _inputs(rng, K=K)
        scores = jnp.asarray(rng.random(K).astype(np.float32))
        st, _ = engine.select_batch(CFG, name, V, G, gb, scores=scores)
        assert isinstance(st, SelectionState)
        piv = np.asarray(st.pivots)
        w = np.asarray(st.weights)
        assert piv.shape == (CFG.r_max,) and w.shape == (CFG.r_max,)
        assert piv.min() >= 0 and piv.max() < K
        active = piv[w > 0]
        assert len(set(active.tolist())) == len(active), "active pivots repeat"
        np.testing.assert_allclose(w.sum(), 1.0, atol=1e-5)
        assert 1 <= int(st.rank) <= CFG.r_max
        assert 0.0 <= float(st.last_error) <= 1.0 + 1e-6

    @pytest.mark.parametrize("name", ["loss_topk", "el2n"])
    def test_score_samplers_require_scores(self, rng, name):
        """Score-consuming samplers fail LOUDLY without scores — via the
        engine AND via Sampler.select directly — instead of silently
        selecting on a zeros placeholder."""
        V, G, gb = _inputs(rng)
        with pytest.raises(ValueError, match=f"sampler '{name}' requires "
                                             "SelectionInputs.scores"):
            engine.select_batch(CFG, name, V, G, gb)
        with pytest.raises(ValueError, match="scores"):
            get_sampler(name).select(CFG, SelectionInputs(V, G, gb))

    def test_declared_requirements_enforced(self, rng):
        """Every registered sampler's declared optional-input requirements
        (needs_scores AND needs_key) must actually be validated by
        Sampler.select — not just documented."""
        V, G, gb = _inputs(rng)
        scores = jnp.asarray(rng.random(V.shape[0]).astype(np.float32))
        key = jax.random.PRNGKey(0)
        for name in available():
            smp = get_sampler(name)
            if smp.needs_scores:
                with pytest.raises(ValueError, match="scores"):
                    smp.select(CFG, SelectionInputs(V, G, gb, None, key))
            if smp.needs_key:
                with pytest.raises(ValueError, match="key"):
                    smp.select(CFG, SelectionInputs(V, G, gb, scores, None))
            # with both supplied, every sampler must select
            st, _ = smp.select(CFG, SelectionInputs(V, G, gb, scores, key))
            assert isinstance(st, SelectionState)

    def test_random_requires_key_via_select(self, rng):
        V, G, gb = _inputs(rng)
        assert get_sampler("random").needs_key
        with pytest.raises(ValueError, match="key"):
            get_sampler("random").select(CFG, SelectionInputs(V, G, gb))

    def test_loss_topk_picks_highest_scores(self, rng):
        K = 16
        V, G, gb = _inputs(rng, K=K)
        scores = jnp.asarray(np.arange(K, dtype=np.float32))
        st, _ = engine.select_batch(CFG, "loss_topk", V, G, gb, scores=scores)
        assert set(np.asarray(st.pivots).tolist()) == set(range(K - CFG.r_max, K))

    def test_full_is_identity_prefix(self, rng):
        V, G, gb = _inputs(rng)
        st, _ = engine.select_batch(CFG, "full", V, G, gb)
        assert np.array_equal(np.asarray(st.pivots), np.arange(CFG.r_max))

    def test_random_deterministic_in_key(self, rng):
        V, G, gb = _inputs(rng)
        key = jax.random.PRNGKey(7)
        a, _ = engine.select_batch(CFG, "random", V, G, gb, key=key)
        b, _ = engine.select_batch(CFG, "random", V, G, gb, key=key)
        assert np.array_equal(np.asarray(a.pivots), np.asarray(b.pivots))

    def test_masked_weight_error_matches_active_subspace(self, rng):
        """Regression: gradmatch clips some weights to 0; last_error must be
        the projection error over ONLY the active columns, not a QR of the
        zero-masked matrix (whose completion directions fake extra span)."""
        K, d = 64, 16
        cfg = GraftConfig(rset=(4, 8, 16), eps=0.25)
        V = jnp.asarray(rng.normal(size=(K, 16)).astype(np.float32))
        G = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
        gb = jnp.mean(G, axis=1)
        st, _ = engine.select_batch(cfg, "gradmatch", V, G, gb)
        w = np.asarray(st.weights)
        assert (w == 0).any(), "seed no longer exercises clipped weights"
        act = np.asarray(st.pivots)[w > 0]
        q, _ = np.linalg.qr(np.asarray(G)[:, act])
        g = np.asarray(gb)
        true_err = np.clip(1 - ((q.T @ g) ** 2).sum() / (g * g).sum(), 0, 1)
        np.testing.assert_allclose(float(st.last_error), true_err, atol=2e-3)

    def test_graft_matches_direct_call(self, rng):
        from repro.selection.graft import graft_select
        V, G, gb = _inputs(rng)
        via_engine, _ = engine.select_batch(CFG, "graft", V, G, gb)
        direct = graft_select(CFG, V, G, gb, jnp.int32(0))
        assert np.array_equal(np.asarray(via_engine.pivots), np.asarray(direct.pivots))
        assert int(via_engine.rank) == int(direct.rank)


class TestVmappedMultiBatch:
    @pytest.mark.parametrize("name", ["graft", "el2n", "random", "loss_topk"])
    def test_equals_python_loop(self, rng, name):
        B, K, d = 4, 24, 16
        Vs = jnp.asarray(rng.normal(size=(B, K, CFG.r_max)).astype(np.float32))
        Gs = jnp.asarray(rng.normal(size=(B, d, K)).astype(np.float32))
        gbs = jnp.mean(Gs, axis=2)
        scores = jnp.asarray(rng.random((B, K)).astype(np.float32))
        keys = jax.random.split(jax.random.PRNGKey(3), B)
        multi, _ = engine.select_multi_batch(CFG, name, Vs, Gs, gbs,
                                             scores=scores, keys=keys)
        assert multi.pivots.shape == (B, CFG.r_max)
        for b in range(B):
            single, _ = engine.select_batch(CFG, name, Vs[b], Gs[b], gbs[b],
                                            scores=scores[b], key=keys[b])
            np.testing.assert_array_equal(np.asarray(multi.pivots[b]),
                                          np.asarray(single.pivots))
            np.testing.assert_allclose(np.asarray(multi.weights[b]),
                                       np.asarray(single.weights), atol=1e-6)
            assert int(multi.rank[b]) == int(single.rank)
            np.testing.assert_allclose(float(multi.last_error[b]),
                                       float(single.last_error), atol=1e-5)

    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_graft_use_pallas_matches_jnp_reference(self, rng, use_pallas):
        """Regression: use_pallas under the multi-batch engine used to vmap
        a grid=() pallas_call (no Mosaic lowering); it now dispatches ONE
        grid=(B,) fused kernel. Both settings must agree with the jnp
        single-batch loop."""
        B, K, d = 3, 24, 16
        cfg = GraftConfig(rset=(2, 4, 8), eps=0.25, use_pallas=use_pallas)
        Vs = jnp.asarray(rng.normal(size=(B, K, cfg.r_max)).astype(np.float32))
        Gs = jnp.asarray(rng.normal(size=(B, d, K)).astype(np.float32))
        gbs = jnp.mean(Gs, axis=2)
        multi, _ = engine.select_multi_batch(cfg, "graft", Vs, Gs, gbs)
        for b in range(B):
            single, _ = engine.select_batch(CFG, "graft", Vs[b], Gs[b], gbs[b])
            np.testing.assert_array_equal(np.asarray(multi.pivots[b]),
                                          np.asarray(single.pivots))
            assert int(multi.rank[b]) == int(single.rank)
            np.testing.assert_allclose(np.asarray(multi.weights[b]),
                                       np.asarray(single.weights), atol=1e-6)
            np.testing.assert_allclose(float(multi.last_error[b]),
                                       float(single.last_error), atol=1e-5)
            np.testing.assert_allclose(float(multi.alignment[b]),
                                       float(single.alignment), atol=1e-5)

    def test_microbatch_stack_feeds_vmapped_path(self, rng):
        from repro.data import DataConfig, SyntheticLM
        data = SyntheticLM(DataConfig(vocab_size=64, seq_len=8, global_batch=4))
        stack = data.microbatch_stack(step=3, num_micro=5)
        assert stack["tokens"].shape == (5, 4, 8)
        np.testing.assert_array_equal(stack["tokens"][2], data.batch_at(5)["tokens"])


class TestShardedSelection:
    def test_single_device_mesh_matches_reference(self, rng):
        V, G, gb = _inputs(rng)
        mesh = make_mesh((1, 1), ("data", "model"))
        sharded, _ = engine.select_sharded(CFG, mesh, V, G)
        single, _ = engine.select_batch(CFG, "graft", V, G, gb)
        np.testing.assert_array_equal(np.asarray(sharded.pivots),
                                      np.asarray(single.pivots))
        assert int(sharded.rank) == int(single.rank)
        np.testing.assert_allclose(float(sharded.last_error),
                                   float(single.last_error), atol=1e-5)
        np.testing.assert_allclose(float(sharded.alignment),
                                   float(single.alignment), atol=1e-5)

    def test_selector_is_cached(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        a = engine.make_sharded_selector(CFG, mesh)
        b = engine.make_sharded_selector(CFG, mesh)
        assert a is b, "sharded selector must not re-trace per call"

    def test_input_validation(self, rng):
        V, G, _ = _inputs(rng, K=6, r=8)
        mesh = make_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError, match="r_max"):
            engine.select_sharded(CFG, mesh, V, G)

    def test_no_data_axis_rejected(self, rng):
        V, G, _ = _inputs(rng)
        mesh = make_mesh((1,), ("model",))
        with pytest.raises(ValueError, match="no axis"):
            engine.select_sharded(CFG, mesh, V, G)

    def test_multi_device_mesh_matches_reference(self):
        """4 forced CPU devices (fresh subprocess — device count is fixed at
        backend init): every shard holds a replica of the same batch; the
        sharded path must reproduce the single-device pivots per shard and
        the psum'd global rank decision must equal the single-device one."""
        code = """
            import numpy as np, jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from repro.selection import GraftConfig, engine
            assert len(jax.devices()) == 4
            rng = np.random.default_rng(0)
            K, d, n = 24, 16, 4
            cfg = GraftConfig(rset=(2, 4, 8), eps=0.2)
            V1 = jnp.asarray(rng.normal(size=(K, 8)).astype(np.float32))
            G1 = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
            single, _ = engine.select_batch(cfg, "graft", V1, G1, jnp.mean(G1, axis=1))
            mesh = make_mesh((2, 2), ("data", "model"))  # 2-way data sharding
            n_sh = 2
            sharded, _ = engine.select_sharded(cfg, mesh,
                                               jnp.tile(V1, (n_sh, 1)),
                                               jnp.tile(G1, (1, n_sh)))
            piv = np.asarray(sharded.pivots).reshape(n_sh, cfg.r_max)
            for s in range(n_sh):
                assert np.array_equal(piv[s] - s * K, np.asarray(single.pivots)), s
            assert int(sharded.rank) == int(single.rank)
            np.testing.assert_allclose(float(sharded.last_error),
                                       float(single.last_error), atol=1e-5)
            np.testing.assert_allclose(float(sharded.alignment),
                                       float(single.alignment), atol=1e-5)
            np.testing.assert_allclose(np.asarray(sharded.weights).sum(), 1.0,
                                       atol=1e-5)
            print("SHARDED_OK")
        """
        assert "SHARDED_OK" in run_forced_devices(code, devices=4)


class TestSamplerV2Conformance:
    """Protocol conformance for EVERY registered sampler: init_carry/select
    round-trip on the single-batch, vmapped multi-batch, and forced-4-device
    shard_map paths, plus bit-identity of legacy (stateless) samplers with
    their pre-v2 ``fn``."""

    def _spec_inputs(self, rng, K=24, d=16):
        V = jnp.asarray(rng.normal(size=(K, CFG.r_max)).astype(np.float32))
        G = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
        scores = jnp.asarray(rng.random(K).astype(np.float32))
        key = jax.random.PRNGKey(11)
        return V, G, jnp.mean(G, axis=1), scores, key

    @pytest.mark.parametrize("name", sorted(available()))
    def test_select_roundtrips_carry(self, rng, name):
        from repro.selection import CarrySpec
        smp = get_sampler(name)
        V, G, gb, scores, key = self._spec_inputs(rng)
        spec = CarrySpec(batch_size=int(V.shape[0]), grad_dim=int(G.shape[0]))
        carry0 = smp.init_carry(CFG, spec)
        st, carry1 = smp.select(CFG, SelectionInputs(V, G, gb, scores, key),
                                carry0)
        assert isinstance(st, SelectionState)
        assert (jax.tree_util.tree_structure(carry1)
                == jax.tree_util.tree_structure(carry0))
        for a, b in zip(jax.tree_util.tree_leaves(carry0),
                        jax.tree_util.tree_leaves(carry1)):
            assert a.shape == b.shape and a.dtype == b.dtype
        if not smp.stateful:
            assert not jax.tree_util.tree_leaves(carry1), (
                f"stateless sampler '{name}' returned a non-empty carry")
        # second hop: the returned carry feeds straight back in
        st2, _ = smp.select(CFG, SelectionInputs(V, G, gb, scores, key),
                            carry1, step=1)
        assert isinstance(st2, SelectionState)

    @pytest.mark.parametrize("name", sorted(available()))
    def test_legacy_samplers_bit_identical_to_fn(self, rng, name):
        """The v2 protocol is a pure superset: a stateless sampler routed
        through select/engine must reproduce its pre-v2 ``fn`` output
        bit-for-bit."""
        smp = get_sampler(name)
        if smp.stateful:
            pytest.skip("stateful sampler has no pre-v2 fn")
        V, G, gb, scores, key = self._spec_inputs(rng)
        inputs = SelectionInputs(V, G, gb, scores, key)
        # eager vs eager: Sampler.select is a zero-cost shim around fn
        direct = smp.fn(CFG, inputs, jnp.int32(0))
        via_select, carry = smp.select(CFG, inputs)
        # jitted vs jitted: the carry-threading engine compiles to the same
        # program as a bare jit of fn (the {} carry is leafless)
        direct_jit = jax.jit(smp.fn, static_argnums=0)(CFG, inputs,
                                                       jnp.int32(0))
        via_engine, _ = engine.select_batch(CFG, name, V, G, gb,
                                            scores=scores, key=key)
        for field in SelectionState._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(direct, field)),
                np.asarray(getattr(via_select, field)), err_msg=field)
            np.testing.assert_array_equal(
                np.asarray(getattr(direct_jit, field)),
                np.asarray(getattr(via_engine, field)), err_msg=field)
        assert not jax.tree_util.tree_leaves(carry)

    @pytest.mark.parametrize("name", sorted(available()))
    def test_vmapped_path_all_samplers(self, rng, name):
        B, K, d = 3, 24, 16
        Vs = jnp.asarray(rng.normal(size=(B, K, CFG.r_max)).astype(np.float32))
        Gs = jnp.asarray(rng.normal(size=(B, d, K)).astype(np.float32))
        gbs = jnp.mean(Gs, axis=2)
        scores = jnp.asarray(rng.random((B, K)).astype(np.float32))
        keys = jax.random.split(jax.random.PRNGKey(5), B)
        multi, carry = engine.select_multi_batch(CFG, name, Vs, Gs, gbs,
                                                 scores=scores, keys=keys)
        assert multi.pivots.shape == (B, CFG.r_max)
        assert multi.weights.shape == (B, CFG.r_max)
        for leaf in jax.tree_util.tree_leaves(carry):
            assert leaf.shape[0] == B, "carry must stack along the batch axis"
        # round-trip: the stacked carry feeds the next refresh
        multi2, _ = engine.select_multi_batch(CFG, name, Vs, Gs, gbs,
                                              scores=scores, keys=keys,
                                              carry=carry, step=1)
        assert multi2.pivots.shape == (B, CFG.r_max)

    def test_forced_4device_shardmap_all_samplers(self):
        """Every registered sampler runs under the sharded selector on a
        forced-4-device CPU mesh and round-trips its carry (fresh subprocess:
        device count is fixed at backend init)."""
        code = """
            import numpy as np, jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from repro.selection import GraftConfig, available, engine, get_sampler
            assert len(jax.devices()) == 4
            rng = np.random.default_rng(0)
            K, d = 16, 12
            cfg = GraftConfig(rset=(2, 4), eps=0.2)
            mesh = make_mesh((2, 2), ("data", "model"))
            n_sh = 2
            V = jnp.asarray(rng.normal(size=(n_sh * K, cfg.r_max)).astype(np.float32))
            G = jnp.asarray(rng.normal(size=(d, n_sh * K)).astype(np.float32))
            scores = jnp.asarray(rng.random(n_sh * K).astype(np.float32))
            for name in available():
                state, carry = engine.select_sharded(cfg, mesh, V, G,
                                                     sampler=name, scores=scores)
                piv = np.asarray(state.pivots)
                assert piv.shape == (n_sh * cfg.r_max,), (name, piv.shape)
                assert piv.min() >= 0 and piv.max() < n_sh * K, name
                np.testing.assert_allclose(np.asarray(state.weights).sum(),
                                           1.0, atol=1e-5, err_msg=name)
                smp = get_sampler(name)
                state2, carry2 = engine.select_sharded(cfg, mesh, V, G,
                                                       sampler=name,
                                                       scores=scores,
                                                       carry=carry, step=1)
                assert (jax.tree_util.tree_structure(carry2)
                        == jax.tree_util.tree_structure(carry)), name
                if not smp.stateful:
                    assert not jax.tree_util.tree_leaves(carry), name
            print("CONFORMANCE_OK")
        """
        assert "CONFORMANCE_OK" in run_forced_devices(code, devices=4)


class TestStreamingGraft:
    """The frequent-directions sketch reservoir behind ``streaming_graft``."""

    def _inputs(self, rng, K=24, d=16):
        V = jnp.asarray(rng.normal(size=(K, CFG.r_max)).astype(np.float32))
        G = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
        return V, G, jnp.mean(G, axis=1)

    def test_carry_shapes_and_footprint(self):
        from repro.selection import CarrySpec
        from repro.selection.streaming import SketchCarry, init_sketch_carry
        cfg = dataclasses.replace(CFG, sketch_rows=8)
        carry = init_sketch_carry(cfg, CarrySpec(batch_size=24, grad_dim=16))
        assert isinstance(carry, SketchCarry)
        assert carry.sketch.shape == (8, 16)      # fixed (L, d), K-independent
        assert carry.g_ema.shape == (16,)
        assert carry.count.shape == () and carry.agreement.shape == ()
        assert all(leaf.dtype == jnp.float32 for leaf in carry)

    def test_first_refresh_matches_per_batch_graft(self, rng):
        """Empty reservoir ⇒ agreement 0 ⇒ the blended target is exactly the
        per-batch mean gradient: refresh #1 is bit-identical to plain
        GRAFT."""
        V, G, gb = self._inputs(rng)
        stream, carry = engine.select_batch(CFG, "streaming_graft", V, G, gb)
        plain, _ = engine.select_batch(CFG, "graft", V, G, gb)
        for field in ("pivots", "weights", "rank", "last_error"):
            np.testing.assert_array_equal(
                np.asarray(getattr(stream, field)),
                np.asarray(getattr(plain, field)), err_msg=field)
        assert float(carry.count) == 1.0

    def test_reservoir_evolves_and_modulates_selection(self, rng):
        """Feeding the same batch twice drives agreement → 1 (the sketch
        spans the batch gradients); a live reservoir may change the blended
        target while the selection stays well-formed."""
        V, G, gb = self._inputs(rng)
        smp = get_sampler("streaming_graft")
        _, c1 = engine.select_batch(CFG, "streaming_graft", V, G, gb)
        st2, c2 = engine.select_batch(CFG, "streaming_graft", V, G, gb,
                                      carry=c1, step=1)
        assert float(c2.count) == 2.0
        assert float(c2.agreement) > 0.9, (
            "repeated batch must be captured by the sketch")
        assert 0.0 <= float(c2.agreement) <= 1.0
        assert smp.stateful
        w = np.asarray(st2.weights)
        np.testing.assert_allclose(w.sum(), 1.0, atol=1e-5)

    def test_sketch_rows_bound_holds_under_many_updates(self, rng):
        """The reservoir footprint is CONSTANT: 20 refreshes over random
        batches never grow the carry beyond (sketch_rows, d)."""
        cfg = dataclasses.replace(CFG, sketch_rows=4)
        V, G, gb = self._inputs(rng)
        carry = None
        for step in range(20):
            G = jnp.asarray(rng.normal(size=G.shape).astype(np.float32))
            _, carry = engine.select_batch(cfg, "streaming_graft", V, G,
                                           jnp.mean(G, axis=1),
                                           carry=carry, step=step)
        assert carry.sketch.shape == (4, 16)
        assert float(carry.count) == 20.0
        assert bool(jnp.all(jnp.isfinite(carry.sketch)))

    def test_carry_checkpoint_roundtrip_bit_exact(self, rng, tmp_path):
        """The reservoir survives a save/restore cycle bit-exactly — the
        invariant the chaos ``streaming_nan_rollback`` scenario leans on."""
        from repro.checkpoint import CheckpointManager
        V, G, gb = self._inputs(rng)
        _, c1 = engine.select_batch(CFG, "streaming_graft", V, G, gb)
        _, c2 = engine.select_batch(CFG, "streaming_graft", V, G, gb,
                                    carry=c1, step=1)
        state = {"step": jnp.int32(2), "sampler_carry": c2}
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(2, state)
        mgr.wait()
        restored = mgr.restore(2, state)
        for a, b in zip(jax.tree_util.tree_leaves(c2),
                        jax.tree_util.tree_leaves(restored["sampler_carry"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_streaming_via_graft_train_step(self, rng):
        """End to end: ``--train.sampler=streaming_graft`` threads the carry
        through the jitted train step — it advances ONLY on refresh steps."""
        from repro import configs
        from repro.launch import steps as steps_lib
        from repro.launch.specs import default_train_config
        mcfg = configs.get_smoke_config("minicpm-2b")
        tcfg = default_train_config("minicpm-2b", batch=8)
        tcfg = dataclasses.replace(
            tcfg, sampler="streaming_graft",
            graft=dataclasses.replace(tcfg.graft, refresh_every=2))
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (8, 16)),
                           dtype=jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        state = steps_lib.init_train_state(mcfg, tcfg, jax.random.PRNGKey(2),
                                           batch_size=8)
        assert float(state["sampler_carry"].count) == 0.0
        state, metrics = steps_lib.graft_train_step(mcfg, tcfg, state, batch)
        assert np.isfinite(metrics["loss"])
        assert float(state["sampler_carry"].count) == 1.0   # step 0 refreshes
        state, _ = steps_lib.graft_train_step(mcfg, tcfg, state, batch)
        assert float(state["sampler_carry"].count) == 1.0   # step 1 does not


class TestCompatShim:
    def test_core_graft_reexports_selection(self):
        from repro.core import graft as core_graft
        from repro.selection import base as sel_base
        from repro.selection import graft as sel_graft
        assert core_graft.GraftConfig is sel_base.GraftConfig
        assert core_graft.GraftState is sel_base.SelectionState
        assert core_graft.graft_select is sel_graft.graft_select
        assert core_graft.init_state is sel_base.init_state
        assert core_graft.maybe_refresh is sel_graft.maybe_refresh

    def test_core_package_still_exports_graft_names(self):
        import repro.core as core
        assert core.GraftConfig is GraftConfig
        cfg = core.GraftConfig(rset=(2, 4))
        assert cfg.r_max == 4


class TestSourcesRegistry:
    """Feature-extractor / gradient-source registries (selection inputs)."""

    def test_builtins_registered(self):
        from repro.selection import available_features, available_grad_sources
        for f in ("svd", "sketch_svd", "pca_sketch", "pooled_raw", "ica"):
            assert f in available_features()
        for g in ("probe", "logit_embed", "full"):
            assert g in available_grad_sources()

    def test_unknown_names_error_with_available(self):
        from repro.selection import resolve_features, resolve_grad_source
        with pytest.raises(KeyError, match="unknown feature extractor"):
            resolve_features("bogus")
        with pytest.raises(KeyError, match="unknown grad source"):
            resolve_grad_source("bogus")

    @pytest.mark.parametrize("name", ["svd", "sketch_svd", "pca_sketch",
                                      "pooled_raw"])
    def test_feature_extractors_shapes_and_order(self, rng, name):
        from repro.selection import resolve_features
        K, M, R = 16, 48, 4
        A = jnp.asarray(rng.normal(size=(K, M)).astype(np.float32))
        V = resolve_features(name)(A, R)
        assert V.shape == (K, R)
        assert bool(jnp.all(jnp.isfinite(V)))
        # relevance ordering: column energy must be non-increasing
        energy = np.asarray(jnp.sum(V * V, axis=0))
        assert np.all(energy[:-1] >= energy[1:] - 1e-4), energy

    def test_pooled_raw_pads_when_narrow(self, rng):
        from repro.selection import resolve_features
        A = jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))
        V = resolve_features("pooled_raw")(A, 6)
        assert V.shape == (8, 6)
        assert bool(jnp.all(V[:, 3:] == 0.0))

    def test_grad_sources_through_selection_inputs(self, rng):
        """selection_inputs resolves feature/grad modes from GraftConfig —
        every registered combination must produce well-shaped V/G/scores."""
        import dataclasses as dc
        from repro import configs
        from repro.launch import steps as steps_lib
        from repro.launch.specs import default_train_config
        from repro.models import model as M
        mcfg = configs.get_smoke_config("minicpm-2b")
        params = M.init_params(mcfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (8, 32)),
                           dtype=jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        for fm in ("svd", "sketch_svd", "pca_sketch", "pooled_raw"):
            for gm in ("probe", "logit_embed"):
                tcfg = default_train_config("minicpm-2b", batch=8,
                                            feature_mode=fm, grad_mode=gm)
                assert tcfg.graft.feature_mode == fm
                V, G, gbar, scores = steps_lib.selection_inputs(
                    mcfg, tcfg, params, batch)
                assert V.shape == (8, tcfg.graft.r_max)
                assert G.shape[1] == 8 and gbar.shape == (G.shape[0],)
                assert scores.shape == (8,)
                assert bool(jnp.all(jnp.isfinite(V)))
                assert bool(jnp.all(jnp.isfinite(G)))
        del dc

    def test_custom_registration_and_overwrite_guard(self):
        from repro.selection import sources
        fx = sources.FeatureExtractor("custom_feat_test",
                                      lambda A, r: A[:, :r])
        try:
            sources.register_features(fx)
            assert sources.resolve_features("custom_feat_test") is fx
            with pytest.raises(ValueError, match="already registered"):
                sources.register_features(fx)
        finally:
            sources._FEATURES.pop("custom_feat_test", None)

    def test_ica_mode_reachable_from_graft_config(self, rng):
        """ROADMAP gap closed: feature_mode='ica' resolves through the
        registry and selection_inputs, with kurtosis-ordered columns."""
        from repro import configs
        from repro.launch import steps as steps_lib
        from repro.launch.specs import default_train_config
        from repro.models import model as M
        from repro.selection import resolve_features
        K, M_, R = 16, 48, 4
        A = jnp.asarray(rng.normal(size=(K, M_)).astype(np.float32))
        V = resolve_features("ica")(A, R)
        assert V.shape == (K, R) and bool(jnp.all(jnp.isfinite(V)))
        mcfg = configs.get_smoke_config("minicpm-2b")
        params = M.init_params(mcfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (8, 16)),
                           dtype=jnp.int32)
        tcfg = default_train_config("minicpm-2b", batch=8,
                                    feature_mode="ica")
        V, G, gbar, scores = steps_lib.selection_inputs(
            mcfg, tcfg, params, {"tokens": toks, "labels": toks})
        assert V.shape == (8, tcfg.graft.r_max)
        assert bool(jnp.all(jnp.isfinite(V)))

    def test_full_grad_source_exact_parity(self, rng):
        """grad_mode='full' (per_sample_grads_full behind the GradSource
        protocol) on a tiny f32 model: the mean per-sample gradient must
        equal the batch-loss gradient (linearity of the mean-CE loss), and
        the per-sample rows restricted to the lm_head leaf must match the
        analytic head gradient (1/S)·Σ_s h_s (p−y)_sᵀ."""
        from repro import configs
        from repro.models import model as M
        from repro.selection import sources
        mcfg = configs.get_smoke_config("stablelm-12b")
        assert not mcfg.tie_embeddings
        mcfg = dataclasses.replace(mcfg, param_dtype="float32", num_layers=1)
        params = M.init_params(mcfg, jax.random.PRNGKey(1))
        K, S = 4, 8
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (K, S)),
                           dtype=jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        h, _ = M.forward_hiddens(mcfg, params, batch)
        logits = M.logits_from_hiddens(mcfg, params, h)
        src = sources.resolve_grad_source("full")
        assert src.needs_params and src.needs_batch
        with pytest.raises(ValueError, match="requires GradSourceInputs.batch"):
            src(sources.GradSourceInputs(logits=logits, labels=toks,
                                         hiddens=h, mcfg=mcfg, params=params))
        emb = src(sources.GradSourceInputs(
            logits=logits, labels=toks, hiddens=h, mcfg=mcfg, params=params,
            batch=batch))                                   # (K, |Θ|)
        num_params = sum(int(np.prod(l.shape)) for l in
                         jax.tree_util.tree_leaves(params))
        assert emb.shape == (K, num_params)
        # 1) mean of per-sample grads == batch gradient
        gref = jax.grad(lambda p: M.loss_fn(mcfg, p, batch)[0])(params)
        flat_ref = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                    for l in jax.tree_util.tree_leaves(gref)])
        np.testing.assert_allclose(np.asarray(jnp.mean(emb, axis=0)),
                                   np.asarray(flat_ref), atol=1e-5)
        # 2) per-sample lm_head rows == analytic (1/S)·h (p−y)ᵀ
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        offset = 0
        head_slice = None
        for path, leaf in leaves:
            n = int(np.prod(leaf.shape))
            if "lm_head" in "/".join(str(getattr(p, "key", p)) for p in path):
                head_slice = (offset, offset + n, leaf.shape)
            offset += n
        assert head_slice is not None
        lo, hi, shape = head_slice
        p_soft = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        onehot = jax.nn.one_hot(toks, mcfg.vocab_size, dtype=jnp.float32)
        analytic = jnp.einsum("ksd,ksv->kdv", h.astype(jnp.float32),
                              p_soft - onehot) / S
        np.testing.assert_allclose(
            np.asarray(emb[:, lo:hi]).reshape((K,) + shape),
            np.asarray(analytic), atol=2e-4)

    def test_full_grad_source_selects_through_train_step(self, rng):
        """grad_mode='full' end to end: selection_inputs → a GRAFT train
        step on a tiny model (the small-model oracle path)."""
        from repro import configs
        from repro.launch import steps as steps_lib
        from repro.launch.specs import default_train_config
        from repro.models import model as M
        mcfg = configs.get_smoke_config("minicpm-2b")
        params = M.init_params(mcfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, mcfg.vocab_size, (8, 16)),
                           dtype=jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        tcfg = default_train_config("minicpm-2b", batch=8, grad_mode="full")
        V, G, gbar, scores = steps_lib.selection_inputs(
            mcfg, tcfg, params, batch)
        num_params = sum(int(np.prod(l.shape)) for l in
                         jax.tree_util.tree_leaves(params))
        assert G.shape == (num_params, 8) and gbar.shape == (num_params,)
        assert bool(jnp.all(jnp.isfinite(G)))
        state = steps_lib.init_train_state(mcfg, tcfg, jax.random.PRNGKey(2),
                                           batch_size=8)
        state, metrics = steps_lib.graft_train_step(mcfg, tcfg, state, batch)
        assert np.isfinite(metrics["loss"])
