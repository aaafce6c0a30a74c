"""Optimizers, schedules, data pipeline, checkpointing, compression,
accumulation, straggler monitor."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis_compat import given, settings, st  # skips, not collection errors, without hypothesis

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticClassification, SyntheticLM
from repro.distributed import accumulate, compression
from repro.distributed.straggler import StragglerConfig, StragglerMonitor
from repro.optim import OptimizerConfig, make_optimizer
from repro.optim.schedules import cosine, wsd


class TestOptimizers:
    @pytest.mark.parametrize("name,lr", [("adamw", 0.05), ("sgd", 0.05),
                                         ("lion", 0.005), ("adafactor", 0.1)])
    def test_converges_on_quadratic(self, name, lr):
        cfg = OptimizerConfig(name=name, learning_rate=lr, schedule="constant",
                              weight_decay=0.0)
        opt = make_optimizer(cfg)
        W = {"a": jnp.ones((6, 6)), "b": jnp.ones((6,))}
        state = opt.init(W)

        @jax.jit
        def step(W, state, i):
            loss, g = jax.value_and_grad(
                lambda w: sum(jnp.sum(w[k] ** 2) for k in w))(W)
            W, state, m = opt.apply(W, g, state, i)
            return W, state, loss

        for i in range(300):
            W, state, loss = step(W, state, jnp.int32(i))
        assert float(loss) < 0.3

    def test_adamw_matches_reference_numpy(self):
        """One AdamW step vs a hand-written numpy reference."""
        cfg = OptimizerConfig(name="adamw", learning_rate=0.1,
                              schedule="constant", weight_decay=0.01,
                              beta1=0.9, beta2=0.95, eps=1e-8, clip_norm=None)
        opt = make_optimizer(cfg)
        w0 = np.array([1.0, -2.0, 3.0], np.float32)
        g = np.array([0.5, 0.25, -1.0], np.float32)
        params = {"w": jnp.asarray(w0)}
        state = opt.init(params)
        new_p, _, _ = opt.apply(params, {"w": jnp.asarray(g)}, state, jnp.int32(0))
        m = 0.1 * g
        v = 0.05 * g * g
        mh, vh = m / (1 - 0.9), v / (1 - 0.95)
        ref = w0 - 0.1 * (mh / (np.sqrt(vh) + 1e-8) + 0.01 * w0)
        np.testing.assert_allclose(np.asarray(new_p["w"]), ref, rtol=1e-5)

    def test_adafactor_state_is_factored(self):
        cfg = OptimizerConfig(name="adafactor")
        opt = make_optimizer(cfg)
        params = {"w": jnp.zeros((32, 16)), "b": jnp.zeros((16,))}
        state = opt.init(params)
        assert state["v"]["w"]["vr"].shape == (32,)
        assert state["v"]["w"]["vc"].shape == (16,)
        assert state["v"]["b"]["v"].shape == (16,)

    def test_grad_clipping(self):
        from repro.optim import clip_by_global_norm
        g = {"a": jnp.full((10,), 100.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-4
        assert float(norm) > 100


class TestSchedules:
    def test_wsd_phases(self):
        f = wsd(2.0, 1000, warmup_steps=100, decay_fraction=0.1)
        assert abs(float(f(50)) - 1.0) < 1e-5          # mid-warmup
        assert abs(float(f(500)) - 2.0) < 1e-5         # stable plateau
        assert float(f(999)) < 0.1                     # decayed
        assert float(f(950)) < 2.0                     # inside decay window

    def test_cosine_endpoints(self):
        f = cosine(1.0, 100, warmup_steps=10, min_ratio=0.1)
        assert float(f(0)) == 0.0
        assert abs(float(f(10)) - 1.0) < 1e-5
        assert abs(float(f(100)) - 0.1) < 1e-5


class TestDataPipeline:
    def test_determinism(self):
        d = SyntheticLM(DataConfig(global_batch=4, seq_len=16))
        b1, b2 = d.batch_at(7), d.batch_at(7)
        assert np.array_equal(b1["tokens"], b2["tokens"])

    def test_labels_are_shifted_tokens(self):
        d = SyntheticLM(DataConfig(global_batch=2, seq_len=16))
        b = d.batch_at(0)
        assert b["tokens"].shape == b["labels"].shape == (2, 16)
        assert not np.array_equal(b["tokens"], b["labels"])

    def test_elastic_host_sharding(self):
        """Same global stream regardless of host count (elastic restarts)."""
        whole = SyntheticLM(DataConfig(global_batch=8, seq_len=8)).batch_at(5)
        parts = [SyntheticLM(DataConfig(global_batch=8, seq_len=8,
                                        num_hosts=4, host_index=i)).batch_at(5)
                 for i in range(4)]
        np.testing.assert_array_equal(
            whole["tokens"], np.concatenate([p["tokens"] for p in parts]))

    def test_resume_state(self):
        d = SyntheticLM(DataConfig(global_batch=2, seq_len=8))
        it = iter(d)
        next(it); next(it); next(it)
        state = d.state_dict()
        d2 = SyntheticLM(DataConfig(global_batch=2, seq_len=8))
        d2.load_state_dict(state)
        np.testing.assert_array_equal(next(iter(d2))["tokens"],
                                      d.batch_at(3)["tokens"])

    def test_markov_structure_is_learnable(self):
        """Bigram statistics must beat unigram (the stream has structure)."""
        d = SyntheticLM(DataConfig(global_batch=32, seq_len=64, vocab_size=64,
                                   num_clusters=4))
        toks = np.concatenate([d.batch_at(i)["tokens"].ravel() for i in range(4)])
        uni = np.bincount(toks, minlength=64) / len(toks)
        h_uni = -np.sum(uni * np.log(uni + 1e-12))
        pairs = np.stack([toks[:-1], toks[1:]])
        joint = np.zeros((64, 64))
        np.add.at(joint, (pairs[0], pairs[1]), 1)
        joint /= joint.sum()
        cond = joint / (joint.sum(1, keepdims=True) + 1e-12)
        h_bi = -np.sum(joint * np.log(cond + 1e-12))
        assert h_bi < h_uni - 0.05, (h_bi, h_uni)

    def test_classification_split(self):
        ds = SyntheticClassification(n=512, dim=16, num_classes=5)
        (xtr, ytr), (xte, yte) = ds.split(0.25)
        assert len(ytr) == 384 and len(yte) == 128
        assert set(np.unique(ytr)) <= set(range(5))


class TestCheckpoint:
    def test_roundtrip_and_gc(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), keep_last_n=2)
        tree = {"w": jnp.arange(6.0).reshape(2, 3), "s": jnp.int32(3)}
        for s in (10, 20, 30):
            cm.save(s, tree, extra={"k": s})
        assert cm.all_steps() == [20, 30]
        out = cm.restore(30, jax.tree_util.tree_map(jnp.zeros_like, tree))
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))
        assert cm.manifest(20)["extra"]["k"] == 20

    def test_corruption_detected(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.ones((4,))}
        path = cm.save(1, tree)
        # corrupt the leaf file
        fname = [f for f in os.listdir(path) if f.endswith(".npy")][0]
        arr = np.load(os.path.join(path, fname))
        np.save(os.path.join(path, fname), arr + 1)
        with pytest.raises(IOError, match="checksum"):
            cm.restore(1, tree)

    def test_interrupted_save_never_corrupts_latest(self, tmp_path):
        """A tmp dir from a crashed save must not count as a checkpoint."""
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, {"w": jnp.ones((2,))})
        os.makedirs(os.path.join(str(tmp_path), "tmp.2.999"))  # simulated crash
        assert cm.latest_step() == 1

    def test_async_save(self, tmp_path):
        cm = CheckpointManager(str(tmp_path), async_save=True)
        cm.save(5, {"w": jnp.ones((8,))})
        cm.wait()
        assert cm.latest_step() == 5

    def test_shape_mismatch_rejected(self, tmp_path):
        cm = CheckpointManager(str(tmp_path))
        cm.save(1, {"w": jnp.ones((4,))})
        with pytest.raises(ValueError, match="shape"):
            cm.restore(1, {"w": jnp.ones((5,))})


class TestCompression:
    def test_quantization_error_bound(self, rng):
        """|x − deq(q(x))| ≤ absmax/254 per block (int8 step/2)."""
        x = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32) * 5)
        q, s = compression.quantize_int8(x)
        back = compression.dequantize_int8(q, s, x.shape, jnp.float32)
        blocks = np.asarray(x)[: (1000 // 256) * 256].reshape(-1, 256)
        err = np.abs(np.asarray(back) - np.asarray(x))
        assert err.max() <= np.abs(np.asarray(x)).max() / 127.0 + 1e-6

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 999), scale=st.floats(1e-3, 1e3))
    def test_property_roundtrip_bounded(self, seed, scale):
        g = np.random.default_rng(seed)
        x = jnp.asarray((g.normal(size=(300,)) * scale).astype(np.float32))
        q, s = compression.quantize_int8(x)
        back = compression.dequantize_int8(q, s, x.shape, jnp.float32)
        # per-block bound: |err| <= block_absmax/127 (half-step would be /254)
        xb, _ = compression._pad_to_block(x)
        blocks = np.asarray(xb).reshape(-1, 256)
        bound = np.abs(blocks).max(1) / 127.0 + 1e-9
        errb = np.abs(np.asarray(back) - np.asarray(x))
        errb = np.pad(errb, (0, blocks.size - errb.size)).reshape(-1, 256)
        assert (errb.max(1) <= bound + 1e-6).all()

    def test_error_feedback_converges_on_quadratic(self):
        """EF-compressed gradient descent reaches the optimum (bias cancels)."""
        target = jnp.asarray(np.linspace(-1, 1, 64).astype(np.float32))
        w = jnp.zeros((64,))
        err = jnp.zeros((64,))
        for _ in range(200):
            g = 2 * (w - target)
            comp = g.astype(jnp.float32) + err
            q, s = compression.quantize_int8(comp)
            gq = compression.dequantize_int8(q, s, g.shape, jnp.float32)
            err = comp - gq
            w = w - 0.05 * gq
        assert float(jnp.max(jnp.abs(w - target))) < 1e-2

    def test_ef_compressed_psum_under_shard_map(self):
        """Single-device shard_map sanity: reduces to identity mean."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("pod",), devices=jax.devices()[:1])
        g = jnp.asarray(np.random.default_rng(0).normal(size=(256,)).astype(np.float32))
        e = jnp.zeros((256,))

        def f(g, e):
            return compression.ef_compressed_psum(g, e, "pod", 1)

        out, new_e = shard_map(f, mesh=mesh, in_specs=(P(), P()),
                               out_specs=(P(), P()))(g, e)
        np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.05)


class TestAccumulation:
    def test_matches_full_batch_grads(self, rng):
        params = {"w": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32))}
        batch = {"x": jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32)),
                 "y": jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))}

        def loss_fn(p, b):
            return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)

        loss_full, g_full = jax.value_and_grad(loss_fn)(params, batch)
        loss_acc, g_acc = accumulate.accumulated_grads(loss_fn, params, batch, 4)
        np.testing.assert_allclose(float(loss_full), float(loss_acc), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g_full["w"]),
                                   np.asarray(g_acc["w"]), rtol=1e-4)

    def test_indivisible_raises(self):
        with pytest.raises(ValueError, match="divisible"):
            accumulate.split_microbatches({"x": jnp.zeros((10, 2))}, 3)


class TestStraggler:
    def test_flags_outliers_and_keeps_ema_clean(self):
        mon = StragglerMonitor(StragglerConfig(min_history=3, threshold=1.5))
        flagged = [mon.record(t) for t in
                   [1.0, 1.0, 1.0, 1.0, 1.0, 5.0, 1.0, 1.0]]
        assert flagged[5] is True and sum(flagged) == 1
        assert abs(mon.ema - 1.0) < 0.05          # outlier didn't poison EMA
        assert mon.summary()["flagged"] == 1

    def test_rank_backoff(self):
        mon = StragglerMonitor()
        assert mon.suggested_rank(64, True) == 32
        assert mon.suggested_rank(64, False) == 64


class TestSampling:
    def test_greedy_matches_argmax(self, rng):
        from repro.launch.sampling import sample_tokens
        logits = jnp.asarray(rng.normal(size=(4, 50)).astype(np.float32))
        out = sample_tokens(jax.random.PRNGKey(0), logits, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_top_k_restricts_support(self, rng):
        from repro.launch.sampling import sample_tokens
        logits = jnp.asarray(rng.normal(size=(1, 100)).astype(np.float32))
        top5 = set(np.argsort(-np.asarray(logits)[0])[:5].tolist())
        draws = {int(sample_tokens(jax.random.PRNGKey(i), logits,
                                   temperature=1.0, top_k=5)[0])
                 for i in range(50)}
        assert draws <= top5

    def test_top_p_keeps_minimal_nucleus(self):
        from repro.launch.sampling import sample_tokens
        # one dominant token (p≈0.97) → nucleus at 0.9 is a single token
        logits = jnp.asarray([[10.0, 1.0, 0.0, -1.0]])
        draws = {int(sample_tokens(jax.random.PRNGKey(i), logits,
                                   temperature=1.0, top_p=0.9)[0])
                 for i in range(20)}
        assert draws == {0}


class TestMetricsAndEval:
    def test_jsonl_roundtrip_and_throughput(self, tmp_path):
        from repro.launch.metrics import MetricsLogger, read_metrics
        path = str(tmp_path / "m.jsonl")
        lg = MetricsLogger(path, num_chips=2, flops_per_step=1e12)
        lg.log(0, {"loss": 2.0}, tokens=100)
        lg.log(1, {"loss": 1.5}, tokens=100)
        lg.close()
        rows = read_metrics(path)
        assert rows[0]["loss"] == 2.0
        assert "tokens_per_s" in rows[1] and "mfu" in rows[1]
        assert rows[1]["tokens_seen"] == 200

    def test_jsonl_rows_buffered_until_flush(self, tmp_path):
        """One logical row per step, but host writes only every
        ``flush_every`` rows and on close — the step loop never pays a
        per-step file syscall."""
        from repro.launch.metrics import MetricsLogger, read_metrics
        path = str(tmp_path / "buffered.jsonl")
        lg = MetricsLogger(path, flush_every=3)
        lg.log(0, {"loss": 1.0})
        lg.log(1, {"loss": 2.0})
        assert read_metrics(path) == []               # still buffered
        lg.log(2, {"loss": 3.0})                      # hits the boundary
        assert [r["step"] for r in read_metrics(path)] == [0, 1, 2]
        lg.log(3, {"loss": 4.0})
        lg.close()                                    # close drains the tail
        rows = read_metrics(path)
        assert [r["step"] for r in rows] == [0, 1, 2, 3]
        assert rows[3]["loss"] == 4.0

    def test_step_time_isolated_from_host_pauses(self, tmp_path):
        """Regression: step_time_s/mfu/tokens_per_s came from the wall gap
        between log calls, so an eval/checkpoint pause between steps
        cratered the NEXT step's MFU. With step_time passed, throughput is
        computed from the dispatch clock and the pause lands in
        host_overhead_s instead."""
        import time as time_mod
        from repro.launch.metrics import MetricsLogger, read_metrics
        path = str(tmp_path / "t.jsonl")
        lg = MetricsLogger(path, num_chips=1, flops_per_step=1e12,
                           flush_every=1, peak_flops_per_chip=197e12)
        lg.log(0, {"loss": 2.0}, tokens=100, step_time=0.01)
        time_mod.sleep(0.08)                  # simulated eval pause
        lg.log(1, {"loss": 1.9}, tokens=100, step_time=0.01)
        lg.close()
        rows = read_metrics(path)
        for r in rows:
            assert r["step_time_s"] == pytest.approx(0.01)
            assert r["tokens_per_s"] == pytest.approx(100 / 0.01)
        assert rows[1]["host_overhead_s"] >= 0.05   # the pause, separated
        assert rows[1]["mfu"] == pytest.approx(
            1e12 / (0.01 * 197e12), rel=1e-6)

    def test_lazy_rows_materialize_at_flush(self, tmp_path):
        """MetricsFuture rows queue without a device sync; the flush
        boundary is the one materialization point."""
        import jax.numpy as jnp
        from repro.launch.metrics import (MetricsFuture, MetricsLogger,
                                          read_metrics)
        path = str(tmp_path / "lazy.jsonl")
        lg = MetricsLogger(path, flush_every=3)
        futs = [MetricsFuture({"loss": jnp.float32(i)}) for i in range(3)]
        lg.log(0, futs[0])
        lg.log(1, futs[1])
        assert not futs[0].materialized and not futs[1].materialized
        assert "loss" in futs[0]              # key checks never sync
        assert not futs[0].materialized
        lg.log(2, futs[2])                    # flush boundary drains all
        assert all(f.materialized for f in futs)
        assert [r["loss"] for r in read_metrics(path)] == [0.0, 1.0, 2.0]
        lg.close()

    def test_eval_stream_disjoint_and_ppl(self):
        from repro import configs
        from repro.launch.evaluate import make_eval_fn
        from repro.models import model as M
        mcfg = configs.get_smoke_config("minicpm-2b")
        params = M.init_params(mcfg, jax.random.PRNGKey(0))
        ev = make_eval_fn(mcfg, batch=4, seq=16, num_batches=2)
        out = ev(params)
        assert out["eval_ppl"] == pytest.approx(
            np.exp(out["eval_loss"]), rel=1e-5)
        assert 0 < out["eval_loss"] < 20

    def test_train_loop_with_metrics_and_eval(self, tmp_path):
        from repro.launch.train import RunConfig, train
        from repro.launch.metrics import read_metrics
        mpath = str(tmp_path / "metrics.jsonl")
        run = RunConfig(arch="minicpm-2b", steps=6, batch=8, seq=32,
                        graft_rset=(2, 4), log_every=100,
                        metrics_path=mpath, eval_every=3)
        report = train(run)
        rows = read_metrics(mpath)
        assert len(rows) == 6
        assert any("eval_ppl" in h for h in report["history"])

    def test_train_step_flops_attention_term(self):
        """Regression for the mfu denominator: 6·N·tokens misses the
        O(S²·Dh·H) attention work, so at fixed tokens the param-only
        estimate is flat in S while compiled FLOPs grow. The
        attention-aware estimate must track that growth better."""
        from repro.launch.metrics import (attention_train_flops,
                                          train_step_flops)
        from repro.models import model as M

        def mk(S):
            cfg = M.ModelConfig(
                family="dense", num_layers=2, d_model=64, num_heads=4,
                num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64,
                param_dtype="float32", scan_layers=False)
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            B = 512 // S                       # fixed tokens per step
            toks = jnp.zeros((B, S), jnp.int32)
            batch = {"tokens": toks, "labels": toks}

            def step(p, b):
                return jax.grad(lambda pp: M.loss_fn(cfg, pp, b)[0])(p)

            compiled = jax.jit(step).lower(params, batch).compile()
            measured = compiled.cost_analysis().get("flops", 0.0)
            n = sum(int(np.prod(l.shape))
                    for l in jax.tree_util.tree_leaves(params))
            return (measured,
                    train_step_flops(n, B * S, remat=False),
                    train_step_flops(n, B * S, remat=False, mcfg=cfg, seq=S))

        m1, p1, a1 = mk(128)
        m2, p2, a2 = mk(512)
        assert p1 == p2                        # param-only: blind to S
        assert a2 > a1 > p1                    # attention term grows with S
        measured_ratio = m2 / m1
        assert measured_ratio > 1.05           # XLA sees the S² work too
        # attention-aware ratio lands closer to the measured growth
        assert abs(a2 / a1 - measured_ratio) < abs(1.0 - measured_ratio)

    def test_attention_flops_window_and_pattern_aware(self):
        """A sliding window caps the per-query KV horizon, and only LOCAL
        layers in the pattern get the cap."""
        from repro.launch.metrics import attention_train_flops
        from repro.models import model as M
        import dataclasses

        base = M.ModelConfig(
            family="dense", num_layers=4, d_model=64, num_heads=4,
            num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=64)
        full = attention_train_flops(base, seq=1024, tokens_per_step=1024)
        local = dataclasses.replace(base, sliding_window=64,
                                    layer_pattern=("local",))
        capped = attention_train_flops(local, seq=1024, tokens_per_step=1024)
        assert capped < full / 4               # horizon 512 → ~64
        mixed = dataclasses.replace(base, sliding_window=64,
                                    layer_pattern=("local", "global"))
        half = attention_train_flops(mixed, seq=1024, tokens_per_step=1024)
        assert capped < half < full

    def test_device_clock_times_all_but_first_step(self):
        """N observed steps yield exactly N−1 timings, delivered to both
        poll() (straggler feed) and device_time() (logger)."""
        from repro.launch.metrics import DeviceClock
        clock = DeviceClock()
        for step in range(5):
            clock.observe(step, jnp.float32(step))
        clock.drain()
        assert clock.timed_steps == 4
        assert clock.device_time(0, timeout=0.1) is None   # no predecessor
        for step in range(1, 5):
            assert clock.device_time(step, timeout=1.0) >= 0.0
        assert clock.total_device_s >= 0.0
        assert len(clock.poll()) == 4
        assert clock.poll() == []              # fresh list drained once
        clock.close()
