"""Distributed machinery: logical-axis rules, dry-run smoke (8 fake devices
via subprocess — the 512-device override belongs only to dryrun), collective
parsing, multi-device compression."""
import numpy as np
import jax
import pytest

from jax.sharding import PartitionSpec as P

from conftest import run_forced_devices
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh


def run_py(code: str, devices: int = 8, timeout: int = 480) -> str:
    return run_forced_devices(code, devices=devices, timeout=timeout)


class TestShardingRules:
    def _mesh(self):
        return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])

    def test_logical_to_spec_filters_missing_axes(self):
        mesh = self._mesh()
        spec = sh.logical_to_spec(("act_batch", None, "act_heads"), mesh)
        # "pod" axis not in mesh → filtered from the tuple rule
        assert spec == P(("data",), None, "model")

    def test_drop_indivisible(self):
        mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
        # sizes are 1 so everything divides; use a fake larger mesh via spec
        spec = sh.drop_indivisible(P("data", "model"), (4, 4), mesh)
        assert spec == P("data", "model")

    def test_duplicate_axis_dedup(self):
        mesh = self._mesh()
        spec = sh.drop_indivisible(P("data", ("data", "model")), (4, 8), mesh)
        # first dim claims "data"; second keeps only "model"
        assert spec == P("data", "model")

    def test_constrain_noop_outside_context(self):
        import jax.numpy as jnp
        x = jnp.ones((4, 4))
        y = sh.constrain(x, ("act_batch", None))
        assert y is x


class TestCollectiveParsing:
    def test_parse_known_ops(self):
        from repro.launch.dryrun import parse_collective_bytes
        hlo = """
          %ag = bf16[4,1024]{1,0} all-gather(bf16[1,1024]{1,0} %x), replica_groups={}
          %ar = f32[2048]{0} all-reduce(f32[2048]{0} %y), to_apply=%sum
          %rs = f32[512]{0} reduce-scatter(f32[2048]{0} %z), dimensions={0}
          %cp = u8[100]{0} collective-permute(u8[100]{0} %w), source_target_pairs={{0,1}}
          %a2a = s32[64]{0} all-to-all(s32[64]{0} %v), dimensions={0}
          %dot = f32[8,8]{1,0} dot(f32[8,8] %a, f32[8,8] %b)
        """
        res = parse_collective_bytes(hlo)
        assert res["bytes_by_op"]["all-gather"] == 1 * 1024 * 2
        assert res["bytes_by_op"]["all-reduce"] == 2048 * 4
        assert res["bytes_by_op"]["reduce-scatter"] == 2048 * 4
        assert res["bytes_by_op"]["collective-permute"] == 100
        assert res["bytes_by_op"]["all-to-all"] == 64 * 4
        assert res["total_count"] == 5


@pytest.mark.slow
class TestDryrunSmoke:
    def test_train_cell_compiles_on_2x4(self):
        out = run_py("""
            from repro.launch import dryrun
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            res = dryrun.run_cell("minicpm-2b", "train_4k", "t", "graft",
                                  with_deltas=False, smoke=True,
                                  mesh_override=mesh)
            print("FLOPS", res["full"]["flops"] > 0)
            print("COLL", res["full"]["collectives"]["total_count"] > 0)
        """)
        assert "FLOPS True" in out and "COLL True" in out

    def test_decode_cell_compiles_multipod_2x2x2(self):
        out = run_py("""
            from repro.launch import dryrun
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
            res = dryrun.run_cell("hymba-1.5b", "decode_32k", "t", "serve",
                                  with_deltas=False, smoke=True,
                                  mesh_override=mesh)
            print("OK", res["full"]["flops"] >= 0)
        """)
        assert "OK True" in out

    def test_production_mesh_shapes(self):
        out = run_py("""
            from repro.launch.mesh import make_production_mesh
            m1 = make_production_mesh()
            m2 = make_production_mesh(multi_pod=True)
            print(m1.devices.shape, m1.axis_names)
            print(m2.devices.shape, m2.axis_names)
        """, devices=512)
        assert "(16, 16) ('data', 'model')" in out
        assert "(2, 16, 16) ('pod', 'data', 'model')" in out


@pytest.mark.slow
class TestCompressionMultiDevice:
    def test_ef_psum_across_8_shards(self):
        out = run_py("""
            import jax, jax.numpy as jnp, numpy as np
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            from repro.distributed import compression
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("pod",))
            g = jnp.asarray(np.random.default_rng(0).normal(
                size=(8, 512)).astype(np.float32))
            e = jnp.zeros((8, 512))
            def f(g, e):
                out, ne = compression.ef_compressed_psum(g[0], e[0], "pod", 8)
                return out[None], ne[None]
            out, _ = shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                               out_specs=(P("pod"), P("pod")))(g, e)
            ref = np.asarray(g).mean(0)
            err = np.abs(np.asarray(out)[0] - ref).max()
            print("ERR_OK", err < 0.05, float(err))
        """)
        assert "ERR_OK True" in out


class TestElasticRestore:
    def test_checkpoint_restores_onto_different_sharding(self, tmp_path):
        """Save on 1 device, restore with an explicit sharding tree."""
        import jax.numpy as jnp
        from repro.checkpoint import CheckpointManager
        cm = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        cm.save(1, tree)
        mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])
        shard = jax.sharding.NamedSharding(mesh, P("data", None))
        out = cm.restore(1, tree, sharding_tree={"w": shard})
        assert out["w"].sharding.is_equivalent_to(shard, 2)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.asarray(tree["w"]))


@pytest.mark.slow
class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        out = run_py("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.distributed.pipeline import pipeline_forward
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("pod",), devices=jax.devices()[:4])
            S, M, mb, D = 4, 3, 2, 8
            rng = np.random.default_rng(0)
            Ws = jnp.asarray(rng.normal(size=(S, D, D)).astype(np.float32) * 0.3)
            x = jnp.asarray(rng.normal(size=(M, mb, D)).astype(np.float32))
            out = pipeline_forward(lambda W, h: jnp.tanh(h @ W), Ws, x, mesh)
            ref = x
            for s in range(S):
                ref = jnp.tanh(ref @ Ws[s])
            print("ERR_OK", float(jnp.max(jnp.abs(out - ref))) < 1e-5)
        """)
        assert "ERR_OK True" in out

    def test_bubble_fraction(self):
        from repro.distributed.pipeline import pipeline_bubble_fraction
        assert pipeline_bubble_fraction(2, 1) == 0.5
        assert pipeline_bubble_fraction(4, 13) == 3 / 16
        assert pipeline_bubble_fraction(1, 8) == 0.0
