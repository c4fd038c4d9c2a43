"""Distribution layer: sharding rules (divisibility guards, axis-reuse
guards), HLO collective parsing, mesh construction purity."""
import pytest
import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

import importlib.util

if importlib.util.find_spec("repro.dist") is None:
    # skip only when the package is genuinely absent; a broken import
    # inside an existing repro.dist must still fail loudly
    pytest.skip("repro.dist not present in this build",
                allow_module_level=True)
from repro.dist.compat import abstract_mesh
from repro.dist.hlo_analysis import analyze_collectives, type_bytes
from repro.dist.shardings import ShardingRules
from repro.nn.layers import Axes


def _mesh(shape=(16, 16), axes=("data", "model")):
    return abstract_mesh(shape, axes)


class TestShardingRules:
    def test_basic_spec(self):
        r = ShardingRules(_mesh())
        assert r.spec((256, 4096), Axes(("act_batch", "act_embed"))) == \
            P("data", None)
        assert r.spec((4096, 12288), Axes(("embed", "mlp"))) == \
            P("data", "model")

    def test_divisibility_guard(self):
        r = ShardingRules(_mesh())
        # 40 heads % 16 != 0 -> unsharded; flattened 40*128 divides fine
        assert r.spec((40,), Axes(("kv_heads_n",))) == P(None)
        assert r.spec((5120,), Axes(("heads",))) == P("model")
        # odd vocab (minicpm) falls back to replicated
        assert r.spec((122753, 2304), Axes(("vocab", "embed"))) == \
            P(None, "data")

    def test_axis_reuse_guard(self):
        r = ShardingRules(_mesh())
        # (lru, lru) both preferring model: only the first gets it
        spec = r.spec((2560, 2560), Axes(("lru", "lru")))
        assert spec == P("model", None)

    def test_multipod_combined_axis(self):
        r = ShardingRules(_mesh((2, 16, 16), ("pod", "data", "model")))
        assert r.spec((256, 4096), Axes(("act_batch", "act_seq"))) == \
            P(("pod", "data"), "model")
        # batch=1 (long_500k): everything falls back
        assert r.spec((1, 4096), Axes(("act_batch", "act_seq"))) == \
            P(None, "model")

    def test_missing_mesh_axis_skipped(self):
        r = ShardingRules(_mesh())  # no 'pod' axis
        assert r.spec((256,), Axes(("act_batch",))) == P("data")

    def test_override(self):
        r = ShardingRules(_mesh()).override(act_seq=())
        assert r.spec((64, 4096), Axes(("act_batch", "act_seq"))) == \
            P("data", None)

    def test_param_tree_shardings_cover_every_leaf(self):
        from repro.configs.registry import ARCHS, get_config
        from repro.models import lm
        r = ShardingRules(_mesh())
        for arch in ARCHS:
            cfg = get_config(arch)
            abs_p = lm.abstract_params(cfg)
            axes = lm.param_axes(cfg)
            specs = r.tree_specs(abs_p, axes)
            n_leaves = len(jax.tree.leaves(abs_p))
            n_specs = len(jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))
            assert n_leaves == n_specs, arch


class TestHloAnalysis:
    def test_type_bytes(self):
        assert type_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
        assert type_bytes("(f32[4,4]{1,0}, s32[7]{0})") == 64 + 28
        assert type_bytes("f32[]") == 4

    def test_collective_parsing_synthetic(self):
        hlo = """
HloModule m
ENTRY %main {
  %p0 = bf16[64,512]{1,0} parameter(0)
  %dot = f32[64,256]{1,0} dot(%p0, %p0)
  %all-reduce.1 = f32[64,256]{1,0} all-reduce(%dot), replica_groups=[8,8]<=[64]
  %ag = bf16[64,512]{1,0} all-gather(%p0), replica_groups=[4,16]<=[64], dimensions={0}
  ROOT %t = (f32[64,256]{1,0}) tuple(%all-reduce.1)
}
"""
        stats = analyze_collectives(hlo)
        ar_bytes = 64 * 256 * 4
        ag_bytes = 64 * 512 * 2
        assert stats.operand_bytes["all-reduce"] == ar_bytes
        assert stats.operand_bytes["all-gather"] == ag_bytes
        assert stats.wire_bytes["all-reduce"] == pytest.approx(
            ar_bytes * 2 * 7 / 8)
        assert stats.wire_bytes["all-gather"] == pytest.approx(ag_bytes * 15)
        assert stats.counts == {"all-reduce": 1, "all-gather": 1}

    def test_async_start_done_pairs_count_once(self):
        """-start results are (operand, result) tuples; the pair must
        count one collective with the sync convention's result bytes."""
        hlo = """
ENTRY %main {
  %p0 = bf16[16,512]{1,0} parameter(0)
  %ags = (bf16[4,512]{1,0}, bf16[16,512]{1,0}) all-gather-start(%p0), replica_groups=[2,4]<=[8], dimensions={0}
  %agd = bf16[16,512]{1,0} all-gather-done(%ags)
}
"""
        stats = analyze_collectives(hlo)
        result_bytes = 16 * 512 * 2
        assert stats.counts == {"all-gather": 1}
        assert stats.operand_bytes["all-gather"] == result_bytes
        assert stats.wire_bytes["all-gather"] == pytest.approx(
            result_bytes * 3)

    def test_real_compiled_module(self):
        """Single-device module: parser must find zero collectives and not
        crash on real XLA output."""
        fn = jax.jit(lambda x: jnp.sum(x * 2.0))
        txt = fn.lower(jnp.ones((8, 8))).compile().as_text()
        stats = analyze_collectives(txt)
        assert stats.total_wire_bytes == 0


class TestMesh:
    def test_make_production_mesh_is_a_function_not_constant(self):
        import repro.launch.mesh as m
        import inspect
        assert callable(m.make_production_mesh)
        src = inspect.getsource(m)
        # no module-level jax mesh/device calls (device state stays clean)
        for line in src.splitlines():
            stripped = line.split("#")[0].rstrip()
            if stripped.startswith((" ", "\t")) or not stripped:
                continue
            assert "make_mesh(" not in stripped, "module-level mesh!"

    def test_dryrun_sets_flags_before_imports(self):
        import pathlib
        src = pathlib.Path("src/repro/launch/dryrun.py").read_text()
        lines = [l for l in src.splitlines() if l.strip()]
        assert lines[0] == "import os"
        assert "xla_force_host_platform_device_count=512" in lines[1]
