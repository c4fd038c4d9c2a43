"""Required-work counts at PubMed shapes, from the configurations."""
import pytest

from bench.harness import common, work


def _config(name):
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == name)
    cfg = common.load_json(common.ROOT / entry["file"])
    ref = common.load_module(common.BENCH / "references" / f"{cfg['arch']}.py")
    return cfg, ref


def test_pubmed_nnz_counts_self_loops():
    cfg, _ = _config("gcn-pubmed")
    assert work.nnz(cfg) == 88_648 + 19_717 == 108_365


def test_gcn_pubmed_work():
    cfg, ref = _config("gcn-pubmed")
    ops = work.forward_ops(cfg, ref)
    assert [(o.kind, o.flops) for o in ops] == [
        ("agg", 2 * 108_365 * 500), ("dense", 2 * 19_717 * 500 * 16),
        ("agg", 2 * 108_365 * 16), ("dense", 2 * 19_717 * 16 * 3)]
    assert ops[0].bytes == 108_365 * 8 + 2 * 19_717 * 500 * 4
    assert work.forward_flops(cfg, ref) == 429_197_512
    # a training step counts three forwards: 1.29 GFLOP
    assert work.train_step_flops(cfg, ref) == pytest.approx(1.2876e9, rel=1e-4)


def test_sage_mean_pubmed_work():
    cfg, ref = _config("sage_mean-pubmed")
    ops = work.forward_ops(cfg, ref)
    assert [(o.kind, o.flops) for o in ops] == [
        ("agg", 2 * 108_365 * 500), ("dense", 2 * 19_717 * 1000 * 256),
        ("agg", 2 * 108_365 * 256), ("dense", 2 * 19_717 * 512 * 3)]
    assert work.forward_flops(cfg, ref) == pytest.approx(10.32e9, rel=1e-3)


def test_roofline_time_takes_the_larger_bound():
    peak = work.peak("TPU v5 lite")
    assert peak == {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9}
    op = work.Op("agg", flops=197e12, bytes=819e9 * 2)
    assert op.roofline_s(peak) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        work.peak("no such chip")


class _Counted:
    """A reference whose edge softmax counts its own work: per edge and
    head a score, its exponent, the normalisation and the weighted sum."""

    @staticmethod
    def ops(cfg):
        z, heads, d = work.nnz(cfg), 8, 8
        return [("dense", 500, 64),
                ("counted", "attention", 2.0 * z * heads * (3 + d),
                 z * (8.0 + 4 * heads)),
                ("agg", 64)]


def test_counted_op_keeps_its_kind_and_work():
    from bench.harness import kernels
    cfg, _ = _config("gcn-pubmed")
    ops = work.forward_ops(cfg, _Counted)
    assert [o.kind for o in ops] == ["dense", "attention", "agg"]
    z = 108_365
    assert ops[1] == work.Op("attention", 2.0 * z * 8 * 11, z * 40.0)
    # agg and dense are counted as ever beside it
    assert ops[2].flops == 2 * z * 64 and ops[0].flops == 2 * 19_717 * 500 * 64
    peak = work.peak("TPU v5 lite")
    ctx = {"config": cfg, "ref_mod": _Counted, "peak": peak}
    assert kernels.roofline_s(ctx, "attention") == pytest.approx(
        max(ops[1].flops / peak["flops_per_s"],
            ops[1].bytes / peak["bytes_per_s"]))
    assert kernels.roofline_s(ctx) == pytest.approx(
        sum(kernels.roofline_s(ctx, k) for k in ("dense", "attention", "agg")))
    assert work.forward_flops(cfg, _Counted) == sum(o.flops for o in ops)


@pytest.mark.parametrize("op", [("counted", "attention", -1.0, 0.0),
                                ("softmax", 8)])
def test_malformed_op_is_refused(op):
    cfg, _ = _config("gcn-pubmed")

    class Ref:
        @staticmethod
        def ops(cfg):
            return [op]

    with pytest.raises(ValueError):
        work.forward_ops(cfg, Ref)
