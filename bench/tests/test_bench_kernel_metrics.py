"""The per-kernel readers: each kernel's time summed over its instructions,
no partial sum ever reported, and the readers on records taken from a
chip's trace."""
import importlib
import json
import pathlib

import pytest

from bench.harness import common, kernels, trace, work

DATA = pathlib.Path(__file__).resolve().parents[1] / "data"
# the readers of one kernel's time; backward_roofline.train reads all Pallas
# time, whatever its kernels are named
READERS = ("spmm_roofline.infer", "dense_roofline.infer")


def _ctx(config: str, red: dict, **counters) -> dict:
    spec = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in spec["configs"] if c["name"] == config)
    cfg = common.load_json(common.ROOT / entry["file"])
    ref = common.load_module(common.BENCH / "references"
                             / f"{cfg['arch']}.py")
    return {"config": cfg, "ref_mod": ref, "counters": counters,
            "trace": red, "peak": work.peak("TPU v5 lite")}


def _red(ops, pallas_s=None, busy_s=None):
    pallas = sum(s for _, s in ops) if pallas_s is None else pallas_s
    return {"device_ops": ops, "pallas_s": pallas,
            "busy_s": pallas if busy_s is None else busy_s,
            "window_s": 1.0}


def test_suffixes_are_summed():
    red = _red([["gnn_shard_spmm.3", 0.4], ["gnn_shard_spmm.2", 0.3],
                ["gnn_dense_engine.2", 0.05], ["gnn_dense_engine", 0.01],
                ["pad.0", 0.02]], pallas_s=0.76)
    assert kernels.device_s(red, kernels.SPMM) == pytest.approx(0.7)
    assert kernels.device_s(red, kernels.DENSE) == pytest.approx(0.06)


def test_infer_readers_divide_their_kernels_work():
    red = _red([["gnn_shard_spmm.3", 0.4], ["gnn_shard_spmm.2", 0.3],
                ["gnn_dense_engine.2", 0.05], ["gnn_dense_engine.3", 0.01]])
    ctx = _ctx("sage_mean-pubmed", red, forwards=100)
    agg = kernels.roofline_s(ctx, "agg")
    dense = kernels.roofline_s(ctx, "dense")
    # 147.7 us of aggregation, a forward's worth, at PubMed shapes
    assert agg == pytest.approx(147.7e-6, rel=1e-3)
    assert agg + dense == pytest.approx(kernels.roofline_s(ctx))
    spmm = common.metric_reader("spmm_roofline.infer")(ctx)
    assert spmm == pytest.approx(100 * agg * 100 / 0.7)
    assert common.metric_reader("dense_roofline.infer")(ctx) == \
        pytest.approx(100 * dense * 100 / 0.06)


def test_backward_reader_takes_the_step_beyond_the_forward_kernel():
    red = _red([["gnn_fused_aggregate_extract.4", 0.5],
                ["gnn_fused_aggregate_extract.5", 0.1], ["fusion.50", 0.3]],
               pallas_s=0.6, busy_s=1.0)
    ctx = _ctx("gcn-pubmed", red, steps=50)
    want = 100 * 2 * kernels.roofline_s(ctx) * 50 / 0.4
    assert common.metric_reader("backward_roofline.train")(ctx) == \
        pytest.approx(want)


# forward kernels of a training step: the layout of one forward, the time of
# its Pallas calls, and the trace's busy time
LAYOUTS = {
    # both GCN layers dense-first: no fused kernel at all
    "dense-first": ([["gnn_shard_spmm.1", 0.22], ["gnn_shard_spmm.2", 0.21],
                     ["gnn_dense_engine.1", 0.01],
                     ["gnn_dense_engine.2", 0.005]], 0.445, 1.2),
    # layer 0 dense-first, layer 1 fused (the program since it walks the
    # grid fewer times)
    "mixed": ([["gnn_fused_aggregate_extract.1", 0.2234],
               ["gnn_shard_spmm.1", 0.2218], ["gnn_dense_engine.1", 0.0107],
               ["fusion.14", 0.238]], 0.4559, 1.1956),
    # a program whose kernels carry no names of the benchmark's
    "unnamed": ([["shard_spmm.2", 0.5], ["dense_engine_matmul.2", 0.1],
                 ["jvp_jit_fused_gnn_layer__.4", 0.2]], 0.8, 1.3),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_backward_reader_takes_busy_less_all_pallas_time(layout):
    """The backward's base is busy time less every Pallas call, named or
    not, with or without the fused kernel."""
    ops, pallas_s, busy_s = LAYOUTS[layout]
    ctx = _ctx("gcn-pubmed", _red(ops, pallas_s=pallas_s, busy_s=busy_s),
               steps=100)
    want = 100 * 2 * kernels.roofline_s(ctx) * 100 / (busy_s - pallas_s)
    assert common.metric_reader("backward_roofline.train")(ctx) == \
        pytest.approx(want)


def test_backward_reader_refuses_a_step_with_no_room_for_a_backward():
    read = common.metric_reader("backward_roofline.train")
    with pytest.raises(RuntimeError, match="no Pallas"):
        read(_ctx("gcn-pubmed", _red([["fusion", 0.1]], pallas_s=0.0,
                                     busy_s=0.1), steps=1))
    with pytest.raises(RuntimeError, match="no more than"):
        read(_ctx("gcn-pubmed", _red([["gnn_shard_spmm.1", 0.1]]), steps=1))


@pytest.mark.parametrize("reader", READERS)
def test_absent_kernel_raises(reader):
    # names present, but not this reader's kernel
    other = "gnn_seg_gather.1"
    ctx = _ctx("sage_mean-pubmed", _red([[other, 1.0]]), forwards=1)
    with pytest.raises(RuntimeError, match="absent"):
        common.metric_reader(reader)(ctx)


@pytest.mark.parametrize("reader", READERS)
def test_unaccounted_pallas_time_raises(reader):
    ops = [["gnn_fused_aggregate_extract.4", 0.5], ["gnn_shard_spmm.2", 0.3],
           ["gnn_dense_engine.2", 0.19]]
    ctx = _ctx("sage_mean-pubmed", _red(ops, pallas_s=1.0, busy_s=2.0),
               forwards=1)
    # 0.99 of 1.0 s named: on the limit, read
    assert common.metric_reader(reader)(ctx) > 0
    ctx["trace"]["pallas_s"] = 1.0011
    with pytest.raises(RuntimeError, match="unaccounted"):
        common.metric_reader(reader)(ctx)


@pytest.mark.parametrize("reader", READERS)
def test_program_without_kernel_names_reads_nothing(reader):
    """A program that predates the names: its metric is left out."""
    ctx = _ctx("sage_mean-pubmed",
               _red([["shard_spmm.2", 0.5], ["dense_engine_matmul.2", 0.1],
                     ["jvp_jit_fused_gnn_layer__.4", 0.2]]), forwards=1)
    assert common.metric_reader(reader)(ctx) is None


def test_no_pallas_time_raises():
    ctx = _ctx("sage_mean-pubmed", _red([["fusion", 0.1]], pallas_s=0.0),
               forwards=1)
    with pytest.raises(RuntimeError, match="no Pallas"):
        common.metric_reader("spmm_roofline.infer")(ctx)


def _calls(rec: dict, site: str) -> float:
    """Calls of one instruction in the record's window, a call clipped at
    an edge counting by the share of it inside."""
    w0, w1 = rec["window_ns"]
    return sum((min(s + d, w1) - max(s, w0)) / d
               for n, s, d, _ in rec["device"] if n == site and d > 0)


# per record: the configuration, its counter, the instruction counted as
# one call, and each reader with the range predicted for it before the chip run
RECORDED = [
    ("trace_infer_named_v5e.json", "sage_mean-pubmed", "forwards",
     "gnn_shard_spmm.2", {"spmm_roofline.infer": (1.5, 2.5),
                          "dense_roofline.infer": (25.0, 35.0)}),
    ("trace_train_named_v5e.json", "gcn-pubmed", "steps",
     "gnn_fused_aggregate_extract.2", {"backward_roofline.train": (3.0, 5.5)}),
]


@pytest.mark.parametrize("name,config,counter,site,readers", RECORDED,
                         ids=[r[0] for r in RECORDED])
def test_readers_on_recorded_trace(name, config, counter, site, readers):
    rec = json.loads((DATA / name).read_text())
    red = trace.reduce(rec)
    ops = [n for n, _ in red["device_ops"]]
    # the kernels appear under their own names, never their wrappers'
    assert not [n for n in ops if n.startswith(
        ("jvp_jit_fused_gnn_layer", "shard_spmm", "dense_engine_matmul"))]
    named = sum(s for n, s in red["device_ops"]
                if kernels.base_name(n) in kernels.NAMES)
    assert named == pytest.approx(red["pallas_s"], rel=1e-9)
    ctx = _ctx(config, red, **{counter: _calls(rec, site)})
    for reader, (lo, hi) in readers.items():
        assert lo <= common.metric_reader(reader)(ctx) <= hi, reader


def test_names_are_the_programs():
    """The benchmark's record of the kernel names, one file each, is the
    program's: each file's module defines that ``KERNEL_NAME``. (A later
    kernel adds a file; the four the readers name stay among them.)"""
    from repro.kernels import dense_engine, fused_gnn, seg_gather, shard_spmm
    assert {fused_gnn.KERNEL_NAME, shard_spmm.KERNEL_NAME,
            dense_engine.KERNEL_NAME, seg_gather.KERNEL_NAME} == {
        kernels.FUSED, kernels.SPMM, kernels.DENSE, kernels.GATHER} <= \
        set(kernels.NAMES)
    recs = kernels.records()
    assert tuple(recs) == kernels.NAMES
    for name, rec in recs.items():
        assert importlib.import_module(rec["module"]).KERNEL_NAME == name
        assert rec["work"] and all(isinstance(k, str) for k in rec["work"])


def _write_kernels(directory, extra: dict) -> None:
    for rec in list(kernels.records().values()) + [extra]:
        (directory / f"{rec['name']}.json").write_text(json.dumps(rec))


def test_a_kernel_file_names_a_fifth_kernel(tmp_path, monkeypatch):
    """A kernel is added with a file of its own: its time then counts as
    named, where without the file it is Pallas time unaccounted."""
    fifth = {"name": "gnn_edge_softmax_aggregate",
             "module": "repro.kernels.edge_softmax", "work": ["attention"]}
    red = _red([["gnn_shard_spmm.2", 0.3], ["gnn_dense_engine.2", 0.05],
                ["gnn_edge_softmax_aggregate.1", 0.6],
                ["gnn_edge_softmax_aggregate.2", 0.05]])
    with pytest.raises(RuntimeError, match="unaccounted"):
        kernels.device_s(red, kernels.SPMM)
    _write_kernels(tmp_path, fifth)
    monkeypatch.setattr(kernels, "NAMES", tuple(kernels.records(tmp_path)))
    assert len(kernels.NAMES) == 5 and fifth["name"] in kernels.NAMES
    assert kernels.device_s(red, fifth["name"]) == pytest.approx(0.65)
    assert kernels.device_s(red, kernels.SPMM) == pytest.approx(0.3)


@pytest.mark.parametrize("rec", [
    {"name": "gnn_other", "module": "m", "work": ["agg"]},
    {"name": "gnn_new", "module": "m"},
    {"name": "gnn_new", "module": "m", "work": ["agg"], "why": "x"}],
    ids=["misnamed", "missing-field", "extra-field"])
def test_a_malformed_kernel_file_is_refused(tmp_path, rec):
    (tmp_path / "gnn_new.json").write_text(json.dumps(rec))
    with pytest.raises(ValueError, match="kernel file"):
        kernels.records(tmp_path)
