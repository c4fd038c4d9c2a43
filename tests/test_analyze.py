"""repro.analyze: every lint pass must catch its known-bad fixture, the
clean repo must produce zero findings, and the integration hooks
(runtime.compile(analyze=...), Server.start(analyze=...), the autotuner's
static pruning, the launch.analyze CLI) must gate on the report."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import runtime
from repro.analyze import (AnalysisError, Finding, Report, analyze_executable,
                           ast_lint, hlo_lint, jaxpr_lint, plan_lint,
                           preflight, severity_rank)
from repro.dist.hlo_analysis import CollectiveStats
from repro.gnn.executor import plan_model
from repro.gnn.models import ARCHS, ZooSpec
from repro.graphs.datasets import make_dataset


def _setup(scale=0.05, arch="gcn", hidden=8):
    ds = make_dataset("cora", seed=0, scale=scale)
    spec = ZooSpec(arch, ds.profile.feature_dim, hidden,
                   ds.profile.num_classes, num_layers=2)
    return ds, spec


@pytest.fixture(scope="module")
def tiny():
    """One compiled reference-backend gcn on scaled cora, shared by the
    read-only tests (tests that drive jit caches compile their own)."""
    ds, spec = _setup()
    exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64)
    return ds, spec, exe


# --------------------------------------------------------------------------
# report machinery
# --------------------------------------------------------------------------

def _finding(rule="XX001", severity="error", pass_name="plan",
             message="boom", location="here"):
    return Finding(rule=rule, severity=severity, pass_name=pass_name,
                   message=message, location=location)


def test_severity_rank_orders_and_validates():
    assert severity_rank("info") < severity_rank("warning") \
        < severity_rank("error")
    with pytest.raises(ValueError, match="unknown severity"):
        severity_rank("fatal")
    with pytest.raises(ValueError):
        _finding(severity="fatal")   # Finding validates eagerly


def test_report_thresholds_render_and_json_roundtrip():
    rep = Report()
    rep.add(_finding(severity="info"), _finding(severity="warning"))
    assert not rep.failed("error") and rep.failed("warning")
    assert rep.failed("info") and not rep.failed("never")
    assert rep.worst() == "warning"

    rep.add(_finding(severity="error", rule="PL001"))
    assert rep.failed("error") and rep.worst() == "error"
    assert rep.count("error") == 1

    text = rep.render()
    assert "PL001" in text and "1 error" in text
    doc = rep.to_json()
    assert doc["counts"] == {"info": 1, "warning": 1, "error": 1}
    back = [Finding.from_json(d) for d in doc["findings"]]
    assert back == rep.findings


def test_analysis_error_carries_report():
    rep = Report(findings=[_finding(rule="CC001")])
    err = AnalysisError(rep)
    assert err.report is rep and "CC001" in str(err)


# --------------------------------------------------------------------------
# host-sync AST lint
# --------------------------------------------------------------------------

_HOT_FIXTURE = """\
import jax
import jax.numpy as jnp
import numpy as np

def serve(x):
    a = x.item()
    jax.block_until_ready(x)
    b = float(jnp.max(x))
    c = jax.device_get(x)
    d = np.asarray(jnp.sum(x))
    for _ in range(3):
        fn = jax.jit(lambda y: y)
    return a, b, c, d, fn
"""


def test_host_sync_fixture_fires_every_rule():
    fs = ast_lint.lint_source(_HOT_FIXTURE, "fixture.py")
    by_rule = {}
    for f in fs:
        by_rule.setdefault(f.rule, []).append(f)
    assert set(by_rule) == {"HS001", "HS002", "HS003", "HS004", "RT101"}
    assert len(by_rule["HS004"]) == 2          # device_get + np.asarray
    assert by_rule["HS001"][0].severity == "error"
    assert by_rule["HS003"][0].severity == "warning"
    # jit-in-loop is a retrace finding that happens to live in the AST pass
    assert by_rule["RT101"][0].pass_name == "retrace"
    assert all(f.location.startswith("fixture.py:") for f in fs)


def test_host_sync_metadata_accessors_not_flagged():
    src = ("import jax.numpy as jnp\n"
           "def f():\n"
           "    lo = float(jnp.finfo(jnp.float32).max)\n"
           "    hi = int(jnp.iinfo(jnp.int32).max)\n"
           "    return lo, hi\n")
    assert ast_lint.lint_source(src) == []


def test_host_sync_suppression_by_rule_and_pass():
    src = ("import jax\n"
           "def f(x):\n"
           "    a = x.item()  # analyze: allow(HS001)\n"
           "    b = jax.device_get(x)  # analyze: allow(host-sync)\n"
           "    return a, b\n")
    assert ast_lint.lint_source(src) == []
    # a different rule's token does NOT suppress
    src2 = "def f(x):\n    return x.item()  # analyze: allow(HS002)\n"
    assert [f.rule for f in ast_lint.lint_source(src2)] == ["HS001"]


def test_host_sync_syntax_error_is_a_finding_not_a_crash():
    fs = ast_lint.lint_source("def broken(:\n", "bad.py")
    assert [f.rule for f in fs] == ["HS000"]
    assert fs[0].severity == "error"


def test_hot_paths_are_clean():
    """The shipped serving/runtime/kernels trees carry zero host-sync
    findings — the PR's acceptance gate for the AST pass."""
    assert ast_lint.lint_hot_paths() == []


# --------------------------------------------------------------------------
# retrace pass
# --------------------------------------------------------------------------

def test_python_scalar_leaves_flagged():
    fs = jaxpr_lint.python_scalar_leaves(
        {"w": jnp.ones(3), "eps": 0.5, "flag": True}, name="params")
    assert [f.rule for f in fs] == ["RT002", "RT002"]
    # numpy scalars are typed — not flagged
    assert jaxpr_lint.python_scalar_leaves(
        {"eps": np.float32(0.5)}, name="p") == []


def test_trace_stability_oracle():
    grows = jax.jit(lambda x: x + 1)
    fs = jaxpr_lint.trace_stability(
        grows, [(jnp.ones(i),) for i in (1, 2, 3)], name="grows")
    assert [f.rule for f in fs] == ["RT003"]
    assert fs[0].severity == "error"

    stable = jax.jit(lambda x: x * 2)
    assert jaxpr_lint.trace_stability(
        stable, [(jnp.ones(4),)] * 3, name="stable") == []

    # a plain callable exposes no cache: explicit skip, not silence
    fs = jaxpr_lint.trace_stability(lambda x: x, [], name="plain")
    assert [f.rule for f in fs] == ["RT000"]


def test_forward_nodes_bucket_shares_traces(tiny):
    """Regression for the per-node-batch recompile: every batch size in
    one pad bucket must reuse one gather trace (and still gather the
    right rows)."""
    ds, _spec, _ = tiny
    _, spec = _setup()
    exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64)
    logits = np.asarray(exe.forward())
    n = ds.profile.num_nodes
    for k in (1, 2, 3, 5, 8):
        ids = np.arange(k) % n
        np.testing.assert_allclose(np.asarray(exe.forward_nodes(ids)),
                                   logits[ids], rtol=1e-5, atol=1e-6)
    assert jaxpr_lint.cache_size(exe._jit_gather) == 1
    exe.forward_nodes(np.arange(9) % n)       # next bucket: one new trace
    assert jaxpr_lint.cache_size(exe._jit_gather) == 2
    assert exe.forward_nodes(np.arange(0)).shape[0] == 0


# --------------------------------------------------------------------------
# dtype pass
# --------------------------------------------------------------------------

def test_dtype_f64_promotion_flagged():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: jnp.sin(x) * 2.0)(
            jnp.ones(3, jnp.float64))
    fs = jaxpr_lint.dtype_findings(closed, name="fix")
    assert "DT001" in {f.rule for f in fs}
    assert jaxpr_lint.dtype_findings(closed, name="fix",
                                     allow_f64=True) == []


def test_dtype_weak_typed_entry_flagged():
    closed = jax.make_jaxpr(lambda x: x + 1)(3.0)   # Python scalar arg
    fs = jaxpr_lint.dtype_findings(closed, name="fix")
    assert [f.rule for f in fs if f.rule == "DT002"] == ["DT002"]


def test_dtype_int32_overflow_scale_flagged():
    big = jax.ShapeDtypeStruct((2 ** 16, 2 ** 16), jnp.float32)
    closed = jax.make_jaxpr(lambda x: x + 1)(big)   # 2^32 elements, no mem
    fs = jaxpr_lint.dtype_findings(closed, name="fix")
    assert "DT003" in {f.rule for f in fs}


# --------------------------------------------------------------------------
# plan-legality pass
# --------------------------------------------------------------------------

def _plan(ds, arch="gcn", hidden=8, max_n=64):
    spec = ZooSpec(arch, ds.profile.feature_dim, hidden,
                   ds.profile.num_classes, num_layers=2)
    return plan_model(spec, ds.profile.num_nodes, ds.edges.shape[0],
                      max_n=max_n)


def _with_layer(plan, layer):
    return dataclasses.replace(plan, layers=(layer,) + plan.layers[1:])


def test_analytic_plans_clean_every_arch(tiny):
    ds, _, _ = tiny
    for arch in ARCHS:
        plan = _plan(ds, arch)
        for backend in (None, "reference", "pallas"):
            assert plan_lint.check_model_plan(
                plan, backend_name=backend) == [], arch


def test_plan_fixtures_fire_each_rule(tiny):
    ds, _, _ = tiny
    plan = _plan(ds)
    lp = plan.layers[0]

    def rules(p, backend=None):
        return {f.rule for f in plan_lint.check_model_plan(
            p, backend_name=backend)}

    assert "PL001" in rules(_with_layer(
        plan, dataclasses.replace(lp, B=lp.d_agg + 5)))
    assert "PL001" in rules(_with_layer(plan, dataclasses.replace(lp, B=0)))
    assert "PL002" in rules(_with_layer(plan, dataclasses.replace(
        lp, S=lp.S + 3)))
    assert "PL005" in rules(_with_layer(plan, dataclasses.replace(
        lp, order="zigzag")))
    # fused demands linear aggregation: legal on gcn, an error on gin
    fused = _with_layer(plan, dataclasses.replace(lp, fused=True))
    assert rules(fused) == set()
    assert "PL004" in rules(dataclasses.replace(fused, arch="gin"))
    # a fused n=2048 working set (~38 MiB) blows the 16 MiB pallas VMEM
    huge = dataclasses.replace(
        lp, n=2048, S=-(-plan.num_nodes // 2048), B=lp.d_agg, fused=True)
    assert "PL003" in rules(_with_layer(plan, huge), backend="pallas")
    # reddit-scale activation grid: int32 flattened indexing wraps
    wide = dataclasses.replace(lp, d_agg=2 ** 31 // (lp.S * lp.n) + 1)
    assert "PL006" in rules(_with_layer(plan, wide))


def test_executed_digest_ignores_analytic_metadata(tiny):
    ds, _, _ = tiny
    plan = _plan(ds)
    lp = plan.layers[0]
    flipped = _with_layer(plan, dataclasses.replace(
        lp, order="src_stationary" if lp.order == "dst_stationary"
        else "dst_stationary"))
    assert plan_lint.executed_digest(flipped) == \
        plan_lint.executed_digest(plan)
    rebocked = _with_layer(plan, dataclasses.replace(lp, B=max(1, lp.B // 2)))
    assert plan_lint.executed_digest(rebocked) != \
        plan_lint.executed_digest(plan)


def test_prune_keeps_analytic_drops_illegal_and_duplicates(tiny):
    ds, _, _ = tiny
    plan = _plan(ds)
    lp = plan.layers[0]
    order_dup = _with_layer(plan, dataclasses.replace(
        lp, order="src_stationary" if lp.order == "dst_stationary"
        else "dst_stationary"))
    illegal = _with_layer(plan, dataclasses.replace(lp, B=0))
    distinct = _with_layer(plan, dataclasses.replace(lp, B=max(1, lp.B // 2)))

    kept, pruned = plan_lint.prune_candidates(
        [plan, order_dup, illegal, distinct])
    assert kept == [plan, distinct]
    assert [(p["index"], p["reason"]) for p in pruned] == \
        [(1, "duplicate-execution"), (2, "illegal")]
    assert pruned[1]["rules"] == ["PL001"]

    # candidate #0 is the analytic fallback: never pruned, even illegal
    kept, pruned = plan_lint.prune_candidates([illegal, plan])
    assert kept[0] is illegal and not any(p["index"] == 0 for p in pruned)


# --------------------------------------------------------------------------
# comm-contract pass
# --------------------------------------------------------------------------

def _stats(ag_bytes, extra_kind=None):
    wire = {"all-gather": ag_bytes, "all-reduce": 64.0}
    counts = {"all-gather": 2, "all-reduce": 2}
    if extra_kind:
        wire[extra_kind] = 512.0
        counts[extra_kind] = 1
    return CollectiveStats(operand_bytes={}, wire_bytes=wire, counts=counts)


def test_comm_contract_fixtures():
    ok = hlo_lint.check_comm_contract(
        _stats(1000.0), expected_allgather_bytes=1000.0,
        plan_allgather_bytes=1000.0)
    assert ok == []

    meas = hlo_lint.check_comm_contract(
        _stats(1500.0), expected_allgather_bytes=1000.0)
    assert [f.rule for f in meas] == ["CC001"]
    assert meas[0].severity == "error"

    drift = hlo_lint.check_comm_contract(
        _stats(1000.0), expected_allgather_bytes=1000.0,
        plan_allgather_bytes=1200.0)
    assert [f.rule for f in drift] == ["CC002"]

    extra = hlo_lint.check_comm_contract(
        _stats(1000.0, extra_kind="all-to-all"),
        expected_allgather_bytes=1000.0)
    assert [f.rule for f in extra] == ["CC003"]
    assert extra[0].severity == "warning"

    vac = hlo_lint.check_comm_contract(
        CollectiveStats(operand_bytes={}, wire_bytes={}, counts={}),
        expected_allgather_bytes=0.0)
    assert [(f.rule, f.severity) for f in vac] == [("CC004", "info")]


def test_comm_contract_over_comm_stats_dict():
    cs = {"measured_wire_bytes": {"all-gather": 2000.0},
          "measured_counts": {"all-gather": 2},
          "expected_allgather_wire_bytes": 1000.0,
          "plan_allgather_bytes_per_layer": {"0": 600.0, "1": 400.0}}
    fs = hlo_lint.check_comm_stats(cs, location="fixture")
    assert [f.rule for f in fs] == ["CC001"]
    cs["measured_wire_bytes"]["all-gather"] = 1000.0
    assert hlo_lint.check_comm_stats(cs) == []


# --------------------------------------------------------------------------
# integration hooks
# --------------------------------------------------------------------------

def test_analyze_executable_clean_with_probe(tiny):
    ds, _, _ = tiny
    _, spec = _setup()
    exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64)
    rep = analyze_executable(exe, probe=True)
    assert rep.findings == []
    assert "comm" in rep.skipped and "host-sync" in rep.skipped
    assert set(rep.timings_ms) == {"retrace+dtype", "plan"}


def test_compile_analyze_modes(tiny):
    ds, spec, _ = tiny
    with pytest.raises(ValueError, match="analyze"):
        runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                        analyze="loud")
    exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                          analyze="error")
    assert exe.analysis is not None and exe.analysis.findings == []
    off = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                          analyze="off")
    assert off.analysis is None


def test_compile_analyze_error_raises(tiny, monkeypatch):
    ds, spec, _ = tiny
    import repro.analyze as analyze_mod
    bad = Report(findings=[_finding(rule="PL001")])
    monkeypatch.setattr(analyze_mod, "analyze_executable",
                        lambda exe, **kw: bad)
    with pytest.raises(AnalysisError) as err:
        runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                        analyze="error")
    assert err.value.report is bad
    # "warn" downgrades the same report to a UserWarning
    with pytest.warns(UserWarning, match="PL001"):
        exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                              analyze="warn")
    assert exe.analysis is bad


def test_preflight_without_engine_is_hot_path_lint_only():
    rep = preflight()
    assert rep.findings == []
    assert "host-sync" in rep.timings_ms


def test_server_start_analyze_gate(monkeypatch):
    from repro.serving import SchedulerConfig, Server
    from repro.serving.gnn_engine import GNNServeEngine

    ds, spec = _setup()
    engine = GNNServeEngine(backend="reference")
    engine.register_graph("cora", ds)
    engine.register_model("gcn", spec, seed=0)
    srv = Server(engine, SchedulerConfig(max_batch_size=2))

    with pytest.raises(ValueError, match="analyze"):
        srv.start(analyze="bogus")
    assert srv._thread is None

    import repro.analyze as analyze_mod
    bad = Report(findings=[_finding(rule="HS001", pass_name="host-sync")])
    monkeypatch.setattr(analyze_mod, "preflight", lambda eng, **kw: bad)
    with pytest.raises(AnalysisError):
        srv.start(analyze="error")
    assert srv._thread is None          # refused before the driver spawned

    monkeypatch.undo()
    srv.start(analyze="error")          # clean repo: preflight passes
    try:
        assert srv._thread is not None
    finally:
        srv.stop()


def test_cli_gate_clean_on_this_checkout(capsys):
    """`python -m repro.launch.analyze --fail-on error` is the CI gate:
    it must exit 0 on the shipped tree (probes disabled keeps it fast)."""
    from repro.launch import analyze as cli
    rc = cli.main(["--fail-on", "error", "--no-probe"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 error" in out
    rc = cli.main(["--fail-on", "never", "--no-probe", "--json"])
    assert rc == 0
