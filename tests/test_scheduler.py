"""Scheduler/Server invariants (hypothesis) + end-to-end Server tests.

The invariants the continuous-batching scheduler must hold under any
traffic shape:

  * every admitted ticket completes exactly once (Ticket._resolve raises
    on a second resolution, so a clean drain IS the exactly-once proof),
  * FIFO order within equal priority on one stream,
  * expired-deadline requests resolve as Expired — they never vanish and
    never reach the engine,
  * bounded queues reject (typed Rejected, backpressure) rather than grow.

The engine here is a trivial echo so the tests exercise pure scheduling;
the GNN/LM end-to-end paths are covered at the bottom and in
tests/test_serving.py.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving import (Completed, Expired, Failed, Rejected,
                           SchedulerConfig, Server)


class EchoEngine:
    """Routes payload dicts by their 'stream' key; echoes them back."""

    def __init__(self, fail_streams=()):
        self.batches: list[tuple[object, list]] = []
        self.fail_streams = set(fail_streams)

    def route(self, payload):
        if "stream" not in payload:
            raise KeyError("payload has no stream")
        return payload["stream"]

    def step(self, key, payloads):
        if key in self.fail_streams:
            raise RuntimeError(f"engine failure on {key!r}")
        self.batches.append((key, list(payloads)))
        return [dict(p, served=True) for p in payloads]

    def served_order(self, stream=None):
        return [p["i"] for key, batch in self.batches for p in batch
                if stream is None or key == stream]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _server(engine=None, clock=None, **cfg) -> tuple[Server, EchoEngine]:
    engine = engine or EchoEngine()
    srv = Server(engine, SchedulerConfig(**cfg),
                 clock=clock or FakeClock())
    return srv, engine


class TestSchedulerInvariants:
    @settings(deadline=None, max_examples=20)
    @given(n=st.integers(1, 24), batch=st.sampled_from([1, 3, 8]),
           streams=st.integers(1, 3))
    def test_every_admitted_ticket_completes_exactly_once(
            self, n, batch, streams):
        srv, eng = _server(max_batch_size=batch, max_queue_depth=1024)
        tickets = [srv.submit({"stream": i % streams, "i": i})
                   for i in range(n)]
        assert all(t.poll() is None for t in tickets)
        # drain raises if any ticket were resolved twice (_resolve guards)
        assert srv.drain() == n
        assert all(isinstance(t.result(), Completed) for t in tickets)
        m = srv.metrics()
        assert m["completed"] == m["admitted"] == n
        assert srv.drain() == 0          # nothing left, nothing re-runs
        assert sorted(eng.served_order()) == list(range(n))

    @settings(deadline=None, max_examples=20)
    @given(n=st.integers(1, 20), batch=st.sampled_from([1, 2, 8]),
           priority=st.integers(-2, 2))
    def test_fifo_within_equal_priority(self, n, batch, priority):
        srv, eng = _server(max_batch_size=batch, max_queue_depth=1024)
        for i in range(n):
            srv.submit({"stream": "s", "i": i}, priority=priority)
        srv.drain()
        assert eng.served_order("s") == list(range(n))

    @settings(deadline=None, max_examples=20)
    @given(n=st.integers(1, 16), deadline_ms=st.sampled_from([5.0, 50.0]),
           batch=st.sampled_from([2, 8]))
    def test_expired_deadlines_resolve_as_expired(self, n, deadline_ms,
                                                  batch):
        clock = FakeClock()
        srv, eng = _server(clock=clock, max_batch_size=batch,
                           max_queue_depth=1024)
        tickets = [srv.submit({"stream": "s", "i": i},
                              deadline_ms=deadline_ms) for i in range(n)]
        clock.t = deadline_ms / 1e3 + 0.01
        assert srv.drain() == n          # expired tickets don't vanish
        for t in tickets:
            out = t.result()
            assert isinstance(out, Expired)
            assert out.deadline_ms == deadline_ms
            assert out.waited_ms >= deadline_ms
        assert eng.batches == []         # the engine never saw them
        assert srv.metrics()["expired"] == n

    @settings(deadline=None, max_examples=20)
    @given(depth=st.integers(1, 6), extra=st.integers(1, 8))
    def test_bounded_queue_rejects_rather_than_grows(self, depth, extra):
        srv, eng = _server(max_batch_size=2, max_queue_depth=depth)
        tickets = [srv.submit({"stream": "s", "i": i})
                   for i in range(depth + extra)]
        rejected = [t for t in tickets if isinstance(t.poll(), Rejected)]
        assert len(rejected) == extra
        assert all(t.poll().kind == "backpressure" for t in rejected)
        assert srv.metrics()["peak_queue_depth"] == depth
        srv.drain()
        # exactly the admitted prefix was served, in order
        assert eng.served_order("s") == list(range(depth))


class TestSchedulerPolicy:
    def test_priority_then_edf_ordering(self):
        clock = FakeClock()
        srv, eng = _server(clock=clock, max_batch_size=1)
        srv.submit({"stream": "s", "i": 0})                       # prio 0
        srv.submit({"stream": "s", "i": 1}, priority=1,
                   deadline_ms=500.0)                             # prio 1, late dl
        srv.submit({"stream": "s", "i": 2}, priority=1,
                   deadline_ms=100.0)                             # prio 1, early dl
        srv.drain()
        assert eng.served_order("s") == [2, 1, 0]

    def test_starvation_guard_preempts_priority(self):
        clock = FakeClock()
        srv, eng = _server(clock=clock, max_batch_size=2,
                           starvation_ms=100.0)
        srv.submit({"stream": "low", "i": 0}, priority=0)
        clock.t = 0.2                    # low's head is now starving
        srv.submit({"stream": "high", "i": 1}, priority=5)
        assert srv.step(force=True) == 1
        assert eng.batches[0][0] == "low"

    def test_hybrid_formation_max_wait(self):
        clock = FakeClock()
        srv, eng = _server(clock=clock, max_batch_size=4, max_wait_ms=50.0)
        t = srv.submit({"stream": "s", "i": 0})
        assert srv.step() == 0 and t.poll() is None   # underfull, too young
        for i in range(1, 4):
            srv.submit({"stream": "s", "i": i})
        assert srv.step() == 4           # full batch dispatches immediately
        t2 = srv.submit({"stream": "s", "i": 9})
        assert srv.step() == 0
        clock.t = 0.06                   # oldest entry aged past max_wait
        assert srv.step() == 1 and isinstance(t2.poll(), Completed)

    def test_route_rejection_is_typed_not_raised(self):
        srv, eng = _server(max_batch_size=2)
        t = srv.submit({"i": 0})         # no stream -> route raises KeyError
        out = t.poll()
        assert isinstance(out, Rejected) and "KeyError" in out.reason
        assert out.kind == "invalid"
        assert srv.metrics()["rejected"] == 1

    def test_engine_failure_resolves_failed(self):
        srv, eng = _server(EchoEngine(fail_streams={"bad"}),
                           max_batch_size=4)
        tb = srv.submit({"stream": "bad", "i": 0})
        tg = srv.submit({"stream": "good", "i": 1})
        srv.drain()
        assert isinstance(tb.result(), Failed)
        assert "engine failure" in tb.result().error
        assert isinstance(tg.result(), Completed)

    def test_completed_latency_accounting(self):
        clock = FakeClock()
        srv, _ = _server(clock=clock, max_batch_size=8)
        t = srv.submit({"stream": "s", "i": 0})
        clock.t = 0.25                   # queued 250 ms before the dispatch
        out_ = srv.step(force=True)
        out = t.result()
        assert out_ == 1 and isinstance(out, Completed)
        assert out.queue_ms == pytest.approx(250.0)
        assert out.latency_ms == out.queue_ms + out.engine_ms

    def test_background_driver_thread(self):
        srv, eng = _server()             # real-enough: FakeClock at 0 is fine
        srv.start()
        try:
            outs = [srv.submit({"stream": "s", "i": i}).result(timeout_s=10.0)
                    for i in range(5)]
        finally:
            srv.stop()
        assert all(isinstance(o, Completed) for o in outs)
        assert sorted(eng.served_order("s")) == list(range(5))


class TestServerOverGNNEngine:
    @pytest.fixture(scope="class")
    def engine(self):
        from repro.gnn.models import ZooSpec
        from repro.graphs.datasets import make_dataset
        from repro.serving.gnn_engine import GNNServeEngine

        eng = GNNServeEngine(max_shard_n=64, backend="reference")
        ds = make_dataset("cora", seed=0, scale=0.05)
        eng.register_graph("cora", ds)
        eng.register_model("gcn", ZooSpec("gcn", ds.profile.feature_dim, 8,
                                          ds.profile.num_classes,
                                          num_layers=2))
        return eng

    def test_ticketed_results_match_sync_serve(self, engine):
        from repro.serving.gnn_engine import NodeRequest

        reqs = [NodeRequest("cora", np.array([i, i + 3]), model="gcn")
                for i in range(6)]
        srv = Server(engine, SchedulerConfig(max_batch_size=4))
        tickets = [srv.submit(r) for r in reqs]
        srv.drain()
        sync = engine.serve(reqs)
        for t, s in zip(tickets, sync):
            out = t.result()
            assert isinstance(out, Completed)
            np.testing.assert_array_equal(out.value.classes, s.classes)
            np.testing.assert_array_equal(out.value.node_ids, s.node_ids)
            # the Server stamps queue time onto the Prediction itself
            assert out.value.queue_ms == out.queue_ms
            assert out.value.latency_ms == pytest.approx(
                out.queue_ms + out.engine_ms)

    def test_invalid_requests_become_rejected_outcomes(self, engine):
        from repro.serving.gnn_engine import NodeRequest

        srv = Server(engine, SchedulerConfig(max_batch_size=4))
        bad_model = srv.submit(NodeRequest("cora", np.array([0]),
                                           model="nope"))
        bad_graph = srv.submit(NodeRequest("nope", np.array([0]),
                                           model="gcn"))
        bad_ids = srv.submit(NodeRequest("cora", np.array([10 ** 9]),
                                         model="gcn"))
        for t, kind in ((bad_model, "KeyError"), (bad_graph, "KeyError"),
                        (bad_ids, "IndexError")):
            out = t.poll()
            assert isinstance(out, Rejected) and kind in out.reason
        assert srv.queue_depth() == 0
