"""Open-loop serving of a graph that mutates while it is served.

Node requests arrive as a Poisson process at a fixed rate and go through a
``Server`` (driver thread) over a streaming ``GNNServeEngine``; edge deltas
arrive as a second Poisson process and go through ``Server.mutate`` from
their own thread. Each request is timed from when it was due until its
micro-batch was answered, each mutation from when it was due until
``Server.mutate`` returned, so a stall counts against everything due
behind it.

Set-up compiles the model, serves one request, applies one delta and its
inverse (the graph is left with the edge set it started with) and loads
the device-side patch program for every count of patched shard pairs in
``warm_patch_pairs`` (the range a CPU count of the configuration's
deltas spans, with a margin). Every delta of the window comes from the
benchmark's own generator, seeded from the run's seed.

The check takes a sample of the requests answered in the window, drawn
from the seed and holding the longest, and compares each served
probability with the reference's probability of the served class, on the
graph as it stood when the request was answered (the deltas applied up to
then).
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import common, compare, deltas, reference
from bench.harness.program import zoo_spec

GRAPH, MODEL = "graph", "model"


class Recorder:
    """Engine in front of the program's engine: stamps each answered
    request with the time its micro-batch finished and the graph version
    it was answered on (steps and mutations hold the same server lock, so
    the version read here is the one the answers saw)."""

    def __init__(self, engine, step, span):
        self.engine, self._step, self._span = engine, step, span
        self.done: dict[int, tuple[float, int]] = {}

    def route(self, payload):
        return self.engine.route(payload)

    def step(self, key, payloads):
        with self._span("bench.engine_step"):
            out = self._step(key, payloads)
        t = time.perf_counter()
        v = self.engine.graph_version(GRAPH)
        for p in payloads:
            self.done[id(p)] = (t, v)
        return out

    def mutate(self, graph, delta):
        return self.engine.mutate(graph, delta)


def _log(t0: float, msg: str) -> None:
    print(f"[setup] {time.perf_counter() - t0:9.3f} s {msg}", file=sys.stderr,
          flush=True)


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        time.sleep(min(d, 0.002) if d < 0.004 else d - 0.002)


class Run:
    def __init__(self, ctx):
        self.ctx = ctx
        self.p0 = jax.device_get(ctx.params)
        self.tr = ctx.cell.traffic

    def setup(self) -> None:
        from repro.graphs.datasets import GraphData, GraphProfile
        from repro.graphs.delta import GraphDelta
        from repro.serving import Completed, SchedulerConfig, Server
        from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

        self._GraphDelta, self._NodeRequest = GraphDelta, NodeRequest
        self._Completed = Completed
        cfg, g, tr = self.ctx.cell.config, self.ctx.graph, self.tr
        data = GraphData(
            GraphProfile(cfg["name"], g.num_nodes, int(g.edges.shape[0]),
                         g.feature_dim, g.num_classes),
            g.edges.copy(), g.features, g.labels, g.train_mask)
        engine = GNNServeEngine(
            backend=cfg["backend"], plan=cfg["plan"], streaming=True,
            max_shard_n=cfg["shard_n"], edge_slack=tr["edge_slack"])
        engine.register_graph(GRAPH, data)
        engine.register_model(MODEL, zoo_spec(cfg), params=self.ctx.params)
        self.engine = engine
        self.rec = Recorder(engine, self.ctx.hook("engine_step", engine.step),
                            self.ctx.span)
        self.server = Server(self.rec, SchedulerConfig(
            max_batch_size=tr["max_batch_size"],
            max_queue_depth=tr["max_queue_depth"]))

        def serve_one():
            t = self.server.submit(NodeRequest(GRAPH, np.arange(8), MODEL))
            self.server.drain()
            if not isinstance(t.poll(), Completed):
                raise RuntimeError(f"warm-up request not served: {t.poll()}")

        t0 = time.perf_counter()
        serve_one()
        _log(t0, "compiled, first request served")
        dels, adds = deltas.delta_pool(
            g.edges, np.random.default_rng([self.ctx.seed, 5]), 1,
            deletes=tr["deletes"], inserts=tr["inserts"])[0]
        self.server.mutate(GRAPH, GraphDelta(add_edges=adds, del_edges=dels))
        serve_one()
        self.server.mutate(GRAPH, GraphDelta(add_edges=dels, del_edges=adds))
        serve_one()
        _log(t0, "one delta and its inverse applied and served")
        lo, hi = tr["warm_patch_pairs"]
        self._warm_patch_programs(range(lo, hi + 1))
        _log(t0, f"patch programs loaded for {lo}..{hi} shard pairs")
        self.plan(self.ctx.seconds, tr["mutation_rate_per_s"])

    def plan(self, seconds: float, mutation_rate: float) -> None:
        """Draw the window's traffic before it starts. Every seed gets the
        same work in another order: one set of request sizes, of arrival
        gaps and of deltas (valid in any order over the graph as it stands
        now) drawn from the configuration's topology seed, shuffled by the
        run's seed, which also draws the requested node ids."""
        g, tr = self.ctx.graph, self.tr
        self.v0 = self.engine.graph_version(GRAPH)
        self.base_edges = np.array(self.engine.graph_data(GRAPH).edges)
        fixed = np.random.default_rng(
            [self.ctx.cell.config["graph"]["topology_seed"], 6])
        rng = np.random.default_rng([self.ctx.seed, 6])
        self.req_t = deltas.shuffled_arrivals(
            fixed, rng, tr["request_rate_per_s"], seconds)
        sizes = rng.permutation(fixed.integers(
            tr["ids_min"], tr["ids_max"] + 1, size=len(self.req_t)))
        order, cdf = deltas.zipf_by_degree(g.edges, g.num_nodes, tr["zipf_s"])
        self.req_ids = deltas.node_requests(rng, sizes, order, cdf)
        if mutation_rate > 0:
            self.mut_t = deltas.shuffled_arrivals(fixed, rng, mutation_rate,
                                                  seconds)
            pool = deltas.delta_pool(self.base_edges, fixed, len(self.mut_t),
                                     deletes=tr["deletes"],
                                     inserts=tr["inserts"])
            self.deltas = [pool[i] for i in rng.permutation(len(pool))]
        else:
            self.mut_t, self.deltas = np.zeros(0), []

    def _warm_patch_programs(self, pair_counts) -> None:
        """Load the device-side patch update (an indexed set of whole shard
        pairs into a graph array, one program per count of pairs and per
        array type) for each count in ``pair_counts``, with the index and
        value types the patch path uses. The programs compile on several
        threads at once; at most two copies of the grid are made at a
        time."""
        gt = self.engine.executable(MODEL, GRAPH).gt
        S, t0 = gt.S, time.perf_counter()
        # edge_dst has edge_src's shape and type, so it shares its programs
        arrays = (gt.blocks, gt.edge_src, gt.edge_valid)
        grid_copies = threading.Semaphore(2)

        def load(arr, k):
            ai = np.arange(k, dtype=np.int64) // S
            aj = np.arange(k, dtype=np.int64) % S
            vals = jnp.zeros((k,) + tuple(arr.shape[2:]), arr.dtype)
            with grid_copies if arr is gt.blocks else contextlib.nullcontext():
                jax.block_until_ready(arr.at[ai, aj].set(vals))

        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
            jobs = [pool.submit(load, arr, k) for k in pair_counts
                    for arr in arrays]
            for job in jobs:
                job.result()
        _log(t0, f"{len(jobs)} patch programs loaded")

    def window(self, seconds: float) -> dict:
        server, span = self.server, self.ctx.span
        req_n = int(np.searchsorted(self.req_t, seconds))
        mut_n = int(np.searchsorted(self.mut_t, seconds))
        stats0, m0 = self.engine.stats, server.metrics()
        server.start()
        t0 = time.perf_counter() + 0.05
        mut_log = []

        def mutator():
            for i in range(mut_n):
                due = t0 + self.mut_t[i]
                _sleep_until(due)
                t_call = time.perf_counter()
                dels, adds = self.deltas[i]
                ok = True
                try:
                    with span("bench.mutate"):
                        server.mutate(GRAPH, self._GraphDelta(
                            add_edges=adds, del_edges=dels))
                except Exception as err:  # counted as failed, reported
                    ok = False
                    mut_log_err.append(f"{type(err).__name__}: {err}")
                mut_log.append((due, t_call, time.perf_counter(), ok))

        mut_log_err: list[str] = []
        th = threading.Thread(target=mutator, name="bench-mutator")
        th.start()
        sent = []
        for i in range(req_n):
            due = t0 + self.req_t[i]
            _sleep_until(due)
            payload = self._NodeRequest(GRAPH, self.req_ids[i], MODEL)
            t_sub = time.perf_counter()
            with span("bench.submit"):
                ticket = server.submit(payload)
            sent.append((payload, due, t_sub, ticket))
        th.join()
        t_end = t0 + seconds
        give_up = max(time.perf_counter(), t_end) + self.tr["drain_s"]
        for _, _, _, ticket in sent:
            try:
                ticket.result(timeout_s=max(give_up - time.perf_counter(), 0))
            except TimeoutError:
                pass
        t_last = time.perf_counter()
        server.stop(drain=False)
        stats1, m1 = self.engine.stats, server.metrics()

        lat, answered, failed = [], [], 0
        for payload, due, _, ticket in sent:
            out = ticket.poll()
            if isinstance(out, self._Completed) and id(payload) in self.rec.done:
                t_done, v = self.rec.done[id(payload)]
                lat.append((t_done - due) * 1e3)
                answered.append((payload.node_ids, out.value, v))
            else:
                failed += 1
                lat.append((t_last - due) * 1e3)
        mlat = [(t_ret - due) * 1e3 for due, _, t_ret, _ in mut_log]
        mfail = sum(1 for *_, ok in mut_log if not ok)
        self.answered = answered
        self.last = {"mut_log": [(due - t0, t_call - due, t_ret - due, ok)
                                 for due, t_call, t_ret, ok in mut_log],
                     "req_lat_ms": lat}
        self.applied = [self.deltas[i] for i, rec in enumerate(mut_log)
                        if rec[3]]
        req_late = [(t_sub - due) * 1e3 for _, due, t_sub, _ in sent]
        mut_late = [(t_call - due) * 1e3 for due, t_call, _, _ in mut_log]
        d = lambda k: stats1[k] - stats0[k]  # noqa: E731
        dm = lambda k: m1[k] - m0[k]  # noqa: E731
        notes = [
            f"{req_n} requests ({failed} not answered), {mut_n} mutations "
            f"({mfail} failed)",
            f"generator lateness ms: requests p50 "
            f"{common.percentile(req_late, 50):.3f} max "
            f"{max(req_late, default=0):.3f}; mutations p50 "
            f"{common.percentile(mut_late, 50):.3f} max "
            f"{max(mut_late, default=0):.3f}",
            f"device forwards {d('logits_cache_misses')}, patches "
            f"{d('graph_patches')} ({d('graph_patch_rebuilds')} rebuilt), "
            f"recompiles {d('graph_recompiles')}"]
        notes += mut_log_err[:3]
        return {
            "attempted": req_n + mut_n, "failed": failed + mfail,
            "window_s": seconds,
            "e2e": {"serve_p95_ms": common.percentile(lat, 95),
                    "mutate_p95_ms": common.percentile(mlat, 95),
                    "serve_p50_ms": common.percentile(lat, 50),
                    "mutate_p50_ms": common.percentile(mlat, 50)},
            "counters": {
                "queue_ms_total": dm("queue_ms_total"),
                "engine_ms_total": dm("engine_ms_total"),
                "completed": dm("completed"),
                "logits_cache_misses": d("logits_cache_misses"),
                "mutations": d("mutations"),
                "mutate_ms_total": d("mutate_ms_total"),
                "graph_patches": d("graph_patches"),
                "graph_patch_ms_total": d("graph_patch_ms_total")},
            "notes": notes}

    def release(self) -> None:
        self.server = self.engine = self.rec = None

    def check(self, control: str | None = None) -> dict:
        """Root mean square, over every node answer of the sampled
        requests, of the served probability less the reference's
        probability of the served class; ``control`` answers with the
        reference computed in that precision instead of the program."""
        g, tr = self.ctx.graph, self.tr
        rng = np.random.default_rng([self.ctx.seed, 7])
        n = len(self.answered)
        if n == 0:
            return {"prob_rms": float("inf")}
        pick = set(rng.choice(n, size=min(n, tr["check_requests"]),
                              replace=False).tolist())
        pick.add(max(range(n), key=lambda i: len(self.answered[i][0])))
        by_version: dict[int, list] = {}
        for i in pick:
            ids, pred, v = self.answered[i]
            by_version.setdefault(v - self.v0, []).append((ids, pred))
        p0 = jax.tree.map(jnp.asarray, self.p0)
        ref_fwd = reference.Forward(self.ctx.ref_mod, g.num_nodes)
        ctl_fwd = None if control is None else reference.Forward(
            self.ctx.ref_mod, g.num_nodes, control)
        edges, errs = self.base_edges, []
        for k in range(max(by_version) + 1):
            if k in by_version:
                ref_p = compare.softmax(ref_fwd(p0, g.features, edges))
                if ctl_fwd is not None:
                    ctl_p = compare.softmax(ctl_fwd(p0, g.features, edges))
                for ids, pred in by_version[k]:
                    ids = np.asarray(ids)
                    if ctl_fwd is None:
                        classes, probs = pred.classes, pred.probs
                    else:
                        classes = ctl_p[ids].argmax(-1)
                        probs = ctl_p[ids].max(-1)
                    errs.append(compare.prob_errors(
                        np.asarray(classes), np.asarray(probs, np.float64),
                        ref_p[ids]))
            if k < len(self.applied):
                edges = deltas.apply_delta(edges, *self.applied[k])
        e = np.concatenate(errs)
        return {"prob_rms": float(np.sqrt(np.mean(e * e)))}
