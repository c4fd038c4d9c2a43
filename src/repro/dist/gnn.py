"""Multi-device sharded GNN execution (`runtime.compile(..., mesh=...)`).

The paper's 2-D shard grid generalizes directly to a device mesh:

  * the **data** axis owns dst row groups: each data group aggregates its
    own destination nodes via the shard-grid SpMM kernel
    (``kernels/shard_spmm`` handles the rectangular
    local-rows × full-source-grid blocks). WHICH rows a group owns is the
    :class:`~repro.graphs.partition.PartitionPlan`'s choice:
    ``partition="contiguous"`` keeps the historical contiguous dst-shard
    ranges; ``partition="fennel"`` places individual vertices by the
    locality/degree-aware streaming partitioner and this executable
    applies the plan's row permutation when building the padded row
    groups (and inverts it on output, so logits are bit-identical in
    order to the single-device path);
  * the **model** axis owns feature blocks — the distributed
    generalization of the paper's dimension-blocking: each model device
    aggregates only its ceil(D/n_model) feature slice, and the dense
    stage reduces the partial products with a ``psum`` (row-parallel
    matmul);
  * per layer, each device gathers the cross-group source rows of its
    feature block over the data axis. Contiguous plans all-gather EVERY
    row; fennel plans exchange only the **hub broadcast** (top-k
    out-degree rows replicated to every group — GNNIE's graph-specific
    caching) plus the **halo all-gather** (each group's non-hub boundary
    vertices), and layer 0 is free entirely: the permuted input features
    enter the shard_map replicated (they arrive replicated at the jit
    boundary anyway), so the first layer reads its sources locally.
    Measured collective volume (parsed from the compiled HLO by
    ``dist/hlo_analysis.py``) is verified against the plan models in
    :meth:`ShardedExecutable.verify_comm`.

Supported zoo architectures: the linear-aggregation family (``gcn``,
``sage_mean``, ``gin``). ``sage_max`` (edge-list max pooling) and ``gat``
(per-head attention grids) need sharded gather/attention plumbing that is
out of scope here and raise ``NotImplementedError`` at compile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist.hlo_analysis import analyze_collectives
from repro.graphs.partition import PartitionPlan, partition_graph
from repro.kernels.ref import _activate
from repro.runtime.executable import Executable
from repro.runtime.forward import layer_activation

SUPPORTED_ARCHS = ("gcn", "sage_mean", "gin")
PARTITION_METHODS = ("contiguous", "fennel")

_F32 = 4

# mesh layout of each graph jit argument, as the shard_map reads it
_CONTIGUOUS_ARG_SPECS = (P("data", None, None, None),)          # blocks
_FENNEL_ARG_SPECS = (P("data", None, None, None),  # permuted blocks
                     P(None),                      # perm_src
                     P("data", None), P("data", None),  # hub/halo send
                     P(None), P(None))             # hub/halo recv


def _pad_last(x, size: int):
    """Zero-pad the trailing (feature) dim up to ``size``."""
    pad = size - x.shape[-1]
    if pad <= 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


def _feature_block(x, m, bm: int, n_model: int):
    """This model-device's feature block: pad D to bm·n_model, slice
    [m·bm, (m+1)·bm) off the last dim. ``m`` is a traced axis index."""
    xp = _pad_last(x, bm * n_model)
    return jax.lax.dynamic_slice_in_dim(xp, m * bm, bm, axis=x.ndim - 1)


def _weight_block(w, row_off: int, rows: int, m, bm: int, n_model: int):
    """Rows [row_off, row_off+rows) of ``w``, zero-padded to bm·n_model
    rows, then this model-device's bm-row block — the row-parallel half of
    the partial matmul (zero rows pair with zero-padded features)."""
    wp = jnp.pad(w[row_off:row_off + rows],
                 ((0, bm * n_model - rows), (0, 0)))
    return jax.lax.dynamic_slice_in_dim(wp, m * bm, bm, axis=0)


class ShardedExecutable(Executable):
    """An :class:`~repro.runtime.executable.Executable` whose jitted
    forward runs under ``shard_map`` on a ``(data, model)`` mesh.

    Everything above the forward — the cached full-graph softmax,
    ``predict``/``step`` serving entry points, plan/param serialization —
    is inherited unchanged: the sharded forward returns the same (N, C)
    logits, just computed across the mesh (fennel plans un-permute the
    row order outside the measured jit module).
    """

    def __init__(self, *, mesh, partition: str = "contiguous",
                 hub_cache: int = 256, partition_slack: float = 0.0, **kw):
        sizes = dict(mesh.shape)
        if set(sizes) != {"data", "model"}:
            raise ValueError(
                f"sharded execution needs a ('data', 'model') mesh "
                f"(launch.mesh.make_mesh_for builds one); got axes "
                f"{tuple(sizes)}")
        if partition not in PARTITION_METHODS:
            raise ValueError(f"partition must be one of "
                             f"{PARTITION_METHODS}; got {partition!r}")
        spec, gt = kw["spec"], kw["gt"]
        if spec.arch not in SUPPORTED_ARCHS:
            raise NotImplementedError(
                f"sharded execution supports {SUPPORTED_ARCHS}; "
                f"{spec.arch!r} needs sharded gather/attention kernels")
        self.mesh = mesh
        self.n_data = sizes["data"]
        self.n_model = sizes["model"]
        self.partition_method = partition
        self.hub_cache = int(hub_cache)
        # capacity headroom on the fennel hub/halo send slots (>0 for
        # mutable graphs so streaming deltas stay within the compiled
        # template; 0 for frozen graphs = tightest wire volume)
        self.partition_slack = float(partition_slack)
        # pad the shard grid so every data group owns the same number of
        # dst rows (trailing padded rows/slots hold zero nodes/edges)
        self.rows_per_device = -(-gt.S // self.n_data)
        self.S_pad = self.rows_per_device * self.n_data
        # memoized (gt, plan, args) of a pending update_graph build, so
        # the base class's template check and our adoption share one
        # (re-)partition instead of computing it twice
        self._pending_build = None
        if partition == "contiguous":
            self._blocks_padded = self._padded_blocks(gt)
            self.partition: PartitionPlan = partition_graph(
                gt, self.n_data, pad=True)
            self._cached_args = (self._blocks_padded,)
        else:
            self.partition, self._cached_args = \
                self._build_partition_args(gt)
            self._inv_slots = jnp.asarray(self.partition.slot_of)
        super().__init__(**kw)

    # -- graph arrays -> jit arguments ---------------------------------------

    def _place(self, args, specs) -> tuple:
        """Commit graph arrays to the mesh in the layout the shard_map
        reads them — each data group's rows on its own devices — instead
        of leaving every array on the first device for each call to
        re-distribute."""
        return tuple(jax.device_put(a, NamedSharding(self.mesh, s))
                     for a, s in zip(args, specs))

    def _padded_blocks(self, gt):
        """The contiguous method's grid, zero-padded to S_pad rows/cols
        (no copy when S divides by n_data) and placed on the mesh."""
        pad = self.S_pad - gt.S
        blocks = gt.blocks if pad == 0 else jnp.pad(
            gt.blocks, ((0, pad), (0, pad), (0, 0), (0, 0)))
        return self._place((blocks,), _CONTIGUOUS_ARG_SPECS)[0]

    def _permute_blocks(self, gt, perm: np.ndarray):
        """Apply the plan's row permutation to the dense normalized grid:
        flatten to (S·n, S·n), append a zero row/col for empty slots,
        gather rows AND cols by slot -> original id, reshape back to the
        padded (S_pad, S_pad, n, n) grid. Pure data movement — every
        coefficient is copied, so sharded outputs stay allclose with the
        single-device forward."""
        S, n = gt.S, gt.n
        N = S * n
        A = jnp.asarray(gt.blocks).transpose(0, 2, 1, 3).reshape(N, N)
        A = jnp.pad(A, ((0, 1), (0, 1)))
        idx = jnp.asarray(np.where(np.asarray(perm) < 0, N,
                                   np.asarray(perm)), jnp.int32)
        Ap = A[idx][:, idx]
        return Ap.reshape(self.S_pad, n, self.S_pad, n).transpose(0, 2, 1, 3)

    def _build_partition_args(self, gt, *, refine_nodes=None):
        """(plan, graph_args) for ``gt`` under the fennel method. After
        construction the compiled hub/halo capacities are pinned: a graph
        delta that no longer fits raises ValueError (from
        ``partition_graph``) — the stale-build invalidation contract the
        serving engine's mutate path catches with a recompile."""
        prev = pinned_hub = pinned_halo = None
        cur = getattr(self, "partition", None)
        if cur is not None and cur.method == "fennel":
            pinned_hub, pinned_halo = cur.hub_cap, cur.halo_cap
            if cur.node_group is not None and \
                    len(cur.node_group) == gt.S * gt.n:
                prev = cur.node_group
        plan = partition_graph(
            gt, self.n_data, method="fennel", hub_cache=self.hub_cache,
            slack=self.partition_slack, prev_groups=prev,
            refine_nodes=refine_nodes if prev is not None else None,
            hub_cap=pinned_hub, halo_cap=pinned_halo)
        # index arrays enter the jit as ARGUMENTS (same RT003 contract as
        # the graph tensors): a re-partition after a streaming delta swaps
        # them without retracing as long as the capacities hold
        args = (self._permute_blocks(gt, plan.perm),
                np.where(plan.perm < 0, gt.S * gt.n,
                         plan.perm).astype(np.int32),
                plan.hub_send, plan.halo_send, plan.hub_recv,
                plan.halo_recv)
        return plan, self._place(args, _FENNEL_ARG_SPECS)

    # -- graph argument plumbing --------------------------------------------

    def _graph_args_of(self, gt):
        """The sharded forward consumes the dense grid (permuted for
        fennel plans) plus the plan's index arrays; linear-aggregation
        archs never touch the per-shard COO arrays."""
        if self.partition_method == "contiguous":
            if gt is self.gt and hasattr(self, "_blocks_padded"):
                return (self._blocks_padded,)
            return (self._padded_blocks(gt),)
        if gt is self.gt:
            return self._cached_args
        if self._pending_build is not None and self._pending_build[0] is gt:
            return self._pending_build[2]
        plan, args = self._build_partition_args(gt)
        self._pending_build = (gt, plan, args)
        return args

    def update_graph(self, gt, h_grouped=None, *, stale_nodes=None,
                     refine_nodes=None) -> int:
        if self.partition_method == "fennel":
            # re-partition FIRST, warm-started from the current placement
            # with the delta-affected vertices re-scored (the patch's
            # pair rows when given, else the k-hop stale set); raises
            # ValueError on capacity overflow before anything is adopted
            refine = refine_nodes if refine_nodes is not None \
                else stale_nodes
            plan, args = self._build_partition_args(gt, refine_nodes=refine)
            self._pending_build = (gt, plan, args)
            n = super().update_graph(gt, h_grouped,
                                     stale_nodes=stale_nodes)
            self.partition, self._cached_args = plan, args
            self._inv_slots = jnp.asarray(plan.slot_of)
            self._pending_build = None
            return n
        n = super().update_graph(gt, h_grouped, stale_nodes=stale_nodes)
        # refresh the cached padded grid and the comm/balance plan for the
        # post-delta graph (template checks already passed in super())
        self._blocks_padded = self._padded_blocks(gt)
        self.partition = partition_graph(gt, self.n_data, pad=True)
        self._cached_args = (self._blocks_padded,)
        return n

    # -- the sharded forward ------------------------------------------------

    def _forward_graph_fn(self):
        spec, be, plans = self.spec, self.backend, self.plan.layers
        gt, mesh = self.gt, self.mesh
        n_model, S_pad, n, S = self.n_model, self.S_pad, gt.n, gt.S
        rows_loc = self.rows_per_device
        method = self.partition_method

        def layer_body(i, layer, blocks_loc, hb_loc, hb_full, m, d):
            """One zoo layer on this device's dst rows + feature block.
            ``hb_loc`` is this group's rows of the block, ``hb_full`` the
            full (S_pad, n, bm) source block (however it was exchanged);
            ``d`` is the TRUE (unpadded) input feature dim."""
            plan = plans[i]
            act = layer_activation(spec, i)
            bm = hb_loc.shape[-1]
            s_loc = hb_loc.shape[0]
            agg = be.graph_aggregate(blocks_loc, hb_full, block_b=plan.B)
            if spec.arch == "gcn":
                wb = _weight_block(layer["w"], 0, d, m, bm, n_model)
                z = be.dense_matmul(agg.reshape(s_loc * n, bm), wb)
            elif spec.arch == "sage_mean":
                # cat([agg, h]) @ w == agg @ w[:d] + h @ w[d:]
                w1 = _weight_block(layer["w"], 0, d, m, bm, n_model)
                w2 = _weight_block(layer["w"], d, d, m, bm, n_model)
                z = (be.dense_matmul(agg.reshape(s_loc * n, bm), w1)
                     + be.dense_matmul(hb_loc.reshape(s_loc * n, bm), w2))
            else:  # gin: two-matmul MLP — psum between them too
                x = (1.0 + layer["eps"]) * hb_loc + agg
                w1 = _weight_block(layer["w1"], 0, d, m, bm, n_model)
                hid = jax.lax.psum(
                    be.dense_matmul(x.reshape(s_loc * n, bm), w1)
                    .astype(jnp.float32), "model") + layer["b1"]
                hid = jax.nn.relu(hid)
                dh = hid.shape[-1]
                bm2 = -(-dh // n_model)
                hid_b = _feature_block(hid, m, bm2, n_model)
                w2 = _weight_block(layer["w2"], 0, dh, m, bm2, n_model)
                z = jax.lax.psum(
                    be.dense_matmul(hid_b, w2).astype(jnp.float32),
                    "model") + layer["b2"]
                return _activate(z, act).astype(hb_loc.dtype) \
                    .reshape(s_loc, n, -1)
            # row-parallel partials -> full output columns on every device
            z = jax.lax.psum(z.astype(jnp.float32), "model")
            return _activate(z, act).astype(hb_loc.dtype) \
                .reshape(s_loc, n, -1)

        if method == "contiguous":
            def device_fn(p, blocks_loc, h_loc):
                m = jax.lax.axis_index("model")
                for i, layer in enumerate(p["layers"]):
                    d = h_loc.shape[-1]
                    bm = -(-d // n_model)
                    # distributed dimension-blocking: slice this device's
                    # feature block FIRST, then all-gather only that
                    # block's source rows over the data axis
                    hb_loc = _feature_block(h_loc, m, bm, n_model)
                    hb_full = jax.lax.all_gather(hb_loc, "data", axis=0,
                                                 tiled=True)
                    h_loc = layer_body(i, layer, blocks_loc, hb_loc,
                                       hb_full, m, d)
                return h_loc

            p_specs = jax.tree.map(lambda _: P(), self.params)
            smap = jax.shard_map(device_fn, mesh=mesh,
                                 in_specs=(p_specs,
                                           P("data", None, None, None),
                                           P("data", None, None)),
                                 out_specs=P("data", None, None),
                                 check_vma=False)

            def fwd(p, h, blocks_padded):
                # the padded grid enters as a jit argument (streaming
                # deltas swap it in-template); padded-row logits are
                # sliced to true N outside jit by the base class
                hp = jnp.pad(h, ((0, S_pad - S), (0, 0), (0, 0)))
                out = smap(p, blocks_padded, hp)
                return out.reshape(S_pad * n, -1)

            return fwd

        # -- fennel: hub broadcast + halo all-gather --------------------------
        hub_cap, halo_cap = self.partition.hub_cap, self.partition.halo_cap
        loc_n = rows_loc * n

        def assemble(hb_loc, g, hub_send, halo_send, hub_recv, halo_recv):
            """Build the full (S_pad, n, bm) source block for layers >= 1:
            scatter the hub broadcast and the halo all-gather into a
            zero buffer (dummy slots land on a sacrificial trailing row),
            then overwrite this group's own slot range last — overlapped
            slots carry identical values, and the overwrite keeps the
            scatter's cotangents zero there (AD-correct)."""
            bm = hb_loc.shape[-1]
            flat = hb_loc.reshape(loc_n, bm)
            own = jnp.concatenate([flat, jnp.zeros((1, bm), flat.dtype)])
            buf = jnp.zeros((S_pad * n + 1, bm), flat.dtype)
            if hub_cap:
                hub_all = jax.lax.all_gather(own[hub_send], "data",
                                             axis=0, tiled=True)
                buf = buf.at[hub_recv].set(hub_all)
            if halo_cap:
                halo_all = jax.lax.all_gather(own[halo_send], "data",
                                              axis=0, tiled=True)
                buf = buf.at[halo_recv].set(halo_all)
            buf = jax.lax.dynamic_update_slice(
                buf, flat, (g * loc_n, jnp.int32(0)))
            return buf[:-1].reshape(S_pad, n, bm)

        def device_fn(p, blocks_loc, hp_rep, hub_send, halo_send,
                      hub_recv, halo_recv):
            m = jax.lax.axis_index("model")
            g = jax.lax.axis_index("data")
            hub_send, halo_send = hub_send[0], halo_send[0]
            h_loc = jax.lax.dynamic_slice_in_dim(
                hp_rep, g * rows_loc, rows_loc, axis=0)
            for i, layer in enumerate(p["layers"]):
                d = h_loc.shape[-1]
                bm = -(-d // n_model)
                hb_loc = _feature_block(h_loc, m, bm, n_model)
                if i == 0:
                    # layer 0 is collective-free: the permuted input is
                    # replicated at the shard_map boundary (it enters the
                    # jit replicated anyway), so sources are local
                    hb_full = _feature_block(hp_rep, m, bm, n_model)
                else:
                    hb_full = assemble(hb_loc, g, hub_send, halo_send,
                                       hub_recv, halo_recv)
                h_loc = layer_body(i, layer, blocks_loc, hb_loc,
                                   hb_full, m, d)
            return h_loc

        p_specs = jax.tree.map(lambda _: P(), self.params)
        smap = jax.shard_map(
            device_fn, mesh=mesh,
            in_specs=(p_specs, P("data", None, None, None),
                      P(None, None, None), P("data", None),
                      P("data", None), P(None), P(None)),
            out_specs=P("data", None, None),
            check_vma=False)

        def fwd(p, h, blocks_perm, perm_src, hub_send, halo_send,
                hub_recv, halo_recv):
            # permute features into slot order (dummy slot -> appended
            # zero row); the gather runs on the replicated input, so it
            # lowers collective-free
            d = h.shape[-1]
            hflat = h.reshape(S * n, d)
            hpad = jnp.concatenate([hflat, jnp.zeros((1, d), h.dtype)])
            hp = hpad[perm_src].reshape(S_pad, n, d)
            out = smap(p, blocks_perm, hp, hub_send, halo_send,
                       hub_recv, halo_recv)
            # NOTE: returned logits are in SLOT order — the inverse
            # permutation is applied outside this (comm-measured) module
            return out.reshape(S_pad * n, -1)

        return fwd

    # -- forward entry points (fennel un-permutes outside jit) --------------

    def forward(self, params: dict | None = None, features=None):
        if self.partition_method == "contiguous":
            return super().forward(params, features)
        p = self.params if params is None else params
        ga = self._graph_args()
        if features is None:
            if self._h_grouped is None:
                raise ValueError("compiled without features; pass features=")
            out = self._jit_forward(p, self._h_grouped, *ga)
        else:
            h = self.gt.group(jnp.asarray(features))
            if self._jit_forward_donate is not None:
                out = self._jit_forward_donate(p, h, *ga)
            else:
                out = self._jit_forward(p, h, *ga)
        # un-permute OUTSIDE the measured jit module: a gather on the
        # sharded output inside it would add an unmodeled collective to
        # the comm contract; this reuses the bucketless jitted gather
        return self._jit_gather(out, self._inv_slots)[: self.gt.num_nodes]

    def _forward_with_args(self):
        if self.partition_method == "contiguous":
            return super()._forward_with_args()
        g = self._forward_graph_fn()
        num_nodes = self.gt.num_nodes

        # training/analysis view: the inverse permutation rides INSIDE
        # this composition (fit's step jit needs end-to-end
        # differentiability); train comm checks are lower bounds, so the
        # extra gather collectives there are fine
        def fn(p, h, *ga):
            *graph, inv = ga
            return g(p, h, *graph)[inv][:num_nodes]

        return fn, (*self._graph_args(), self._inv_slots)

    # -- communication accounting ------------------------------------------

    def _layer_allgather_bytes(self) -> list[float]:
        """Analytic per-layer all-gather wire bytes of the program above
        (the hlo_analysis convention: gathered result × (g-1)).

        ``contiguous``: each model device gathers its ceil(d/n_model)
        feature block of every row — (n_data-1)·S_pad·n·bm·4 per layer.

        ``fennel``: layer 0 is collective-free (replicated input); every
        later layer ships the hub broadcast + halo all-gather —
        (n_data-1)·n_data·(hub_cap+halo_cap)·bm·4."""
        out = []
        for i, (d, _) in enumerate(self.spec.layer_dims):
            bm = -(-d // self.n_model)
            if self.partition_method == "fennel":
                caps = self.partition.hub_cap + self.partition.halo_cap
                out.append(0.0 if i == 0 else float(
                    (self.n_data - 1) * self.n_data * caps * bm * _F32))
            else:
                out.append(float((self.n_data - 1) * self.S_pad * self.gt.n
                                 * bm * _F32))
        return out

    def comm_stats(self) -> dict:
        """Measured (compiled-HLO) vs modeled cross-device traffic.

        ``measured_*`` come from :func:`dist.hlo_analysis.analyze_collectives`
        over the actual compiled module; ``expected_allgather_wire_bytes``
        is the analytic model above; ``plan_*`` are the PartitionPlan's
        graph-level models (dedup pulls, halo broadcast, hub broadcast)."""
        p_avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
            self.params)
        h_aval = jax.ShapeDtypeStruct((self.gt.S, self.gt.n,
                                       self.spec.in_dim), jnp.float32)
        # the graph arrays keep their mesh placement: the verified
        # program is the one the forward runs
        g_avals = [jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                        sharding=a.sharding)
                   for a in self._graph_args()]
        hlo = self._jit_forward.lower(p_avals, h_aval,
                                      *g_avals).compile().as_text()
        stats = analyze_collectives(hlo)
        dims = [d for d, _ in self.spec.layer_dims]
        fennel = self.partition_method == "fennel"

        def plan_ag(i, d):
            if fennel and i == 0:
                return 0.0
            return self.partition.allgather_bytes_per_layer(
                -(-d // self.n_model), self.gt.n, dtype_bytes=_F32)

        def plan_hub(i, d):
            if not fennel or i == 0:
                return 0.0
            return self.partition.hub_bytes_per_layer(
                -(-d // self.n_model), dtype_bytes=_F32)

        return {
            "n_data": self.n_data,
            "n_model": self.n_model,
            "partition_method": self.partition_method,
            "hub_rows": self.partition.hub_rows,
            "hub_cap": self.partition.hub_cap,
            "halo_cap": self.partition.halo_cap,
            "measured_wire_bytes": dict(stats.wire_bytes),
            "measured_counts": dict(stats.counts),
            "measured_allgather_wire_bytes":
                stats.wire_bytes.get("all-gather", 0.0),
            "expected_allgather_wire_bytes":
                sum(self._layer_allgather_bytes()),
            "plan_transfer_bytes_per_layer": {
                str(i): self.partition.transfer_bytes_per_layer(
                    d, dtype_bytes=_F32)
                for i, d in enumerate(dims)},
            "plan_allgather_bytes_per_layer": {
                str(i): plan_ag(i, d) for i, d in enumerate(dims)},
            "plan_hub_bytes_per_layer": {
                str(i): plan_hub(i, d) for i, d in enumerate(dims)},
            "cross_group_edge_frac": self.partition.cross_group_edge_frac,
        }

    def verify_comm(self, rtol: float = 0.02) -> dict:
        """Assert the measured all-gather volume matches both the analytic
        per-layer model and the PartitionPlan's broadcast model — hub
        terms included (same quantity derived from the plan instead of
        the program, catching drift on either side). The check itself is
        the comm-contract pass
        (:func:`repro.analyze.hlo_lint.check_sharded_executable`) — this
        wrapper turns its error findings into an AssertionError. Returns
        :meth:`comm_stats`."""
        from repro.analyze.hlo_lint import check_comm_stats
        cs = self.comm_stats()
        findings = check_comm_stats(cs, rtol=rtol)
        errors = [f for f in findings if f.severity == "error"]
        assert not errors, "\n".join(f.render() for f in errors)
        return cs

    # -- introspection -----------------------------------------------------

    def producer_orders(self) -> list[tuple[str, int]]:
        """None to report: ``layer_body`` aggregates first in every layer,
        over each device's share of the grid."""
        return []

    def summary(self) -> str:
        head = super().summary()
        plan = self.partition
        extra = ""
        if plan.method == "fennel":
            extra = (f" hubs={plan.hub_rows} "
                     f"(caps hub={plan.hub_cap} halo={plan.halo_cap})")
        return (head + f"\nmesh: data={self.n_data} model={self.n_model} "
                f"partition={plan.method} "
                f"rows/group={self.rows_per_device} (grid padded "
                f"{self.gt.S}->{self.S_pad}) "
                f"cross-group edges {plan.cross_group_edge_frac:.1%}, "
                f"edge imbalance {plan.edge_imbalance:.2f}x" + extra)
