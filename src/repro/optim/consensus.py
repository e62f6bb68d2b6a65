"""Consensus-ADMM distributed training — the paper's technique at LLM scale.

The mesh's ``pod`` axis carries the ADMM graph: each pod is one node i holding
its own full parameter replica theta_i (FSDP/TP-sharded *within* the pod).
Between consensus rounds each pod takes H local optimizer steps on its own
data shard (f_i = local loss). A consensus round then performs, entirely along
the pod axis (the scarce DCN tier):

  1. neighbor exchange of theta (circulant ppermute per graph offset,
     optionally quantized through a pluggable wire codec — int8 per-leaf or
     fp8 per-block, ``repro.wire`` — the dual update absorbs the error),
  2. objective probes f_i(theta_j) on a held-out probe batch (eq. 7 kappas),
  3. the proximal parameter pull + dual update (fused: one HBM pass),
  4. local residuals (eq. 5) and the per-edge penalty update (eq. 4/6/9/12)
     via the same ``repro.core.penalty`` engine the D-PPCA reproduction uses.

Compared to synchronous DP all-reduce every step, cross-pod traffic drops by
~H x and each edge's pull strength eta_ij adapts per the paper — the
"adaptive, dynamic network topology" of Fig. 1c realized on a TPU fabric.

Implementation: the round runs on the flat-buffer engine (``optim.flatten``,
``docs/consensus_engine.md``): params pack into one [J, total] buffer
(leading node axis sharded P('pod', ...)), the exchange is ``jnp.roll`` on
the node axis (GSPMD lowers it to one collective-permute per graph offset),
and the fused update is a single Pallas call inside a shard_map that is
manual over ALL mesh axes. No partial-manual regions: GSPMD-inside-manual
miscompiles at 512 devices (spmd_partitioner_util.cc crash), so everything
else stays plain GSPMD with data/model auto (FSDP/TP/EP untouched).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.async_exec.ledger import AsyncConfig, WireLedger, init_wire_ledger
from repro.core.graph import Graph, build_graph
from repro.core.penalty import (PenaltyConfig, PenaltyState, effective_eta,
                                freeze_penalty, init_penalty_state,
                                update_penalty)
from repro.models.model import Model, arch_rules
from repro.distributed import sharding as shd
from repro.kernels import ref as kref
from repro.obs import node_ring as obs_node_ring
from repro.obs import ring as obs_ring
from repro.obs import schema as obs_schema
from repro.obs import trace as obs_trace
from repro.obs.ring import ObsConfig
from repro.optim import adamw as adamw_lib
from repro.optim import flatten
from repro.topology import (TopologyConfig, TopologyRuntime, TopologyState,
                            active_edge_fraction, compose_mask, sym_age,
                            tick_age)
from repro import wire as wire_lib


@dataclasses.dataclass(frozen=True)
class ConsensusConfig:
    penalty: PenaltyConfig = PenaltyConfig(scheme="nap", eta0=1.0)
    topology: str = "ring"         # circulant: ring | complete | expander
    local_steps: int = 8           # H — local optimizer steps per round
    prox_step: float = 0.5         # alpha in the prox pull (scaled by curv.)
    compression: str = "none"      # legacy spelling: none | int8
    # wire codec for the consensus exchange (repro.wire): native | int8 |
    # fp8_e4m3 | fp8_e5m2. Empty resolves from `compression` ("none" ->
    # native), keeping the legacy knob working; a non-empty value wins.
    wire_codec: str = ""
    use_fused_kernel: bool = True  # Pallas consensus_round (interpret on CPU)
    block_size: int = 0            # flat-layout block; 0 => auto
    grad_rs: bool = False          # reduce-scatter grads to param shards
    # shard the flat consensus state (lam / theta_bar_prev / wire / ledger)
    # over the in-pod mesh axes: P('pod', ('data', 'model', ...)). Each
    # device then runs the fused kernel on only its flat-axis slab and
    # per-device consensus-state HBM shrinks by the in-pod axis size.
    # False keeps the PR 1-3 replicated-in-pod path byte-identical.
    shard_consensus: bool = False
    # dynamic-topology runtime (repro.topology): the default static
    # scheduler without churn keeps the engine on the exact PR 1 code path
    dyn_topology: TopologyConfig = TopologyConfig()
    # bounded-staleness async executor (repro.async_exec): None keeps the
    # trainer strictly synchronous; max_staleness=0 enables the async step
    # functions but waits for every payload (bit-identical to sync)
    async_exec: AsyncConfig | None = None
    # latency-hiding round pipeline: how many graph offsets' collective-
    # permutes may be in flight ahead of the decode/probe consume point.
    # 1 (default) is the strictly sequential permute-then-consume loop;
    # >= 2 issues permutes early behind optimization_barriers, landing
    # them in the WireLedger double buffer, and consumes them in offset
    # order — numerically bit-identical at every depth (pinned), the
    # depth only widens the window the latency-hiding scheduler may
    # overlap. Pair with launch.mesh.set_backend_flags().
    pipeline_offsets: int = 1
    # observability (repro.obs): the on-device metrics ring + trace spans.
    # None (and ObsConfig(enabled=False)) leaves the compiled step
    # byte-identical to a build without the subsystem
    obs: ObsConfig | None = None


# jitted replica init per (model, AdamW config, J, mesh): trainers that
# differ only in their round configuration share one compile
_REPLICA_INIT: dict = {}


class TrainState(NamedTuple):
    params: Any            # [J, ...] per-node replicas, P('pod', ...)
    opt: adamw_lib.AdamWState
    lam: jax.Array         # [J, total] flat dual buffer (FlatLayout)
    theta_bar_prev: jax.Array  # [J, total] flat neighbor mean (eq. 5)
    penalty: PenaltyState  # [J, J] replicated
    step: jax.Array
    topo: TopologyState    # [J, J] replicated — dynamic-topology runtime
    ledger: Any = None     # WireLedger [deg, J, W] — async executor only
    ring: Any = None       # obs.MetricsRing [cap, n_metrics] — obs only
    node_ring: Any = None  # obs.NodeRing [cap, J, n_node_cols] — obs only


def _leading(tree, spec_fn):
    """Map ParamDef-spec tree -> specs with leading 'pod' axis."""
    return jax.tree_util.tree_map(lambda s: P(*(("pod",) + tuple(s))),
                                  spec_fn)


class ConsensusTrainer:
    """Builds jit-able train_step / consensus_step for a model on a mesh."""

    def __init__(self, model: Model, mesh: Mesh, *,
                 adamw: adamw_lib.AdamWConfig, consensus: ConsensusConfig):
        self.model = model
        self.mesh = mesh
        self.acfg = adamw
        self.ccfg = consensus
        self.has_pod = mesh is not None and "pod" in mesh.axis_names
        self.num_nodes = int(mesh.shape["pod"]) if self.has_pod else 1
        self.graph: Graph = build_graph(consensus.topology, self.num_nodes) \
            if self.num_nodes > 1 else build_graph("complete", 1)
        # dynamic-topology runtime: offsets come from ITS superset (equal to
        # the graph's circulant offsets unless churn adds spare offsets)
        self.topo_cfg = consensus.dyn_topology
        self.topo_cfg.validate_penalty(consensus.penalty)
        self.topo_rt = TopologyRuntime(self.graph, self.topo_cfg)
        self.dynamic = self.topo_cfg.is_dynamic and self.num_nodes > 1
        self.offsets = self.topo_rt.offsets if self.num_nodes > 1 else []
        # async executor (repro.async_exec): staleness gating engages the
        # masked kernel path even under a static scheduler
        self.async_cfg = consensus.async_exec
        # latency-hiding round pipeline (docs/consensus_engine.md "Round
        # pipeline"): depth 1 keeps the exact sequential loop; >= 2 issues
        # offset permutes early and lands them in the WireLedger, which
        # the sync path then carries too (needs_ledger)
        self.pipeline_depth = max(1, int(consensus.pipeline_offsets))
        self.pipelined = self.pipeline_depth > 1 and self.num_nodes > 1
        self.needs_ledger = self.num_nodes > 1 \
            and (self.async_cfg is not None or self.pipelined)
        # rules for *inside* the pod-manual region: batch maps to data only
        rules = arch_rules(model.cfg, mesh)
        rules["batch"] = ("data",)
        self.inner_rules = rules
        # in-pod sharding of the flat consensus state: one shard per device
        # position on the non-pod mesh axes (the engine's shard grid)
        self.inner_axes, inner_size = shd.inpod_axes(
            mesh if self.has_pod else None)
        self.sharded = bool(consensus.shard_consensus) \
            and self.num_nodes > 1 and inner_size > 1
        self.n_shards = inner_size if self.sharded else 1
        # static flat-buffer layout for the consensus engine (shards=1 is
        # byte-identical to the unsharded PR 1 layout)
        ap = model.abstract_params()
        bs = consensus.block_size or flatten.auto_block_size(ap)
        self.layout = flatten.FlatLayout.for_tree(ap, block_size=bs,
                                                  node_axis=False,
                                                  shards=self.n_shards)
        self.slayout = self.layout.shard(self.n_shards) if self.sharded \
            else None
        # the pluggable wire codec (repro.wire) every wire producer and
        # consumer goes through: trainer encode/decode, ledger row sizing,
        # kernel dequant granularity, probe-side unpack
        self.codec_name = wire_lib.resolve_codec_name(
            consensus.wire_codec or consensus.compression)
        self.codec = wire_lib.get_codec(self.codec_name, self.layout,
                                        self.slayout)
        self.dequant_spec = self.codec.kernel_dequant_spec()
        # observability (repro.obs): the metrics ring rides in TrainState
        # and trace spans wrap the round phases — both fully gated, so an
        # obs-off trainer lowers byte-identical HLO (tests/test_obs.py)
        self.obs_cfg = consensus.obs
        self.obs_on = self.obs_cfg is not None and self.obs_cfg.enabled
        self.node_ring_on = self.obs_on and self.obs_cfg.with_node_ring
        self._span = obs_trace.span_factory(
            self.obs_on and self.obs_cfg.with_spans)

    # ------------------------------------------------------------ state ----
    def _node_stack(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (self.num_nodes,) + x.shape),
            tree)

    def init_state(self, key: jax.Array) -> TrainState:
        """Fresh state, built already laid out by ``state_shardings``.

        With a pod axis the node replicas (params, AdamW moments) are made
        inside one jit whose outputs are sharded, and the flat buffers are
        made sharded, so each node's state is created on its own pod's
        devices; made eagerly, all J replicas would first land on one
        device, which at published widths does not fit.
        """
        if not self.has_pod:
            return self._assemble(*self._replicas(key))
        sh = self.state_shardings()
        cache_key = (self.model, self.acfg, self.num_nodes, self.mesh)
        make = _REPLICA_INIT.get(cache_key)
        if make is None:     # one compile per model, optimizer and mesh
            make = jax.jit(self._replicas, out_shardings=(sh.params, sh.opt))
            _REPLICA_INIT[cache_key] = make
        state = self._assemble(*make(key), flat_sharding=sh.lam)
        return jax.device_put(state, sh)

    def _replicas(self, key: jax.Array):
        """(params, AdamW state): one init broadcast to the J nodes."""
        with shd.use_mesh(self.mesh, self.inner_rules):
            params1 = self.model.init(key)
        opt1 = adamw_lib.init(self.acfg, params1)
        return self._node_stack(params1), adamw_lib.AdamWState(
            step=opt1.step, m=self._node_stack(opt1.m),
            v=self._node_stack(opt1.v))

    def _assemble(self, params, opt, flat_sharding=None) -> TrainState:
        # two distinct buffers (never aliased: the state may be donated)
        flat_shape = (self.num_nodes, self.layout.total)
        ledger = None
        if self.needs_ledger:
            ledger = init_wire_ledger(self.layout, len(self.offsets),
                                      self.num_nodes, codec=self.codec)
        return TrainState(
            params=params, opt=opt,
            lam=jnp.zeros(flat_shape, jnp.float32, device=flat_sharding),
            theta_bar_prev=jnp.zeros(flat_shape, jnp.float32,
                                     device=flat_sharding),
            penalty=init_penalty_state(self.ccfg.penalty, self.num_nodes),
            step=jnp.zeros((), jnp.int32),
            topo=self.topo_rt.init_state(),
            ledger=ledger,
            ring=(obs_ring.init_ring(self.obs_cfg.ring_capacity)
                  if self.obs_on else None),
            node_ring=(obs_node_ring.init_node_ring(
                self.obs_cfg.ring_capacity, self.num_nodes)
                if self.node_ring_on else None))

    def abstract_state(self) -> TrainState:
        """ShapeDtypeStruct mirror for the dry-run (no allocation)."""
        ap = self.model.abstract_params()

        def stack(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    (self.num_nodes,) + s.shape, s.dtype), tree)

        params = stack(ap)
        opt1 = adamw_lib.abstract_state(self.acfg, ap)
        opt = adamw_lib.AdamWState(step=opt1.step, m=stack(opt1.m),
                                   v=stack(opt1.v))
        flat0 = jax.ShapeDtypeStruct((self.num_nodes, self.layout.total),
                                     jnp.float32)
        pen = init_penalty_state(self.ccfg.penalty, self.num_nodes)
        pen = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), pen)
        topo = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            self.topo_rt.init_state())
        ledger = None
        if self.needs_ledger:
            ledger = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                init_wire_ledger(self.layout, len(self.offsets),
                                 self.num_nodes, codec=self.codec))
        ring = None
        if self.obs_on:
            ring = obs_ring.MetricsRing(
                buf=jax.ShapeDtypeStruct(
                    (self.obs_cfg.ring_capacity, obs_schema.NUM_COLUMNS),
                    jnp.float32),
                head=jax.ShapeDtypeStruct((), jnp.int32))
        node_ring = None
        if self.node_ring_on:
            node_ring = obs_node_ring.NodeRing(
                buf=jax.ShapeDtypeStruct(
                    (self.obs_cfg.ring_capacity, self.num_nodes,
                     obs_schema.NUM_NODE_COLUMNS), jnp.float32),
                head=jax.ShapeDtypeStruct((), jnp.int32))
        return TrainState(params=params, opt=opt, lam=flat0,
                          theta_bar_prev=flat0, penalty=pen,
                          step=jax.ShapeDtypeStruct((), jnp.int32),
                          topo=topo, ledger=ledger, ring=ring,
                          node_ring=node_ring)

    def state_shardings(self) -> TrainState:
        """NamedShardings for every state leaf (pod-leading params etc.)."""
        mesh = self.mesh
        with shd.use_mesh(mesh, self.inner_rules):
            pspec = self.model.param_specs()

        def lead(tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, P(*(("pod",) + tuple(s)))),
                tree, is_leaf=lambda s: isinstance(s, P))

        params_sh = lead(pspec)

        def like_params(tree_of_specs):
            return tree_of_specs

        opt_m = lead(pspec)
        ap = self.model.abstract_params()
        if self.acfg.factored:
            # factored leaves mirror param spec minus trailing dims;
            # factorability decided by SHAPE (mirror adamw._is_factorable)
            def fv(s, p):
                s = tuple(s)
                if len(p.shape) >= 2 and p.shape[-1] > 1 and p.shape[-2] > 1:
                    return (NamedSharding(mesh, P(*(("pod",) + s[:-1]))),
                            NamedSharding(mesh,
                                          P(*(("pod",) + s[:-2] + s[-1:]))))
                return NamedSharding(mesh, P(*(("pod",) + s)))
            opt_v = jax.tree_util.tree_map(
                fv, pspec, ap, is_leaf=lambda s: isinstance(s, P))
        else:
            opt_v = lead(pspec)
        rep = NamedSharding(mesh, P())
        pen = jax.tree_util.tree_map(lambda _: rep,
                                     init_penalty_state(self.ccfg.penalty,
                                                        self.num_nodes))
        # flat buffers: node-sharded rows; with shard_consensus each pod's
        # row additionally splits over the in-pod axes (one slab per device
        # — see docs/consensus_engine.md "Sharded layout"), otherwise it is
        # replicated within the pod (the PR 1 path)
        flat_sh = NamedSharding(mesh, self._flat_pspec())
        topo_sh = jax.tree_util.tree_map(lambda _: rep,
                                         self.topo_rt.init_state())
        ledger_sh = None
        if self.needs_ledger:
            # wire rows shard like the stacked payloads in the fused round
            ledger_sh = WireLedger(
                wires=NamedSharding(mesh, self._flat_pspec(3)), round=rep,
                w_prev=rep)
        # the metrics rings are tiny ([cap, n_metrics] / [cap, J, n_cols]
        # f32) and read by the host drain: replicate them like the other
        # telemetry state (node-ring rows hold the POST-psum per-node
        # residuals, identical on every device by construction)
        ring_sh = obs_ring.MetricsRing(buf=rep, head=rep) \
            if self.obs_on else None
        node_ring_sh = obs_node_ring.NodeRing(buf=rep, head=rep) \
            if self.node_ring_on else None
        return TrainState(
            params=params_sh,
            opt=adamw_lib.AdamWState(step=rep, m=opt_m, v=opt_v),
            lam=flat_sh, theta_bar_prev=flat_sh,
            penalty=pen, step=rep, topo=topo_sh, ledger=ledger_sh,
            ring=ring_sh, node_ring=node_ring_sh)

    # ------------------------------------------------------- local steps ----
    def _local_loss(self, params, batch):
        with shd.use_mesh(self.mesh, self.inner_rules):
            loss, metrics = self.model.loss(params, batch)
        return loss, metrics

    def train_step(self, state: TrainState, batch: Any
                   ) -> tuple[TrainState, dict]:
        """One local optimizer step on every node (no cross-pod traffic)."""
        if not self.has_pod:
            def step1(params, opt, batch):
                (loss, _), grads = jax.value_and_grad(
                    self._local_loss, has_aux=True)(params, batch)
                p, o, m = adamw_lib.update(self.acfg, opt, params, grads)
                return p, o, loss, m["grad_norm"]

            p1 = jax.tree_util.tree_map(lambda x: x[0], state.params)
            o1 = adamw_lib.AdamWState(
                step=state.opt.step,
                m=jax.tree_util.tree_map(lambda x: x[0], state.opt.m),
                v=jax.tree_util.tree_map(lambda x: x[0], state.opt.v))
            b1 = jax.tree_util.tree_map(lambda x: x[0], batch)
            p, o, loss, gn = step1(p1, o1, b1)
            new = state._replace(
                params=jax.tree_util.tree_map(lambda x: x[None], p),
                opt=adamw_lib.AdamWState(
                    step=o.step,
                    m=jax.tree_util.tree_map(lambda x: x[None], o.m),
                    v=jax.tree_util.tree_map(lambda x: x[None], o.v)),
                step=state.step + 1)
            return new, {"loss": loss, "grad_norm": gn}

        # vmap over the node axis: per-node loss/grad/update with NO cross-pod
        # communication (GSPMD shards the leading axis on 'pod'). vmap is
        # preferred over pod-manual shard_map — see consensus_step docstring.
        # MoE archs fall back to a sequential per-node loop (the inner EP
        # shard_map has no vmap batching rule); a production multi-pod MoE
        # deployment runs per-pod controllers instead (DESIGN.md §5).
        def one_node(params, m, v, opt_step, batch):
            (loss, _), grads = jax.value_and_grad(
                self._local_loss, has_aux=True)(params, batch)
            if self.ccfg.grad_rs:
                with shd.use_mesh(self.mesh, self.inner_rules):
                    pspec = self.model.param_specs()
                grads = jax.tree_util.tree_map(
                    lambda g, s: jax.lax.with_sharding_constraint(
                        g, NamedSharding(self.mesh, s)),
                    grads, pspec)
            opt = adamw_lib.AdamWState(step=opt_step, m=m, v=v)
            p_new, opt_new, mtr = adamw_lib.update(self.acfg, opt, params,
                                                   grads)
            return p_new, opt_new.m, opt_new.v, loss, mtr["grad_norm"]

        if self.model.cfg.moe is not None:
            outs = []
            for i in range(self.num_nodes):
                sl = lambda t: jax.tree_util.tree_map(lambda x: x[i], t)
                outs.append(one_node(sl(state.params), sl(state.opt.m),
                                     sl(state.opt.v), state.opt.step,
                                     sl(batch)))
            stack = lambda k: jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[o[k] for o in outs])
            p_new, m_new, v_new = stack(0), stack(1), stack(2)
            loss = jnp.stack([o[3] for o in outs])
            gn = jnp.stack([o[4] for o in outs])
        else:
            p_new, m_new, v_new, loss, gn = jax.vmap(
                one_node, in_axes=(0, 0, 0, None, 0))(
                state.params, state.opt.m, state.opt.v, state.opt.step,
                batch)
        new = state._replace(
            params=p_new,
            opt=adamw_lib.AdamWState(step=state.opt.step + 1, m=m_new,
                                     v=v_new),
            step=state.step + 1)
        return new, {"loss": loss.mean(), "grad_norm": gn}

    # --------------------------------------------------- consensus round ----
    def _probe_vloss(self):
        """Per-node objective probe function (shared by sync/async rounds).

        MoE blocks carry an inner expert-parallel shard_map, which XLA
        cannot batch under vmap — probe those sequentially per node
        (plain GSPMD forwards; J and degree are small).
        """
        j = self.num_nodes
        sequential = self.model.cfg.moe is not None

        def vloss(params, batch):
            if sequential:
                outs = []
                for i in range(j):
                    p_i = jax.tree_util.tree_map(lambda x: x[i], params)
                    b_i = jax.tree_util.tree_map(lambda x: x[i], batch)
                    outs.append(self._local_loss(p_i, b_i)[0])
                return jnp.stack(outs)
            return jax.vmap(lambda p, b: self._local_loss(p, b)[0])(
                params, batch)

        return vloss

    def _finish_round(self, new: TrainState, metrics: dict,
                      node_metrics: dict | None = None
                      ) -> tuple[TrainState, dict]:
        """Every consensus round's single exit: schema + metrics rings.

        Unifies the metrics dict to the full ``obs.schema.ROUND_METRICS``
        key set (sync, async, replicated and sharded rounds all emit
        IDENTICAL keys — pinned by tests/test_obs.py) and, with obs
        enabled, appends the round's row to the on-device metrics ring
        (one ``dynamic_update_slice``; the host drains every K rounds).
        ``node_metrics`` is the per-node dict of ``[J]`` vectors for the
        node ring (``obs.schema.NODE_METRICS``; missing keys pad to the
        defined not-applicable values) — appended the same way when
        ``ObsConfig.with_node_ring`` is on.
        """
        metrics = obs_schema.unify_round_metrics(metrics)
        if self.obs_on and new.ring is not None:
            row = obs_schema.metrics_row(new.step, metrics)
            new = new._replace(ring=obs_ring.ring_append(new.ring, row))
        if self.node_ring_on and new.node_ring is not None:
            nrow = obs_schema.node_row(new.step, node_metrics or {},
                                       self.num_nodes)
            new = new._replace(
                node_ring=obs_node_ring.node_ring_append(new.node_ring,
                                                         nrow))
        return new, metrics

    def _flat_pspec(self, ndim: int = 2) -> P:
        """THE spelling of the flat-buffer sharding, at any rank.

        ``[..., J, total]`` -> ``P(None, ..., 'pod', <in-pod axes>)`` when
        sharded, ``P(None, ..., 'pod', None)`` (replicated in-pod)
        otherwise. Every site that shards a flat buffer — state
        shardings, ledger rows, constraints, the fused-round shard_map
        specs — derives from here, so the scheme can only change in one
        place.
        """
        lead = (None,) * (ndim - 2)
        tail = self.inner_axes if self.sharded else None
        return P(*lead, "pod", tail)

    def _constrain_flat(self, x):
        """Pin a [J, total]-shaped value to the engine's flat sharding.

        Sharded mode only (a no-op otherwise): keeps GSPMD from choosing
        in-pod replication for the packed buffers between the pack/encode
        ops and the manual fused-round region.
        """
        if not self.sharded:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self._flat_pspec(x.ndim)))

    def _encode_wire(self, theta_flat):
        """Flat buffer -> the wire message the permutes move.

        One call into the configured codec (``repro.wire``): native passes
        the packed buffer through, int8/fp8 quantize with their scale
        bytes in-band. Sharded wires are per-shard self-contained slabs
        (see ``docs/wire_formats.md``), pinned to the engine's flat
        sharding so each device encodes only its slab.
        """
        with self._span("wire/encode"):
            wire = self.codec.encode(theta_flat)
        if self.sharded:
            return self._constrain_flat(wire)
        return wire

    def _decode_wire(self, wire):
        """Wire message -> (payload [J, total], scales [J, W] | None).

        ``W`` is the codec's scale width: num_leaves for the int8 tail,
        num_blocks for the fp8 per-block scales (which shard with the
        slabs — slab-local decode, no in-pod broadcast).
        """
        with self._span("wire/decode"):
            payload, scales = self.codec.decode(wire)
        if self.sharded:
            payload = self._constrain_flat(payload)
            if scales is not None and self.dequant_spec.per_block:
                scales = self._constrain_flat(scales)
        return payload, scales

    def _probe_params(self, payload, scales):
        """Decoded (payload, scales) -> the probe forward's param pytree.

        Sharded mode first pins the payload (and per-block scales) to an
        in-pod-REPLICATED sharding — ONE all-gather of the slab-resident
        buffer per offset — so the per-leaf unpack slices below are
        device-local. Without the pin, every leaf slice crossing a slab
        boundary pays its own in-pod resharding collective (the PR 4
        known cost, one per leaf per offset). Collective count pinned in
        tests/test_consensus_fused.py.
        """
        if self.sharded:
            rep = NamedSharding(self.mesh, P("pod", None))
            payload = jax.lax.with_sharding_constraint(payload, rep)
            if scales is not None and self.dequant_spec.per_block:
                scales = jax.lax.with_sharding_constraint(scales, rep)
        return self.codec.unpack(payload, scales)

    def _fused_round(self, theta_flat, lam_flat, bar_prev, wires, scales,
                     e_stack, alpha, sym_sum, eta_node,
                     bar_w=None, inv_deg=None, kick_w=None):
        """One shard_map'd Pallas call over the whole flat buffer.

        Manual over ALL mesh axes with nothing but the kernel inside — the
        historical GSPMD-inside-manual miscompile does not apply because the
        region contains no auto-sharded ops. Each device runs the kernel on
        its pod's node row: the whole row (replicated across the in-pod
        axes) by default, or — with ``shard_consensus`` — only its in-pod
        slab of the flat axis, with the per-shard block->leaf table riding
        as a traced operand and the blockwise residual partials finished by
        ONE psum over the in-pod axes.

        ``bar_w``/``inv_deg`` (dynamic topology) ride next to e_sym / the
        node scalars: the traced edge gates select the masked kernel.
        ``kick_w`` (zero-kick absorption for newly-gated edges) is one more
        [deg, J] operand next to the gates.
        """
        from repro.kernels import ops as kops

        lay = self.layout
        sharded = self.sharded
        inner = self.inner_axes
        masked = bar_w is not None
        kicked = kick_w is not None
        per_block = self.dequant_spec.per_block
        pod = P("pod")
        flat_spec = self._flat_pspec(2)
        wires_spec = self._flat_pspec(3)
        # per-leaf scale rows are replicated in-pod (global leaf ids);
        # per-block rows (fp8) shard with the slabs, so each device's
        # kernel reads its own blocks' scales at local block ids
        scales_spec = self._flat_pspec(3) if per_block \
            else P(None, "pod", None)

        # node scalars ride as one stacked [3|4, J] SMEM block; the traced
        # edge gates / kick weights (when present) are extra [deg, J]
        # operands; the sharded path appends its [n_shards, blocks/shard]
        # block->leaf table, sharded so each device reads its slab's row
        rows = [alpha, sym_sum, eta_node] + ([inv_deg] if masked else [])
        node_sc = jnp.stack(rows, axis=0)
        args = [theta_flat, lam_flat, bar_prev, wires, scales, e_stack] \
            + ([bar_w] if masked else []) + ([kick_w] if kicked else []) \
            + [node_sc]
        in_specs = (flat_spec, flat_spec, flat_spec,
                    wires_spec, scales_spec,
                    P(None, "pod")) \
            + ((P(None, "pod"),) if masked else ()) \
            + ((P(None, "pod"),) if kicked else ()) + (P(None, "pod"),)
        if sharded:
            args.append(jnp.asarray(self.slayout.block_leaf_shards,
                                    jnp.int32))
            in_specs += (P(inner, None),)

        def local(theta, lam, barp, w, s, e, *rest):
            rest = list(rest)
            bw = rest.pop(0) if masked else None
            kw = rest.pop(0) if kicked else None
            nsc = rest.pop(0)
            out = kops.consensus_round(
                theta, lam, barp, w, s, e, nsc[0], nsc[1], nsc[2],
                block_leaf=(None if sharded
                            else tuple(lay.block_leaf.tolist())),
                block_leaf_arr=rest.pop(0)[0] if sharded else None,
                block_size=lay.block_size,
                bar_w=bw, inv_deg=nsc[3] if masked else None, kick_w=kw,
                scales_per_block=per_block)
            if sharded:
                # finish the blockwise residual partials across the slab
                # grid: ONE psum over the in-pod axes per reduction
                tn, ln, bar, rsq, ssq = out
                out = (tn, ln, bar, jax.lax.psum(rsq, inner),
                       jax.lax.psum(ssq, inner))
            return out

        fn = shd.shard_map(
            local, self.mesh, in_specs=in_specs,
            out_specs=(flat_spec, flat_spec, flat_spec, pod, pod))
        with self._span("consensus/fused_round"):
            return fn(*args)

    def consensus_step(self, state: TrainState, probe_batch: Any
                       ) -> tuple[TrainState, dict]:
        """One ADMM consensus round along the pod axis (flat-buffer engine).

        Per round: pack params once into the [J, total] wire buffer, then

          * exchange — ONE ``jnp.roll`` per graph offset on the pod-sharded
            node axis (GSPMD lowers it to a collective-permute of the whole
            contiguous buffer; int8 wire carries its bitcast scales in-band),
          * objective probes f_i(theta_j) on the held-out probe batch
            (eq. 7 kappas) straight off the rolled payloads,
          * ONE fused Pallas call (``kernels.consensus_round``) for
            dequant + neighbor means + prox pull + dual update + both
            residual reductions (eq. 5) — or the blockwise-identical jnp
            reference when ``use_fused_kernel=False``,
          * the per-edge penalty update (eq. 4/6/9/12) via
            ``repro.core.penalty``.

        No partial-manual shard_map around GSPMD ops: the XLA SPMD
        partitioner miscompiles GSPMD-inside-manual at 512 devices; the
        fused kernel runs under a fully-manual region instead.
        """
        if self.num_nodes <= 1:
            return self._finish_round(state, {
                "r_max": jnp.zeros(()),
                "eta_mean": jnp.asarray(self.ccfg.penalty.eta0)})
        j = self.num_nodes
        offsets = self.offsets
        deg = len(offsets)
        adj = jnp.asarray(self.graph.adj)
        pcfg = self.ccfg.penalty
        idx = jnp.arange(j)
        lay = self.layout
        dynamic = self.dynamic

        vloss = self._probe_vloss()

        # probe own objective (pre-update params, eq. 7 semantics)
        with self._span("consensus/probe"):
            f_self = vloss(state.params, probe_batch)          # [J]

        # pack in the params' native float dtype: the uncompressed wire then
        # moves the same bytes/param as the old per-leaf exchange (bf16 = 2B)
        with self._span("consensus/pack"):
            theta_flat = self._constrain_flat(
                lay.pack(state.params, dtype=lay.wire_dtype))
            wire = self._encode_wire(theta_flat)

        eta = state.penalty.eta
        ones = jnp.ones((j, self.dequant_spec.scale_width), jnp.float32)
        sym_sum = jnp.zeros((j,), jnp.float32)
        f_nbr = jnp.zeros((j, j), jnp.float32)
        payloads, scale_rows, e_rows = [], [], []
        topo = state.topo
        # scheduler zero-kick (engine side): consume the pending kick
        # weights stored when edges gated at the END of the last round —
        # their neighbors' parameters are on THIS round's wire
        kick_on = dynamic and self.topo_cfg.can_gate
        kick_rows = []
        if dynamic:
            mask_f = topo.mask.astype(jnp.float32)
            act = jnp.zeros((j,), jnp.float32)
            w_rows = []
            payload_dtype = self.codec.payload_dtype
        # per-node wire accounting for the node ring: offsets whose permute
        # ran AND whose payload this node consumed (mask or pending kick)
        rx = jnp.zeros((j,), jnp.float32) if self.node_ring_on else None

        # ---- pipelined issue phase (pipeline_offsets >= 2) ---------------
        # Reuse the async executor's WireLedger as the sync path's double
        # buffer: raw rolled wire rows are issued AHEAD of the consume
        # loop (up to `depth` permutes in flight before any decode/probe
        # work) and read back in offset order. Each issue past the first
        # window ties to the consume token of the offset `depth` earlier
        # through an optimization_barrier — a real data dependency that
        # bounds the in-flight window — and the latency-hiding scheduler
        # (launch.mesh.set_backend_flags) overlaps the permutes with the
        # earlier offsets' decode/probe compute. Bit-identical to the
        # sequential loop at every depth: only scheduling freedom grows.
        pipelined = self.pipelined
        skip_dead = dynamic and self.topo_cfg.skip_dead_offsets
        if pipelined:
            assert state.ledger is not None, \
                "init_state builds the wire ledger for pipeline_offsets>=2"
            depth = min(self.pipeline_depth, deg)
            inflight: list = [None] * deg
            needs: list = [None] * deg
            if skip_dead:
                for d0, off0 in enumerate(offsets):
                    jidx0 = (idx + off0) % j
                    m0 = mask_f[idx, jidx0]
                    needs[d0] = m0.sum() if not kick_on \
                        else m0.sum() + topo.kick[idx, jidx0].sum()

            def _issue_row(d, token=None):
                src = wire
                if token is not None:
                    src, _ = jax.lax.optimization_barrier((src, token))

                def _roll(src=src, off_d=offsets[d]):
                    # same barrier discipline as the sequential _exchange:
                    # pins the wire dtype; the span brackets the real wire
                    with self._span(f"consensus/exchange/off{off_d}"):
                        return jax.lax.optimization_barrier(
                            jnp.roll(src, -off_d, axis=0))

                if needs[d] is None:
                    return _roll()
                # dead-offset skip with the permute issued a step early:
                # hold last round's ledger row (never decoded — the dead
                # branch below skips the consume entirely)
                return jax.lax.cond(needs[d] > 0, _roll,
                                    lambda: state.ledger.wires[d])

            for d0 in range(depth):
                inflight[d0] = _issue_row(d0)

        for d, off in enumerate(offsets):
            jidx = (idx + off) % j

            def _exchange(d=d, off=off):
                if pipelined:
                    # consume the pre-issued row from the double buffer
                    payload, scales = self._decode_wire(inflight[d])
                else:
                    # rolled[i] = wire_{(i+off) % j}: ONE collective-
                    # permute on pod moving the whole contiguous buffer
                    # (payload + in-band scales). The barrier pins the
                    # exchange to the wire dtype — without it XLA hoists
                    # the consumers' f32 upcast above the permute and a
                    # bf16 wire would cross the DCN at 4 B/param.
                    with self._span(f"consensus/exchange/off{off}"):
                        rolled = jax.lax.optimization_barrier(
                            jnp.roll(wire, -off, axis=0))
                        payload, scales = self._decode_wire(rolled)
                with self._span("consensus/probe"):
                    f_off = vloss(self._probe_params(payload, scales),
                                  probe_batch)
                return payload, (ones if scales is None else scales), f_off

            if dynamic:
                m_off = mask_f[idx, jidx]                          # [J]
                k_off = topo.kick[idx, jidx] if kick_on else None
                if self.topo_cfg.skip_dead_offsets:
                    # an all-gated offset round skips its permute AND its
                    # probe at runtime; the mask is replicated so every
                    # device takes the same branch. The dead branch probes
                    # f_self (a no-op for the eq. 8 extremes). A pending
                    # zero-kick keeps the offset alive: the absorption term
                    # needs the gated neighbor's payload off the wire.
                    def _dead():
                        return (jnp.zeros((j, lay.total), payload_dtype),
                                ones, f_self)

                    need = needs[d] if pipelined \
                        else (m_off.sum() if not kick_on
                              else m_off.sum() + k_off.sum())
                    payload, scales_row, f_off = jax.lax.cond(
                        need > 0, _exchange, _dead)
                    executed = (need > 0).astype(jnp.float32)
                else:
                    payload, scales_row, f_off = _exchange()
                    executed = jnp.ones((), jnp.float32)
                if self.node_ring_on:
                    consumed = m_off + k_off if kick_on else m_off
                    rx = rx + executed * (consumed > 0).astype(jnp.float32)
                if kick_on:
                    kick_rows.append(k_off)
                # the traced gate flows into the edge weights: a masked
                # edge costs zero math in the fused kernel
                e_sym = 0.5 * (eta[idx, jidx] + eta[jidx, idx]) * m_off
                act = act + m_off
                w_rows.append(m_off)
            else:
                payload, scales_row, f_off = _exchange()
                if self.node_ring_on:
                    rx = rx + 1.0
                e_sym = 0.5 * (eta[idx, jidx] + eta[jidx, idx])    # [J]
            # scatter-free write of F[i, (i+off)%j]: static circulant mask
            # (an .at[].set scatter costs extra collective-permutes on SPMD)
            mask = jnp.asarray(np.roll(np.eye(j), off, axis=1), jnp.float32)
            f_nbr = f_nbr + f_off[:, None] * mask
            sym_sum = sym_sum + e_sym
            payloads.append(payload)
            scale_rows.append(scales_row)
            e_rows.append(e_sym)
            if pipelined and d + depth < deg:
                # bounded window: the next issue waits (only) on this
                # offset's consume token
                inflight[d + depth] = _issue_row(d + depth, token=f_off)

        wires = self._constrain_flat(jnp.stack(payloads))  # [deg, J, total]
        scales = jnp.stack(scale_rows)              # [deg, J, L]
        e_stack = jnp.stack(e_rows)                 # [deg, J]

        # -- fused round: dequant + means + prox + dual + residuals --------
        alpha = self.ccfg.prox_step / (1.0 + 2.0 * sym_sum)    # [J]
        if dynamic:
            # active-degree neighbor mean; ghosts (degree 0) get bar = 0
            inv_deg = jnp.where(act > 0, 1.0 / jnp.maximum(act, 1.0), 0.0)
            eta_node = sym_sum * inv_deg
            bar_w = jnp.stack(w_rows)               # [deg, J]
        else:
            eta_node = sym_sum / deg
            bar_w = inv_deg = None
        kick_w = jnp.stack(kick_rows) if kick_on else None
        if self.ccfg.use_fused_kernel:
            theta_new, lam_new, bar_new, r_sq, s_sq = self._fused_round(
                theta_flat, state.lam, state.theta_bar_prev, wires, scales,
                e_stack, alpha, sym_sum, eta_node,
                bar_w=bar_w, inv_deg=inv_deg, kick_w=kick_w)
        else:
            theta_new, lam_new, bar_new, r_sq, s_sq = \
                kref.consensus_round_ref(
                    theta_flat, state.lam, state.theta_bar_prev, wires,
                    scales, e_stack, alpha, sym_sum, eta_node,
                    block_leaf=lay.block_leaf, block_size=lay.block_size,
                    bar_w=bar_w, inv_deg=inv_deg, kick_w=kick_w,
                    scales_per_block=self.dequant_spec.per_block)

        params_new = lay.unpack(theta_new)
        r_norm = jnp.sqrt(r_sq)
        s_norm = jnp.sqrt(s_sq)

        if dynamic:
            # penalties keep adapting on gated GRAPH edges (the eq. 10
            # top-up must still see them to revive) and on repair edges,
            # but never on ghost rows/cols
            alive = topo.node_alive
            adj_pen = (adj & alive[:, None] & alive[None, :]) | topo.mask
        else:
            adj_pen = adj
        with self._span("consensus/penalty"):
            penalty_new = update_penalty(
                pcfg, state.penalty, adj=adj_pen, f_self=f_self,
                f_nbr=f_nbr, r_norm=r_norm, s_norm=s_norm)
            topo_new = self.topo_rt.update(
                topo, penalty=penalty_new,
                r_norm=r_norm) if dynamic else topo
        if kick_on:
            # edges the scheduler just gated: park their final consensus
            # force (the symmetrized weight applied THIS round) for the
            # kernel to absorb into the dual next round
            newly_off = (topo.mask & ~topo_new.mask).astype(jnp.float32)
            topo_new = topo_new._replace(
                kick=0.5 * (eta + eta.T) * newly_off)
        new = state._replace(params=params_new, lam=lam_new,
                             theta_bar_prev=bar_new, penalty=penalty_new,
                             topo=topo_new)
        if pipelined and self.async_cfg is not None:
            # the issued raw rows ARE next round's double buffer; w_prev
            # records the weights applied this round so an interleaved
            # bounded-staleness step absorbs kicks correctly. The PURE-sync
            # path skips this writeback: nothing consumes it — the async
            # invariant makes the first read of every edge fresh (the
            # zero-initialized ledger is never decoded), and the dead-offset
            # hold only needs a shape-stable row — so skipping saves a
            # wire-sized [deg, J, W] copy per round.
            new = new._replace(ledger=WireLedger(
                wires=self._constrain_flat(jnp.stack(inflight)),
                round=state.ledger.round + 1,
                w_prev=0.5 * (eta + eta.T) * (mask_f if dynamic else 1.0)))
        if dynamic:
            # ghost and zero-active-degree rows have bar = 0, so their
            # "residual" is the full parameter norm; an isolated node has
            # no consensus constraint — exclude both from the extremes
            alive_f = topo.node_alive.astype(jnp.float32) \
                * (act > 0).astype(jnp.float32)
            r_rep, s_rep = r_norm * alive_f, s_norm * alive_f
            f_rep = (f_self * alive_f).sum() / jnp.maximum(alive_f.sum(), 1)
        else:
            r_rep, s_rep, f_rep = r_norm, s_norm, f_self.mean()
        metrics = {
            "r_max": r_rep.max(), "s_max": s_rep.max(),
            "f_mean": f_rep,
            "eta_mean": jnp.where(adj, penalty_new.eta, 0.0).sum()
            / jnp.maximum(adj.sum(), 1),
            "active_edges": (active_edge_fraction(topo, adj) if dynamic
                             else jnp.ones(())),
        }
        node_metrics = None
        if self.node_ring_on:
            node_metrics = {
                "r": r_rep, "s": s_rep, "f_local": f_self,
                "eta_row_mean":
                    jnp.where(adj, penalty_new.eta, 0.0).sum(axis=1)
                    / jnp.maximum(adj.sum(axis=1), 1),
                "alive": (topo.node_alive.astype(jnp.float32) if dynamic
                          else jnp.ones((j,), jnp.float32)),
                "wire_rx_bytes": rx * float(self.codec.wire_bytes()),
            }
        return self._finish_round(new, metrics, node_metrics)

    # ------------------------------------------- async consensus round ----
    def consensus_step_async(self, state: TrainState, probe_batch: Any,
                             arrivals: jax.Array,
                             advance: jax.Array | None = None
                             ) -> tuple[TrainState, dict]:
        """One bounded-staleness consensus round (``repro.async_exec``).

        The synchronous round blocks on every graph offset before any
        node's prox/dual work runs. This variant instead consumes, per
        directed edge, the freshest payload that has LANDED — falling back
        to the double-buffered wire ledger (the payload consumed last
        round) when a neighbor is late — and treats a payload older than
        ``AsyncConfig.max_staleness`` rounds as a temporarily gated edge:
        zero math through the masked kernel, with the edge's final
        consensus force zero-kick-absorbed into the dual so gating
        preserves stationarity. A fresh arrival revives the edge the same
        round.

        Args:
          arrivals: [deg, J] bool, replicated — ``arrivals[d, i]`` means
            the payload from node ``(i + off_d) % J`` reached node i before
            this round's compute deadline (the host executor derives it
            from its round clock; in a real deployment it is the DMA
            completion bit of the double buffer).
          advance: optional [J] bool — nodes actually running a consensus
            round this fleet tick. A frozen (mid-compute) node keeps its
            params / duals / penalty rows; its staleness clocks still tick.

        With ``max_staleness=0`` no staleness is tolerated — the executor
        waits for every wire and this method IS the synchronous round
        (pinned bit-identical by test), with the ledger passing through
        untouched.
        """
        if self.async_cfg is None:
            raise ValueError("consensus_step_async needs ConsensusConfig."
                             "async_exec=AsyncConfig(...)")
        if self.num_nodes <= 1:
            return self._finish_round(state, {
                "r_max": jnp.zeros(()),
                "eta_mean": jnp.asarray(self.ccfg.penalty.eta0)})
        acfg = self.async_cfg
        if acfg.max_staleness == 0:
            # the sync round already emits the full unified key set (the
            # schema registry replaced this path's ad-hoc zero padding)
            return self.consensus_step(state, probe_batch)

        assert state.ledger is not None, "init_state builds the wire ledger"
        j = self.num_nodes
        offsets = self.offsets
        adj = jnp.asarray(self.graph.adj)
        pcfg = self.ccfg.penalty
        idx = jnp.arange(j)
        lay = self.layout
        dynamic = self.dynamic
        ledger: WireLedger = state.ledger
        vloss = self._probe_vloss()
        n_stale = acfg.max_staleness

        # ---- staleness clocks: tick, then gate -------------------------
        # arrivals [deg, J] -> the [J, J] clock grid via the static
        # circulant masks (scatter-free, mirroring the f_nbr writes)
        fresh = jnp.zeros((j, j), bool)
        covered = np.zeros((j, j), bool)
        for d, off in enumerate(offsets):
            circ = np.roll(np.eye(j, dtype=bool), off, axis=1)
            covered |= circ
            fresh = fresh | (arrivals[d][:, None] & jnp.asarray(circ))
        # pairs outside the compiled offset superset never move a payload;
        # keep their clocks at zero instead of counting phantom staleness
        fresh = fresh | jnp.asarray(~covered)
        prev_live = sym_age(state.topo) <= n_stale          # pre-tick view
        topo = tick_age(state.topo, fresh)
        age_s = sym_age(topo)
        live = age_s <= n_stale              # the bounded-staleness gate
        if self.topo_cfg.scheduler == "stale":
            # the mask's only gating source is staleness itself, which
            # `live` already recomputes from THIS round's clocks — gate on
            # the composed full-graph mask instead of last epoch's mask,
            # so a fresh arrival revives the edge the SAME round
            base_mask = compose_mask(adj, topo, adj)
            prev_base = compose_mask(adj, state.topo, adj)
        else:
            base_mask = prev_base = topo.mask
        gate_m = base_mask & live
        gate_f = gate_m.astype(jnp.float32)
        # the staleness-damped per-edge penalties actually applied this
        # round: eta / (1 + gamma * age) on active edges, zero on gated
        # ones, symmetrized so the dual weights stay symmetric. ONE source
        # of truth for the damping schedule: core.penalty.effective_eta.
        eta_eff = effective_eta(pcfg, state.penalty, gate_m, age=age_s,
                                stale_gamma=acfg.stale_gamma)
        w_applied = 0.5 * (eta_eff + eta_eff.T)            # [J, J]

        # ---- zero-kick bookkeeping -------------------------------------
        # (a) edges that just aged past the bound absorb THIS round from
        #     the ledger (their payload is exactly the last-known neighbor
        #     estimate the dual was built against), at EXACTLY the weight
        #     they applied last round (ledger.w_prev — the penalty state
        #     has advanced one update since, so it cannot be recomputed);
        # (b) edges the scheduler gated last round ride in topo.kick.
        newly_stale = prev_base & prev_live & ~live
        kick_m = jnp.where(newly_stale, ledger.w_prev, 0.0) + topo.kick

        with self._span("consensus/probe"):
            f_self = vloss(state.params, probe_batch)           # [J]
        with self._span("consensus/pack"):
            theta_flat = self._constrain_flat(
                lay.pack(state.params, dtype=lay.wire_dtype))
            wire = self._encode_wire(theta_flat)

        ones = jnp.ones((j, self.dequant_spec.scale_width), jnp.float32)
        sym_sum = jnp.zeros((j,), jnp.float32)
        act = jnp.zeros((j,), jnp.float32)
        f_nbr = jnp.zeros((j, j), jnp.float32)
        payloads, scale_rows, e_rows = [], [], []
        w_rows, kick_rows, ledger_rows = [], [], []
        # pipelined (pipeline_offsets >= 2): issue the offset permutes —
        # and their arrival merges against the held ledger rows — ahead of
        # the decode/probe consume loop, exactly like the sync round's
        # issue phase. Same bounded window via consume-token barriers;
        # bit-identical values at every depth.
        pipelined = self.pipelined
        depth = min(self.pipeline_depth, len(offsets)) if pipelined else 1
        landed: list = [None] * len(offsets)

        def _merge_row(d, token=None):
            off_d = offsets[d]
            arr_d = arrivals[d].astype(bool)                    # [J]
            held_d = ledger.wires[d]                            # [J, W]
            src = wire
            if token is not None:
                src, _ = jax.lax.optimization_barrier((src, token))

            def _issue(src=src, off_d=off_d):
                # round k's permute issues regardless of who consumes it
                # fresh — the overlap the executor's clock accounts for.
                # The barrier pins the wire dtype (see consensus_step).
                with self._span(f"consensus/exchange/off{off_d}"):
                    return jax.lax.optimization_barrier(
                        jnp.roll(src, -off_d, axis=0))

            def _hold(held_d=held_d):
                return held_d

            # nothing arrived on this offset => the in-flight payload is
            # still on the wire; skip the permute entirely this tick
            rolled = jax.lax.cond(arr_d.any(), _issue, _hold)
            return jnp.where(arr_d[:, None], rolled, held_d)

        for d0 in range(depth if pipelined else 0):
            landed[d0] = _merge_row(d0)

        for d, off in enumerate(offsets):
            jidx = (idx + off) % j
            merged = landed[d] if pipelined else _merge_row(d)
            payload, scales_row = self._decode_wire(merged)
            g_off = gate_f[idx, jidx]
            k_off = kick_m[idx, jidx]

            def _probe(payload=payload, scales_row=scales_row):
                with self._span("consensus/probe"):
                    return vloss(self._probe_params(payload, scales_row),
                                 probe_batch)

            # probe the payload actually consumed (stale ones included —
            # it IS our current estimate of the neighbor); a fully gated,
            # kick-free offset skips the forward pass
            f_off = jax.lax.cond((g_off.sum() + k_off.sum()) > 0,
                                 _probe, lambda: f_self)
            # staleness-damped symmetrized penalty: stale duals pull less
            e_sym = w_applied[idx, jidx]
            circ_f = jnp.asarray(np.roll(np.eye(j), off, axis=1),
                                 jnp.float32)
            f_nbr = f_nbr + f_off[:, None] * circ_f
            sym_sum = sym_sum + e_sym
            act = act + g_off
            payloads.append(payload)
            scale_rows.append(ones if scales_row is None else scales_row)
            e_rows.append(e_sym)
            w_rows.append(g_off)
            kick_rows.append(k_off)
            ledger_rows.append(merged)
            if pipelined and d + depth < len(offsets):
                landed[d + depth] = _merge_row(d + depth, token=f_off)

        wires = self._constrain_flat(jnp.stack(payloads))  # [deg, J, total]
        scales = jnp.stack(scale_rows)              # [deg, J, L]
        e_stack = jnp.stack(e_rows)                 # [deg, J]
        bar_w = jnp.stack(w_rows)
        kick_w = jnp.stack(kick_rows)

        alpha = self.ccfg.prox_step / (1.0 + 2.0 * sym_sum)
        inv_deg = jnp.where(act > 0, 1.0 / jnp.maximum(act, 1.0), 0.0)
        eta_node = sym_sum * inv_deg
        if self.ccfg.use_fused_kernel:
            theta_new, lam_new, bar_new, r_sq, s_sq = self._fused_round(
                theta_flat, state.lam, state.theta_bar_prev, wires, scales,
                e_stack, alpha, sym_sum, eta_node,
                bar_w=bar_w, inv_deg=inv_deg, kick_w=kick_w)
        else:
            theta_new, lam_new, bar_new, r_sq, s_sq = \
                kref.consensus_round_ref(
                    theta_flat, state.lam, state.theta_bar_prev, wires,
                    scales, e_stack, alpha, sym_sum, eta_node,
                    block_leaf=lay.block_leaf, block_size=lay.block_size,
                    bar_w=bar_w, inv_deg=inv_deg, kick_w=kick_w,
                    scales_per_block=self.dequant_spec.per_block)

        params_new = lay.unpack(theta_new)
        r_norm = jnp.sqrt(r_sq)
        s_norm = jnp.sqrt(s_sq)

        # penalties keep adapting on stale-gated and scheduler-gated graph
        # edges (the eq. 10 top-up revives them) but never on ghost rows
        alive = topo.node_alive
        adj_pen = (adj & alive[:, None] & alive[None, :]) | topo.mask
        with self._span("consensus/penalty"):
            penalty_new = update_penalty(
                pcfg, state.penalty, adj=adj_pen, f_self=f_self,
                f_nbr=f_nbr, r_norm=r_norm, s_norm=s_norm)
            topo_new = self.topo_rt.update(
                topo, penalty=penalty_new,
                r_norm=r_norm) if dynamic else topo
        if dynamic and self.topo_cfg.can_gate:
            # park kicks ONLY for edges that were ACTIVE this round (mask
            # AND within the staleness bound): an edge that aged out was
            # already absorbed in-round — the scheduler mirroring it out
            # of the mask one epoch later must not absorb it twice
            kick_next = w_applied \
                * (gate_m & ~topo_new.mask).astype(jnp.float32)
        else:
            kick_next = jnp.zeros_like(topo.kick)
        topo_new = topo_new._replace(kick=kick_next)
        ledger_new = WireLedger(wires=self._constrain_flat(
            jnp.stack(ledger_rows)),
            round=ledger.round + 1, w_prev=w_applied)

        new = state._replace(params=params_new, lam=lam_new,
                             theta_bar_prev=bar_new, penalty=penalty_new,
                             topo=topo_new, ledger=ledger_new)
        if advance is not None:
            new = self._freeze_rows(advance, new, state,
                                    topo_new=topo_new,
                                    ledger_new=ledger_new)

        alive_f = topo.node_alive.astype(jnp.float32) \
            * (act > 0).astype(jnp.float32)
        if advance is not None:
            # frozen nodes ran no real round: their residual rows were
            # discarded by _freeze_rows, so keep them out of the extremes
            alive_f = alive_f * advance.astype(jnp.float32)
        r_rep, s_rep = r_norm * alive_f, s_norm * alive_f
        f_rep = (f_self * alive_f).sum() / jnp.maximum(alive_f.sum(), 1)
        mask_edges = jnp.maximum(base_mask.astype(jnp.float32).sum(), 1.0)
        metrics = {
            "r_max": r_rep.max(), "s_max": s_rep.max(),
            "f_mean": f_rep,
            "eta_mean": jnp.where(adj, penalty_new.eta, 0.0).sum()
            / jnp.maximum(adj.sum(), 1),
            "active_edges": (active_edge_fraction(topo, adj) if dynamic
                             else jnp.ones(())),
            "stale_edges": (base_mask & ~live).astype(jnp.float32).sum()
            / mask_edges,
            "age_max": jnp.where(base_mask, age_s, 0).max(),
        }
        node_metrics = None
        if self.node_ring_on:
            # fresh wire bytes per node: offsets whose arrival bit was set
            # for this node this tick (held ledger payloads are not re-paid)
            rx = sum(arrivals[d].astype(jnp.float32)
                     for d in range(len(offsets)))
            node_metrics = {
                "r": r_rep, "s": s_rep, "f_local": f_self,
                "eta_row_mean":
                    jnp.where(adj, penalty_new.eta, 0.0).sum(axis=1)
                    / jnp.maximum(adj.sum(axis=1), 1),
                "age_max": jnp.where(base_mask, age_s, 0).max(axis=1),
                "alive": topo.node_alive.astype(jnp.float32),
                "advance": (advance.astype(jnp.float32)
                            if advance is not None
                            else jnp.ones((j,), jnp.float32)),
                "wire_rx_bytes": rx * float(self.codec.wire_bytes()),
            }
        return self._finish_round(new, metrics, node_metrics)

    def _freeze_rows(self, advance: jax.Array, new: TrainState,
                     old: TrainState, *, topo_new, ledger_new) -> TrainState:
        """Keep non-advancing nodes' state from ``old`` (async fleet tick).

        A node mid-compute at the tick deadline runs no prox/dual update:
        its params, duals and neighbor mean rows stay put. The PENALTY
        freezes per EDGE instead (``core.penalty.freeze_penalty``): an edge
        whose other endpoint advanced keeps adapting in BOTH directions, so
        a frozen node's incident columns and rows stay symmetric — the old
        whole-row freeze let eta[j, i] run ahead of a frozen eta[i, j].
        Staleness clocks and the shared topology/ledger state always
        advance — they model the network, not the node's compute.
        """
        adv = advance.astype(bool)

        def rows(a, b):
            sel = adv.reshape((adv.shape[0],) + (1,) * (a.ndim - 1))
            return jnp.where(sel, a, b)

        penalty = freeze_penalty(advance, new.penalty, old.penalty)
        return new._replace(
            params=jax.tree_util.tree_map(rows, new.params, old.params),
            lam=rows(new.lam, old.lam),
            theta_bar_prev=rows(new.theta_bar_prev, old.theta_bar_prev),
            penalty=penalty, topo=topo_new, ledger=ledger_new)

    # ------------------------------------------------------------- churn ----
    def apply_churn(self, state: TrainState, victim: int) -> TrainState:
        """Host-side layout-preserving node drop — a topology epoch, not a
        crash, and NOT a recompilation: the [J, ...] shapes are unchanged,
        only ``state.topo`` (liveness, mask, repair edges) is rewritten.

        The compiled step functions keep executing; the victim becomes a
        ghost row whose edges all cost zero math. Requires a dynamic
        topology config (``churn=True`` or a non-static scheduler) so the
        engine compiled the masked kernel and the repair offset superset.
        """
        if not self.dynamic:
            raise ValueError(
                "node churn needs ConsensusConfig.dyn_topology with "
                "churn=True (or a non-static scheduler)")
        # drop_node preserves the old leaves' committed shardings, so the
        # jitted step functions keep their cache
        return state._replace(topo=self.topo_rt.drop_node(state.topo,
                                                          victim))

    # ------------------------------------------------------------ driver ----
    def jit_step_fns(self):
        """Jitted (train_step, consensus_step) with the state DONATED.

        Donation lets XLA reuse the state buffers for the outputs — combined
        with the kernel's input/output aliasing the flat theta/lam/bar
        buffers are updated in place, not copied once per round.
        """
        return (jax.jit(self.train_step, donate_argnums=(0,)),
                jax.jit(self.consensus_step, donate_argnums=(0,)))

    def jit_async_step_fns(self):
        """Jitted consensus_step_async with the state donated.

        Deliberately does NOT hand out a donated train_step: the local
        step is the one that gets wrapped in ``with_retries`` (which may
        replay the same state buffers) — callers jit it undonated
        themselves, exactly like the sync launcher does.
        """
        return jax.jit(self.consensus_step_async, donate_argnums=(0,))

    def should_sync(self, step: int) -> bool:
        return self.num_nodes > 1 and (step + 1) % self.ccfg.local_steps == 0
