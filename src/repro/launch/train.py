"""Training launcher: consensus-ADMM distributed training end to end.

The same code path runs the reduced configs on CPU fake devices and the
published widths on a TPU; only the mesh and the config sizes change.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \\
      --steps 40 --scheme nap --topology ring --local-steps 4 \\
      --ckpt-dir /tmp/ckpt
  # published widths, depth and vocabulary cut, one node per local chip
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --mesh local \\
      --n-layers 4 --vocab 18944 --seq 2048 --batch-per-node 2
Resume is automatic if the checkpoint dir has state.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.async_exec import (AsyncConfig, AsyncExecutor, RoundClock,
                              straggler_compute)
from repro.checkpoint import latest_steps, restore, save_async, wait_pending
from repro.configs import get_config, get_reduced_config
from repro.core.penalty import PenaltyConfig, SCHEMES
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import (gpu_plugin_installed, make_debug_mesh,
                               make_local_mesh, make_production_mesh,
                               set_backend_flags)
from repro.models import build_model
from repro.obs import ObsConfig, ObsWriter, host_span_factory
from repro.optim import ConsensusConfig, ConsensusTrainer
from repro.optim.adamw import AdamWConfig
from repro.runtime import (ElasticController, RetryPolicy, StragglerMonitor,
                           aged_out_nodes, with_retries)
from repro.runtime.compile_cache import enable_compile_cache
from repro.topology import SCHEDULERS as TOPO_SCHEDULERS, TopologyConfig


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--n-layers", type=int, default=0,
                    help="cut the config's depth to N layers (widths stay "
                         "as the config has them); 0 keeps its depth")
    ap.add_argument("--vocab", type=int, default=0,
                    help="cut the config's vocabulary to N rows; 0 keeps it")
    ap.add_argument("--mesh", choices=["debug", "prod", "local", "none"],
                    default="debug",
                    help="debug = 8 CPU fake devices, prod = 256/512-chip "
                         "v5e pods, local = every local device, one "
                         "consensus node each: ('pod', 'data', 'model') "
                         "of shape (devices, 1, 1)")
    ap.add_argument("--multi-pod", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="debug/prod meshes: add the 'pod' axis that "
                         "carries the consensus graph (--no-multi-pod "
                         "trains one node)")
    ap.add_argument("--scheme", choices=SCHEMES, default="nap")
    ap.add_argument("--topology", default="ring")
    ap.add_argument("--topo-scheduler", choices=TOPO_SCHEDULERS,
                    default="static",
                    help="dynamic-topology edge scheduler (repro.topology)")
    ap.add_argument("--topo-churn", action="store_true",
                    help="compile the churn offset superset so node drops "
                         "are layout-preserving (no recompilation)")
    ap.add_argument("--drop-node", default="",
                    help="STEP:VICTIM — simulate losing pod VICTIM after "
                         "STEP (debug-mesh churn drill; implies --topo-churn)")
    ap.add_argument("--drop-stragglers", action="store_true",
                    help="ghost a flagged straggler pod via the topology "
                         "runtime instead of just logging it (async mode "
                         "flags by edge age, sync mode by wall-clock EMA)")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="bounded-staleness executor (repro.async_exec): "
                         "consensus rounds consume the freshest LANDED "
                         "payload per edge instead of barriering")
    ap.add_argument("--max-staleness", type=int, default=2,
                    help="async: rounds a consumed payload may lag; older "
                         "edges gate until a fresh payload lands (0 = "
                         "wait for everything, bit-identical to sync)")
    ap.add_argument("--slow-node", default="",
                    help="async drill: NODE:FACTOR — model pod NODE taking "
                         "FACTOR x the fleet round time (e.g. 0:2.0)")
    ap.add_argument("--shard-consensus", action="store_true",
                    help="shard the flat consensus state (lam, neighbor "
                         "mean, wire/ledger rows) over the in-pod mesh "
                         "axes: per-device consensus-state HBM shrinks by "
                         "the in-pod axis size (docs/consensus_engine.md)")
    ap.add_argument("--pipeline-offsets", type=int, default=1,
                    help="round pipeline depth: how many graph offsets may "
                         "have their collective-permute in flight while "
                         "earlier offsets decode/probe/fuse (1 = today's "
                         "sequential loop, bit-identical at every depth; "
                         "docs/consensus_engine.md \"Round pipeline\")")
    ap.add_argument("--no-async-collectives", action="store_true",
                    help="skip arming the XLA GPU latency-hiding/async-"
                         "stream flags (set_backend_flags) before jax init; "
                         "they are armed only where a GPU plugin is "
                         "installed. The pipeline still reorders "
                         "issue/consume but the scheduler won't hide the "
                         "permutes")
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--eta0", type=float, default=0.1)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--compression", default="none",
                    help="legacy spelling of --wire-codec (none | int8)")
    ap.add_argument("--wire-codec", default="",
                    choices=["", "native", "int8", "fp8_e4m3", "fp8_e5m2"],
                    help="consensus wire codec (repro.wire): native = "
                         "params dtype, int8 = absmax per leaf + bitcast "
                         "scale tail, fp8_* = 1 B/param float8 with "
                         "per-block f32 scales; empty resolves from "
                         "--compression")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-dir", default="",
                    help="observability (repro.obs): drain the on-device "
                         "metrics ring + topology event journal into this "
                         "directory (metrics.jsonl / events.jsonl / "
                         "rollup.json; async runs add the RoundClock "
                         "Perfetto trace). Unset = obs fully off — the "
                         "compiled step is byte-identical")
    ap.add_argument("--obs-ring-cap", type=int, default=256,
                    help="rows in the on-device metrics ring")
    ap.add_argument("--obs-drain-every", type=int, default=8,
                    help="host drain cadence in consensus rounds")
    ap.add_argument("--no-node-ring", action="store_true",
                    help="compile out the per-node telemetry ring "
                         "(obs.node_ring), keeping only the scalar ring")
    ap.add_argument("--health", action="store_true",
                    help="run the online health monitor (repro.obs.health) "
                         "over drained per-node rows: health_* events in "
                         "the journal, a per-node score table + advisory "
                         "recommendations in the rollup and printed at "
                         "exit. ADVISORY ONLY — nothing acts on it. "
                         "Requires --obs-dir")
    ap.add_argument("--profile-rounds", type=int, default=0,
                    help="capture a jax profiler trace covering the first "
                         "N consensus rounds into <obs-dir>/profile "
                         "(view in Perfetto/TensorBoard; the obs trace "
                         "spans label the round phases)")
    args = ap.parse_args(argv)
    if args.health and not args.obs_dir:
        ap.error("--health requires --obs-dir (the monitor feeds off "
                 "drained per-node telemetry)")
    return args


def arch_config(args):
    """The run's ArchConfig: the full or reduced config, with only its
    depth and vocabulary cut by --n-layers / --vocab."""
    cfg = get_reduced_config(args.arch) if args.reduced \
        else get_config(args.arch)
    cuts = {}
    if args.n_layers:
        cuts["n_layers"] = args.n_layers
    if args.vocab:
        cuts["vocab"] = args.vocab
    return dataclasses.replace(cfg, **cuts), cfg


def build_trainer(args):
    """(cfg, trainer) exactly as ``main`` runs them — also what a caller
    compiles ahead of the run to check the fit on the device."""
    cfg, _ = arch_config(args)
    model = build_model(cfg)
    if args.mesh == "prod":
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif args.mesh == "debug":
        mesh = make_debug_mesh(multi_pod=args.multi_pod)
    elif args.mesh == "local":
        mesh = make_local_mesh()
    else:
        mesh = None

    churn = args.topo_churn or args.drop_stragglers or bool(args.drop_node)
    topo_sched = args.topo_scheduler
    if args.async_mode and topo_sched == "static" and args.max_staleness > 0:
        # the stale scheduler mirrors the executor's in-round gating into
        # the topology mask (monitoring + wire accounting see it)
        topo_sched = "stale"
    obs_cfg = ObsConfig(ring_capacity=args.obs_ring_cap,
                        drain_every=args.obs_drain_every,
                        with_node_ring=not args.no_node_ring) \
        if args.obs_dir else None
    return cfg, ConsensusTrainer(
        model, mesh,
        adamw=AdamWConfig(lr=args.lr),
        consensus=ConsensusConfig(
            penalty=PenaltyConfig(scheme=args.scheme, eta0=args.eta0),
            topology=args.topology, local_steps=args.local_steps,
            compression=args.compression,
            wire_codec=args.wire_codec,
            shard_consensus=args.shard_consensus,
            pipeline_offsets=args.pipeline_offsets,
            dyn_topology=TopologyConfig(scheduler=topo_sched, churn=churn,
                                        max_staleness=args.max_staleness),
            async_exec=(AsyncConfig(max_staleness=args.max_staleness)
                        if args.async_mode else None),
            obs=obs_cfg))


def main(argv=None):
    args = parse_args(argv)
    if not args.no_async_collectives and gpu_plugin_installed():
        # must land before the first jax device touch (build_model / mesh
        # construction below) — a warn-no-op afterwards. The flags are
        # GPU-compiler options; no other backend reads them.
        set_backend_flags(async_collectives=True)
    enable_compile_cache()
    cfg, base = arch_config(args)
    for name in ("n_layers", "vocab"):
        if getattr(cfg, name) != getattr(base, name):
            print(f"cut: {name} {getattr(base, name)} -> "
                  f"{getattr(cfg, name)} ({cfg.arch_id}, widths unchanged)",
                  flush=True)
    cfg, trainer = build_trainer(args)
    drop_at, drop_victim = (-1, -1)
    if args.drop_node:
        drop_at, drop_victim = (int(x) for x in args.drop_node.split(":"))
    state = trainer.init_state(jax.random.PRNGKey(args.seed))
    start_step = 0
    if args.ckpt_dir and latest_steps(args.ckpt_dir):
        state, meta = restore(args.ckpt_dir, state)
        start_step = int(meta["step"])
        print(f"resumed from step {start_step}")

    data = SyntheticTokens(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq,
        batch_per_node=args.batch_per_node,
        num_nodes=trainer.num_nodes, seed=args.seed))

    # local step stays undonated: with_retries may replay it with the same
    # state buffers; the consensus round is never retried, so donate there.
    # The local step never reads the flat consensus buffers, so they stay
    # out of its jit: an undonated jit would copy them into its outputs,
    # 8 B/param of HBM (plus the wire ledger) for nothing.
    train_jit = jax.jit(trainer.train_step)

    def train(s, b):
        keep = {"lam": s.lam, "theta_bar_prev": s.theta_bar_prev,
                "ledger": s.ledger}
        new, m = train_jit(s._replace(lam=None, theta_bar_prev=None,
                                      ledger=None), b)
        return new._replace(**keep), m
    _, cons = trainer.jit_step_fns()
    executor = None
    if args.async_mode and trainer.num_nodes > 1:
        compute = np.ones(trainer.num_nodes)
        if args.slow_node:
            v, f = args.slow_node.split(":")
            compute = straggler_compute(trainer.num_nodes, victim=int(v),
                                        factor=float(f))
        executor = AsyncExecutor(trainer, RoundClock(
            compute_s=compute, wire_s=0.25,
            offsets=tuple(trainer.offsets)))
    monitor = StragglerMonitor(trainer.num_nodes)
    elastic = ElasticController(trainer.graph, topology=trainer.topo_rt)
    step_fn = with_retries(train, RetryPolicy())

    writer = None
    if args.obs_dir:
        writer = ObsWriter(args.obs_dir, meta={
            "arch": cfg.arch_id, "scheme": args.scheme,
            "topology": args.topology, "num_nodes": trainer.num_nodes,
            "wire_codec": trainer.codec_name,
            "wire_bytes_per_round":
                trainer.codec.wire_bytes() * max(len(trainer.offsets), 1),
            "offsets": [int(o) for o in trainer.offsets],
            "async": bool(args.async_mode),
            "ring_capacity": args.obs_ring_cap,
            "drain_every": args.obs_drain_every,
        }, max_staleness=(args.max_staleness if args.async_mode else None),
            health=args.health)
    round_span = host_span_factory(writer is not None)
    rounds, profiling = 0, False

    def make_batch(step):
        if cfg.frontend != "none":
            return data.embeds_batch(step, cfg.d_model)
        return data.batch(step)

    t_start = time.time()
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = make_batch(step)
        state, m = step_fn(state, batch)
        jax.block_until_ready(m["loss"])
        dt = time.time() - t0
        slow = monitor.observe(np.full(trainer.num_nodes, dt))
        line = f"step {step:5d} loss {float(m['loss']):.4f} {dt*1e3:.0f}ms"
        if trainer.should_sync(step):
            probe = make_batch(10**6 + step)
            if args.profile_rounds > 0 and rounds == 0 and not profiling:
                jax.profiler.start_trace(
                    os.path.join(args.obs_dir or ".", "profile"))
                profiling = True
            with round_span("round/async" if executor is not None
                            else "round/sync"):
                if executor is not None:
                    state, cm = executor.consensus_round(state, probe)
                else:
                    state, cm = cons(state, probe)
            rounds += 1
            if profiling and rounds >= args.profile_rounds:
                jax.block_until_ready(cm["r_max"])
                jax.profiler.stop_trace()
                profiling = False
                print(f"profile trace ({args.profile_rounds} rounds) -> "
                      f"{os.path.join(args.obs_dir or '.', 'profile')}",
                      flush=True)
            if writer is not None and rounds % args.obs_drain_every == 0:
                writer.drain(state, step=step + 1)
            line += (f" | consensus r={float(cm['r_max']):.4f} "
                     f"eta={float(cm['eta_mean']):.4f}")
            if trainer.dynamic:
                line += f" active={float(cm['active_edges']):.2f}"
            if executor is not None and "stale_edges" in cm:
                line += (f" stale={float(cm['stale_edges']):.2f}"
                         f" age_max={int(cm['age_max'])}")
            if executor is not None and args.drop_stragglers:
                # async unification: the staleness clocks ARE the
                # straggler signal — wall-clock EMA not needed
                for v in aged_out_nodes(
                        state.topo, max_staleness=args.max_staleness):
                    alive = np.asarray(state.topo.node_alive)
                    if alive[v] and alive.sum() > 2:
                        state = state._replace(topo=elastic.drop_preserving(
                            v, state.topo, step))
                        line += f" | ghosted aged-out node {v}"
        if step == drop_at:
            # layout-preserving churn drill: ghost the victim, keep going —
            # same compiled step fns, no restart (a topology epoch)
            state = state._replace(topo=elastic.drop_preserving(
                drop_victim, state.topo, step))
            line += f" | dropped node {drop_victim} (topology epoch)"
        if slow and executor is None:
            line += f" | stragglers: {slow}"
            if args.drop_stragglers and trainer.dynamic:
                for v in slow:
                    # re-read liveness each drop: several stragglers may be
                    # flagged in one step and the >2-survivors floor must
                    # see the drops already applied
                    alive = np.asarray(state.topo.node_alive)
                    if alive[v] and alive.sum() > 2:
                        state = state._replace(topo=elastic.drop_preserving(
                            v, state.topo, step))
                        line += f" | ghosted straggler {v}"
        print(line, flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save_async(args.ckpt_dir, step + 1, state,
                       metadata={"step": step + 1, "arch": cfg.arch_id,
                                 "scheme": args.scheme,
                                 "topology": args.topology})
    wait_pending()
    print(f"done: {args.steps - start_step} steps in "
          f"{time.time() - t_start:.1f}s")
    if executor is not None:
        print(f"async executor: {executor.summary()}")
    if writer is not None:
        writer.drain(state, step=args.steps)          # tail < drain_every
        if executor is not None:
            writer.observe_executor(executor.summary())
            executor.export_timeline(
                os.path.join(args.obs_dir, "roundclock_trace.json"))
        rollup = writer.finalize(
            extra=({"async_summary": executor.summary()}
                   if executor is not None else None))
        print(f"obs: {rollup['rounds']} rounds, "
              f"{rollup['journal_events']} topology events, "
              f"{rollup['dropped_rows']} dropped rows -> {args.obs_dir}")
        if args.health and "health" in rollup:
            h = rollup["health"]
            print("health scores (1.0 = clean):")
            for n in h["nodes"]:
                active = [k for k in ("divergence", "eta_stall",
                                      "eta_oscillation", "straggler",
                                      "drift") if n.get(k)]
                tag = f" [{', '.join(active)}]" if active else ""
                print(f"  node {n['node']}: {n['score']:.2f}{tag}")
            recs = h["recommendations"]
            for note in recs["notes"]:
                print(f"  advisory: {note}")
            if not recs["notes"]:
                print("  no advisories")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
