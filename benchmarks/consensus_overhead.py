"""Consensus-round overhead microbench (the paper's technique at LM scale).

Measures on the CPU debug mesh: local step time, fused (flat-buffer Pallas
engine) vs unfused (blockwise jnp reference) consensus round time, the
effect of int8 exchange compression, and the communication-volume ratio of
consensus-every-H vs all-reduce-every-step (analytic).

Emits ``BENCH_consensus.json`` under ``benchmarks/results/``; the
repo-root copy is the committed perf baseline tracking round ms, wire
bytes per round and the HBM-pass estimate of the fused engine from PR 1
on, promoted exclusively by ``benchmarks/run.py`` (single-writer rule).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import require_devices, write_csv, write_json


def _time_round(cons, state, data, *, rounds: int = 10):
    """Median-of-rounds: CPU interpret-mode rounds are ~1s and noisy."""
    import jax
    state, cm = cons(state, data.batch(0, probe=True))      # warm/compile
    jax.block_until_ready(cm["r_max"])
    times = []
    for s in range(rounds):
        t0 = time.time()
        state, cm = cons(state, data.batch(s, probe=True))
        jax.block_until_ready(cm["r_max"])
        times.append(time.time() - t0)
    return float(np.median(times)), state


def run(steps: int = 6, sharded: bool = False,
        codec: bool = False) -> list[dict]:
    import jax
    require_devices("consensus_overhead", 8)
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(multi_pod=True)

    rows = []
    from repro.configs import get_reduced_config
    from repro.models import build_model
    from repro.optim import flatten
    cfg = get_reduced_config("qwen3-4b")
    model = build_model(cfg)
    # same wire accounting as the measured rows / dryrun roofline
    ap = model.abstract_params()
    lay0 = flatten.FlatLayout.for_tree(
        ap, block_size=flatten.auto_block_size(ap), node_axis=False)
    params_bytes = lay0.wire_bytes("none")

    for h in (1, 4, 16):
        # cross-pod bytes per step: consensus exchanges deg x params every H
        deg = 1  # ring with J=2
        consensus_bytes = deg * params_bytes / h
        allreduce_bytes = 2 * params_bytes          # ring AR every step
        rows.append({"mode": f"consensus_H{h}", "wire_bytes_per_step":
                     int(consensus_bytes),
                     "vs_allreduce": round(consensus_bytes
                                           / allreduce_bytes, 4)})
    rows.append({"mode": "allreduce_every_step",
                 "wire_bytes_per_step": int(allreduce_bytes),
                 "vs_allreduce": 1.0})

    dev = jax.devices()[0]
    bench = {"mesh": f"2x2x2 ({len(jax.devices())} {dev.platform} devices, "
                     f"{dev.device_kind})",
             "arch": "qwen3-4b (reduced)", "rounds": {}}
    from repro.core.penalty import PenaltyConfig
    from repro.data import DataConfig, SyntheticTokens
    from repro.launch.dryrun import fused_round_roofline
    from repro.optim import ConsensusConfig, ConsensusTrainer
    from repro.optim.adamw import AdamWConfig
    data = SyntheticTokens(DataConfig(
        vocab=cfg.vocab, seq_len=32, batch_per_node=2, num_nodes=2))
    for compression in ("none", "int8"):
        t_local = None          # train_step is fused-flag independent:
        for fused in (True, False):     # time it once per compression
            tr = ConsensusTrainer(
                model, mesh, adamw=AdamWConfig(lr=1e-2),
                consensus=ConsensusConfig(
                    penalty=PenaltyConfig(scheme="nap", eta0=0.1),
                    topology="ring", local_steps=4,
                    compression=compression, use_fused_kernel=fused))
            state = tr.init_state(jax.random.PRNGKey(0))
            train, cons = tr.jit_step_fns()
            state, m = train(state, data.batch(0))          # warm
            if t_local is None:
                t0 = time.time()
                for s in range(steps):
                    state, m = train(state, data.batch(s))
                jax.block_until_ready(m["loss"])
                t_local = (time.time() - t0) / steps
            t_cons, state = _time_round(cons, state, data)
            tag = f"{'fused' if fused else 'unfused'}_{compression}"
            # per node per round, summed over graph offsets — the same
            # accounting the dryrun roofline uses
            wire_bytes = len(tr.offsets) * tr.layout.wire_bytes(
                compression)
            rows.append({"mode": f"measured_{tag}",
                         "wire_bytes_per_step": wire_bytes,
                         "vs_allreduce": round(t_cons
                                               / max(t_local, 1e-9), 3)})
            bench["rounds"][tag] = {
                "round_ms": round(t_cons * 1e3, 2),
                "local_step_ms": round(t_local * 1e3, 2),
                "wire_bytes_per_round": wire_bytes,
            }
            print(f"consensus bench ({tag}): local {t_local*1e3:.1f}ms "
                  f"round {t_cons*1e3:.1f}ms")
    if sharded:
        # sharded-engine cell (--sharded): measured sharded fused
        # rounds plus the per-device consensus-state HBM report the
        # CI job uploads as an artifact
        hbm_report = {"mesh": bench["mesh"], "arch": bench["arch"],
                      "compressions": {}}
        for compression in ("none", "int8"):
            tr = ConsensusTrainer(
                model, mesh, adamw=AdamWConfig(lr=1e-2),
                consensus=ConsensusConfig(
                    penalty=PenaltyConfig(scheme="nap", eta0=0.1),
                    topology="ring", local_steps=4,
                    compression=compression, shard_consensus=True))
            state = tr.init_state(jax.random.PRNGKey(0))
            train, cons = tr.jit_step_fns()
            state, m = train(state, data.batch(0))          # warm
            t0 = time.time()
            for s in range(steps):      # own local-step measurement —
                state, m = train(state, data.batch(s))  # no reuse of
            jax.block_until_ready(m["loss"])            # earlier cells
            t_local_sh = (time.time() - t0) / steps
            t_cons, state = _time_round(cons, state, data)
            wire_bytes = len(tr.offsets) * tr.slayout.wire_bytes(
                compression)
            rows.append({"mode": f"measured_sharded_{compression}",
                         "wire_bytes_per_step": wire_bytes,
                         "vs_allreduce": round(
                             t_cons / max(t_local_sh, 1e-9), 3)})
            bench["rounds"][f"sharded_{compression}"] = {
                "round_ms": round(t_cons * 1e3, 2),
                "local_step_ms": round(t_local_sh * 1e3, 2),
                "wire_bytes_per_round": wire_bytes,
            }
            print(f"consensus bench (sharded_{compression}): "
                  f"round {t_cons*1e3:.1f}ms")
            hbm_report["compressions"][compression] = \
                fused_round_roofline(
                    model, mesh, compression=compression,
                    shard_consensus=True,
                    with_ledger=True)["consensus_state"]
        state_rep = hbm_report["compressions"]["none"]
        hbm_report["shrink_factor"] = round(
            state_rep["per_device_unsharded"]["total"]
            / max(state_rep["per_device"]["total"], 1), 2)
        path = write_json("consensus_hbm_report.json", hbm_report)
        print(f"wrote {path} (per-device consensus-state shrink = "
              f"{hbm_report['shrink_factor']}x)")
        bench["hbm_report"] = hbm_report
    if codec:
        # wire-codec cell (--codec): one measured fused round per codec
        # plus the per-codec wire-bytes report the CI codec lane
        # uploads as an artifact (all sizes read from repro.wire)
        from repro import wire as wire_lib
        codec_report = {"mesh": bench["mesh"], "arch": bench["arch"],
                        "codecs": {}}
        for name in wire_lib.WIRE_CODECS:
            tr = ConsensusTrainer(
                model, mesh, adamw=AdamWConfig(lr=1e-2),
                consensus=ConsensusConfig(
                    penalty=PenaltyConfig(scheme="nap", eta0=0.1),
                    topology="ring", local_steps=4, wire_codec=name))
            state = tr.init_state(jax.random.PRNGKey(0))
            train, cons = tr.jit_step_fns()
            state, m = train(state, data.batch(0))          # warm
            t_cons, state = _time_round(cons, state, data)
            wire_bytes = len(tr.offsets) * tr.codec.wire_bytes()
            spec = tr.codec.kernel_dequant_spec()
            rows.append({"mode": f"measured_codec_{name}",
                         "wire_bytes_per_step": wire_bytes,
                         "vs_allreduce": round(
                             wire_bytes / max(allreduce_bytes, 1), 4)})
            codec_report["codecs"][name] = {
                "round_ms": round(t_cons * 1e3, 2),
                "wire_bytes_per_round": wire_bytes,
                "wire_bytes_per_param": round(
                    tr.codec.wire_bytes() / tr.layout.total, 4),
                "scale_granularity": ("block" if spec.per_block
                                      else "leaf"),
                "scale_width": spec.scale_width,
                "roofline": fused_round_roofline(model, mesh,
                                                 compression=name),
            }
            print(f"consensus bench (codec {name}): "
                  f"round {t_cons*1e3:.1f}ms wire {wire_bytes}B")
        native_b = codec_report["codecs"]["native"][
            "wire_bytes_per_round"]
        for name, rec in codec_report["codecs"].items():
            rec["wire_vs_native"] = round(
                rec["wire_bytes_per_round"] / max(native_b, 1), 4)
        path = write_json("wire_codec_report.json", codec_report)
        print(f"wrote {path}")
        bench["codec_report"] = codec_report
    # overlap cell: latency-hiding round pipeline, measured on a
    # 4-pod mesh (ring offsets [1, 3] — depth > 1 is real, unlike the
    # J=2 debug mesh's single offset). overlap_on issues every
    # offset's collective-permute up front (pipeline_offsets=4);
    # overlap_off is the sequential issue-consume loop. Both compute
    # bit-identical rounds, so the ratio isolates pure scheduling.
    from repro.launch.mesh import make_mesh
    mesh4 = make_mesh((4, 2, 1), ("pod", "data", "model"))
    data4 = SyntheticTokens(DataConfig(
        vocab=cfg.vocab, seq_len=32, batch_per_node=2, num_nodes=4))
    overlap_s = {}
    for pipe, tag in ((1, "overlap_off"), (4, "overlap_on")):
        tr = ConsensusTrainer(
            model, mesh4, adamw=AdamWConfig(lr=1e-2),
            consensus=ConsensusConfig(
                penalty=PenaltyConfig(scheme="nap", eta0=0.1),
                topology="ring", local_steps=4, wire_codec="int8",
                pipeline_offsets=pipe))
        state = tr.init_state(jax.random.PRNGKey(0))
        train, cons = tr.jit_step_fns()
        state, m = train(state, data4.batch(0))         # warm
        t_cons, state = _time_round(cons, state, data4)
        wire_bytes = len(tr.offsets) * tr.codec.wire_bytes()
        overlap_s[tag] = t_cons
        rows.append({"mode": f"measured_{tag}",
                     "wire_bytes_per_step": wire_bytes,
                     "vs_allreduce": round(
                         wire_bytes / max(allreduce_bytes, 1), 4)})
        bench["rounds"][tag] = {
            "round_ms": round(t_cons * 1e3, 2),
            "wire_bytes_per_round": wire_bytes,
        }
        print(f"consensus bench ({tag}): round {t_cons*1e3:.1f}ms")
    bench["overlap_ratio"] = round(
        overlap_s["overlap_on"] / max(overlap_s["overlap_off"], 1e-9),
        3)
    print(f"overlap ratio (pipelined/sequential) = "
          f"{bench['overlap_ratio']}")
    bench["fused_round_model"] = {
        comp: fused_round_roofline(model, mesh, compression=comp)
        for comp in ("none", "int8")}
    f_ms = bench["rounds"]["fused_none"]["round_ms"]
    u_ms = bench["rounds"]["unfused_none"]["round_ms"]
    bench["fused_vs_unfused"] = round(f_ms / max(u_ms, 1e-9), 3)
    # results/ only — run.py promotes to the committed root baseline
    path = write_json("BENCH_consensus.json", bench)
    print(f"wrote {path} (fused/unfused = {bench['fused_vs_unfused']})")
    write_csv("consensus_overhead.csv", rows)
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded", action="store_true",
                    help="add the sharded-engine cell (measured sharded "
                         "rounds + per-device consensus-state HBM report)")
    ap.add_argument("--codec", action="store_true",
                    help="add the wire-codec cell: one measured fused "
                         "round per codec (native/int8/fp8_e4m3/fp8_e5m2) "
                         "+ the per-codec wire-bytes report "
                         "(results/wire_codec_report.json)")
    args = ap.parse_args()
    run(sharded=args.sharded, codec=args.codec)
