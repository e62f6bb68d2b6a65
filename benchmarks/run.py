"""Benchmark driver: one harness per paper table/figure + system benches.

Prints a ``name,value,derived`` CSV summary at the end. Full sweeps:
``python -m benchmarks.run --full``.

Process layout: this parent never imports JAX. Each cell runs in a child
process of its own (``python -m benchmarks.run --cell NAME``), one after
another, so a child always owns the devices outright (a parent holding a
TPU would starve it), x64 turned on by the paper benches never leaks into
the trainer cells, and a crash costs one cell, not the summary. A child
prints its summary records as one ``RECORDS <json>`` line.

Output layout (single-writer rule, see ``benchmarks/common.py``): every
benchmark module writes only under ``benchmarks/results/``; THIS driver is
the sole writer of the committed repo-root ``BENCH_*.json`` baselines — it
promotes a cell's results artifact after the cell succeeds on the full
grid (``--full``), so smoke/CI runs can never clobber a baseline.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import subprocess
import sys
import time

CELLS = ("fig2", "fig3", "hopkins", "roofline", "consensus", "lm_ablation",
         "topology", "async", "obs")
# root baseline each cell's results artifact is promoted to (--full only)
BASELINES = {"consensus": "BENCH_consensus.json",
             "topology": "BENCH_topology.json",
             "async": "BENCH_async.json", "obs": "BENCH_obs.json"}
RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _read_csv(name):
    with open(os.path.join(RESULTS, name)) as f:
        return list(csv.DictReader(f))


def _read_json(name):
    with open(os.path.join(RESULTS, name)) as f:
        return json.load(f)


def run_cell(name: str, full: bool) -> list[tuple]:
    """Run one cell in THIS process; return its (name, value, derived)
    summary records. Only ever called in a child."""
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = 20 if full else 3
    out = []

    def record(key, value, derived=""):
        out.append((key, value, derived))

    t0 = time.time()
    if name == "fig2":
        from benchmarks import fig2_synthetic
        rows = fig2_synthetic.run(seeds=seeds if full else 2,
                                  sizes=(12, 16, 20) if full else (12, 20))
        by = {(r["nodes"], r["topology"], r["scheme"]): r for r in rows}
        for j in sorted({r["nodes"] for r in rows}):
            base = by.get((j, "complete", "fixed"))
            vp = by.get((j, "complete", "vp"))
            if base and vp:
                sp = 100 * (base["iters_median"] - vp["iters_median"]) \
                    / base["iters_median"]
                record(f"fig2_J{j}_complete_vp_speedup_pct", round(sp, 1),
                       f"baseline={base['iters_median']:.0f}it")
        record("fig2_wall_s", round(time.time() - t0, 1))
    elif name == "fig3":
        from benchmarks import fig3_sfm
        rows = fig3_sfm.run(seeds=seeds if full else 2)
        by = {(r["topology"], r["t_max"], r["scheme"]): r for r in rows}
        b5 = by.get(("complete", 5, "fixed"))
        n5 = by.get(("complete", 5, "nap"))
        if b5 and n5:
            sp = 100 * (b5["iters_median"] - n5["iters_median"]) \
                / b5["iters_median"]
            record("fig3_tmax5_nap_speedup_pct", round(sp, 1),
                   "NAP accelerates where t_max-bound methods cannot")
        record("fig3_wall_s", round(time.time() - t0, 1))
    elif name == "hopkins":
        from benchmarks import tab_hopkins
        rows = tab_hopkins.run(num_objects=20 if full else 6,
                               seeds=3 if full else 2)
        for r in rows:
            if r["topology"] == "complete" and r["scheme"] in ("vp", "vp_ap"):
                record(f"hopkins_complete_{r['scheme']}_speedup_pct",
                       r["speedup_vs_fixed_pct"],
                       "paper: vp=40.2 vp_ap=37.3")
        record("hopkins_wall_s", round(time.time() - t0, 1))
    elif name == "roofline":
        from benchmarks import roofline
        rows = roofline.run()
        ok = [r for r in rows if r["status"] == "OK"]
        if ok:
            fracs = [r["roofline_frac"] for r in ok]
            record("roofline_cells_ok", len(ok), f"of {len(rows)}")
            record("roofline_frac_median",
                   round(sorted(fracs)[len(fracs) // 2], 4))
    elif name == "consensus":
        from benchmarks import consensus_overhead
        consensus_overhead.run()
        for r in _read_csv("consensus_overhead.csv"):
            if r["mode"] == "consensus_H16":
                record("consensus_H16_wire_vs_allreduce", r["vs_allreduce"],
                       "cross-pod bytes ratio")
    elif name == "topology":
        from benchmarks import topology_dynamics
        rows = topology_dynamics.run(smoke=not full,
                                     seeds=seeds if full else 1)
        by = {(r["topology"], r["scheduler"]): r for r in rows}
        for topo in sorted({r["topology"] for r in rows}):
            b = by.get((topo, "budget"))
            if b:
                record(f"topology_{topo}_budget_active_final",
                       b["active_final"],
                       f"iters={b['iters_median']:.0f} (vs static "
                       f"{by[(topo, 'static')]['iters_median']:.0f})")
        record("topology_wall_s", round(time.time() - t0, 1))
    elif name == "async":
        from benchmarks import async_staleness
        async_staleness.main([] if full else ["--smoke"])
        bench = _read_json("BENCH_async.json")
        for r in bench["rows"]:
            record(f"async_speedup_wire{r['wire_frac']}", r["speedup"],
                   f"sync={r['rounds_sync']}r async={r['ticks_async']}t")
        record("async_objective_drift", bench["objective_drift"],
               "|f_async - f_sync| / f_sync")
    elif name == "obs":
        from benchmarks import obs_overhead
        obs_overhead.run()
        bench = _read_json("BENCH_obs.json")
        record("obs_overhead_pct",
               round(100 * bench["obs_overhead_ratio"], 2),
               f"on={bench['rounds']['obs_on']['round_ms']}ms "
               f"off={bench['rounds']['obs_off']['round_ms']}ms")
    elif name == "lm_ablation":
        from benchmarks import lm_scheme_ablation
        lm_scheme_ablation.run()
        rows = _read_csv("lm_scheme_ablation.csv")
        best = min(rows, key=lambda r: float(r["final_loss"]))
        record("lm_ablation_best_scheme", best["scheme"],
               f"loss={best['final_loss']}")
    else:
        raise ValueError(f"unknown cell {name!r}")
    return out


def spawn_cell(name: str, full: bool) -> tuple[list[tuple], str]:
    """Run one cell in a child process; (records, error or '')."""
    env = dict(os.environ)
    # the trainer cells need an 8-device mesh; on the CPU that is 8 fake
    # host devices (the flag only touches the CPU platform)
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    cmd = [sys.executable, "-m", "benchmarks.run", "--cell", name] \
        + (["--full"] if full else [])
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=3600)
    records = []
    for line in proc.stdout.splitlines():
        if line.startswith("RECORDS "):
            records = [tuple(r) for r in json.loads(line[len("RECORDS "):])]
        else:
            print(line)
    if proc.returncode != 0:
        err = proc.stderr.strip().splitlines()
        return records, (err[-1][:80] if err else f"rc {proc.returncode}")
    return records, ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (20 seeds etc.)")
    ap.add_argument("--only", default="all", choices=("all",) + CELLS)
    ap.add_argument("--cell", choices=CELLS,
                    help="run one cell in this process and print its "
                         "RECORDS line (what each child of the runner runs)")
    args = ap.parse_args(argv)
    if args.cell:
        print("RECORDS " + json.dumps(run_cell(args.cell, args.full)),
              flush=True)
        return 0

    summary, failed = [], False
    for name in CELLS:
        if args.only not in ("all", name):
            continue
        records, err = spawn_cell(name, args.full)
        summary += records
        if err:
            failed = True
            summary.append((f"{name}_bench", "FAILED", err))
        elif args.full and name in BASELINES:
            # single-writer rule: only this driver touches root baselines,
            # and only when the full grid ran
            from benchmarks.common import promote_baseline
            path = promote_baseline(BASELINES[name])
            if path:
                summary.append((f"promoted_{BASELINES[name]}", path, ""))

    print("\nname,value,derived")
    for name, value, derived in summary:
        print(f"{name},{value},{derived}")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
