#!/usr/bin/env python3
"""Readings that the comparison's limits are set from, on the chip at a
cell's own size, in one process:

  python3 benchmarks/chip/control.py --workload <name> \
      [--program 1,2,...] [--control 1,2,3] [--faults 1] [--out FILE]

``--program``: sound runs of the program (warm-up, a short window, the
readings; the lower readings). ``--control``: the reference at float8 in
the program's place, against the float32 reference. ``--faults``: the
faults the comparison must catch, planted in the reference in the
program's place. Prints one line per seed with every number the
comparison takes (and appends it to ``--out``); each number's limit
(``limits/<cell>.json``; PERF.md gives the readings) lies above the sound
runs and below the control or a fault. The benchmark's own runs do not
run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

from chipbench import compare, harness, manifest  # noqa: E402

# faults read at the cell's size, planted in the reference in the
# program's place; a state left unchanged reads 1 and needs no run
FAULTS = {False: ("half_batch",), True: ("half_batch", "no_exchange")}
OPEN = 1e9            # a limit that every reading passes


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=int, default=1)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    cell = manifest.cell(manifest.load(), args.workload)
    import jax
    devices = jax.devices()[:cell["chips"]]
    program, control, faults = (seeds(args.program), seeds(args.control),
                                seeds(args.faults))
    order = list(dict.fromkeys(program + control + faults))
    for seed in order:
        out, t0 = {"seed": seed}, time.perf_counter()
        if seed in program:
            kept = {}
            open_cell = dict(cell, limits={k: OPEN for k in cell["limits"]})
            result = harness.run_cell(open_cell, seed, args.seconds, False,
                                      devices, time.perf_counter(),
                                      keep=kept)
            ref = kept["ref"]
            out["program"] = compare.numbers(kept["program"], ref)
            out["setup_s"] = result["metrics"]["setup_s"]["value"]
        else:
            ref = compare.reference_readings(cell, seed, devices, "f32")
        if seed in control:
            out["control"] = compare.numbers(compare.reference_readings(
                cell, seed, devices, "fp8"), ref)
        if seed in faults:
            for fault in FAULTS[cell["chips"] > 1]:
                out[fault] = compare.numbers(compare.reference_readings(
                    cell, seed, devices, fault=fault), ref)
        out["seconds"] = time.perf_counter() - t0
        line = json.dumps(out)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    print(json.dumps({"limits": cell["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
