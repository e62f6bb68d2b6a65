"""Drive one run of a cell on the CPU at a test size, with the timed path
sound or broken underneath; print the result's line.

  python fault_run.py <cell> <fault>[,<fault>...] [<trace 0|1>]

Skips the harness's look for a chip and runs the rest of a run: the
launcher's loop, the window, the readings, the reference and the
comparison, with the cell's own limits. Faults are planted in the
program, where the timed path produces its answer, and taken out again
before the next. ``control`` puts the float8 reference in the program's
place instead. One line ``RESULT <json>`` per fault.
"""
import contextlib
import copy
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(HERE))), "src"))

from chipbench import compare, harness, manifest  # noqa: E402

SEED = 2 ** 31 + 1013


class NoRoll:
    """``jax.numpy`` with ``roll`` as the identity: the exchange between
    nodes left out, each node sees its own row as its neighbours'."""

    def __init__(self, jnp):
        self._jnp = jnp

    def __getattr__(self, name):
        if name == "roll":
            return lambda x, shift, axis=None: x
        return getattr(self._jnp, name)


@contextlib.contextmanager
def planted(fault: str):
    """The fault in the program while the block runs, then the program
    as it was."""
    import jax
    from repro.optim import consensus
    trainer = consensus.ConsensusTrainer
    step, jnp = trainer.train_step, consensus.jnp
    fused = trainer._fused_round
    if fault == "unchanged":
        trainer.train_step = lambda self, s, b: (s, step(self, s, b)[1])
    elif fault == "half_batch":
        trainer.train_step = lambda self, s, b: step(
            self, s, jax.tree_util.tree_map(
                lambda x: x[:, :x.shape[1] // 2], b))
    elif fault == "loss_altered":
        def altered(self, s, b):
            new, m = step(self, s, b)
            return new, dict(m, loss=m["loss"] * 1.02)
        trainer.train_step = altered
    elif fault == "no_exchange":
        consensus.jnp = NoRoll(jnp)
    elif fault == "unchanged_lam":
        def keep_lam(self, theta, lam, *args, **kw):
            out = fused(self, theta, lam, *args, **kw)
            return (out[0], lam) + tuple(out[2:])
        trainer._fused_round = keep_lam
    else:
        assert fault == "sound", fault
    try:
        yield
    finally:
        trainer.train_step, consensus.jnp = step, jnp
        trainer._fused_round = fused


def one(cell: dict, fault: str, trace: bool, devices, kept: dict) -> dict:
    """One case; ``kept`` carries the float32 reference from case to case
    (one seed, one reference)."""
    if fault == "control":
        # the reference at float8 in the program's place
        if "ref" not in kept:
            kept["ref"] = compare.reference_readings(cell, SEED, devices)
        ref = kept["ref"]
        low = compare.reference_readings(cell, SEED, devices, "fp8")
        checks = compare.checks(low, ref, cell["limits"])
        return {"correct": all(c["value"] <= c["limit"]
                               for c in checks.values()), "checks": checks}
    with planted(fault):
        return harness.run_cell(copy.deepcopy(cell), SEED, 1, trace,
                                devices, time.perf_counter(), keep=kept)


def main() -> None:
    name, faults = sys.argv[1], sys.argv[2].split(",")
    trace = len(sys.argv) > 3 and sys.argv[3] == "1"
    import jax
    cell = manifest.cell(manifest.load(), name)
    cell["model"] = json.loads(
        (manifest.HERE / "tests" / "data" / "tiny.json").read_text())
    cell["traffic_mix"].update(seq_len=32, warmup_steps=4, trace_seconds=0.3)
    devices = jax.devices()[:cell["chips"]]
    assert len(devices) == cell["chips"], devices
    kept = {}
    for fault in faults:
        result = one(cell, fault, trace, devices, kept)
        print("RESULT " + json.dumps(dict(result, fault=fault)), flush=True)


if __name__ == "__main__":
    main()
