"""The comparison catches a broken timed path and the float8 control,
and passes a sound one.

Each cell's cases run one after another in one process of
``fault_run.py`` on the CPU (the ring on four host devices), at a test
size, with the cell's own limits. The processes keep to two cores, so
that the suite's multi-device scripts running beside them are not
starved past their collectives' time limit.
"""
import json
import os
import subprocess
import sys

import pytest

from chipbench import manifest

CASES = {
    "qwen3-4b.solo": {"sound": True, "control": False, "unchanged": False,
                      "half_batch": False, "loss_altered": False},
    "qwen3-4b.ring4.h2": {"sound": True, "control": False,
                          "no_exchange": False, "unchanged_lam": False},
}


def _two_cores():
    cores = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, cores[-2:])


@pytest.mark.parametrize("cell", sorted(CASES))
def test_faults_decide_correct(cell):
    chips = manifest.cell(manifest.load(), cell)["chips"]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips} "
                         "--xla_cpu_multi_thread_eigen=false",
               OMP_NUM_THREADS="2")
    p = subprocess.run(
        [sys.executable, str(manifest.HERE / "tests" / "fault_run.py"),
         cell, ",".join(CASES[cell])], cwd=manifest.ROOT, env=env,
        capture_output=True, text=True, timeout=1200,
        preexec_fn=_two_cores)
    assert p.returncode == 0, p.stderr[-3000:]
    got = {r["fault"]: r for r in (
        json.loads(line[len("RESULT "):]) for line in p.stdout.splitlines()
        if line.startswith("RESULT "))}
    assert set(got) == set(CASES[cell])
    for fault, correct in CASES[cell].items():
        assert got[fault]["correct"] is correct, (fault, got[fault]["checks"])
