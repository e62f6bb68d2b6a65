"""Shared benchmark utilities.

Output layout (single-writer rule):

  * every benchmark module writes ONLY under ``benchmarks/results/`` —
    CSVs via ``write_csv``, JSON artifacts via ``write_json``;
  * the repo-root ``BENCH_*.json`` files are the COMMITTED baselines.
    ``benchmarks/run.py`` is their single writer: it promotes a cell's
    ``results/BENCH_*.json`` to the root via ``promote_baseline`` after
    the cell succeeds (full-grid runs only, so CI smoke grids can never
    clobber a committed baseline).
"""
from __future__ import annotations

import contextlib
import csv
import io
import json
import os
import shutil
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_csv(name: str, rows: list[dict]):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    if not rows:
        return path
    with open(path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
        w.writeheader()
        w.writerows(rows)
    return path


def write_json(name: str, obj):
    """Write a JSON artifact under ``benchmarks/results/`` (always)."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def promote_baseline(name: str) -> str | None:
    """Copy ``results/<name>`` to the repo root (the committed baseline).

    ONLY ``benchmarks/run.py`` calls this — the single-writer rule that
    keeps benchmark modules from clobbering committed baselines.
    """
    src = os.path.join(RESULTS_DIR, name)
    if not os.path.exists(src):
        return None
    dst = os.path.join(REPO_ROOT, name)
    shutil.copyfile(src, dst)
    return dst


def require_devices(bench: str, n: int):
    """Fail, naming the platform and device count, when fewer than ``n``
    devices are visible: a benchmark never reports analytic numbers or
    skips in place of a measurement."""
    import jax
    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"{bench}: needs {n} devices, found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind}); on the "
            f"CPU run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return devs


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.dt = time.time() - self.t0
