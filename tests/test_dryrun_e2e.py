"""Dry-run machinery end-to-end at debug scale (subprocess: 8 devices).

Exercises _compile_step/_corrected_record/lower-cell plumbing with reduced
configs on a small mesh — the same code paths the production 512-device
dry-run uses, cheap enough for CI.
"""

import pytest

from script_result import run_result

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses as dc
import jax
from repro.configs import get_reduced_config
from repro.configs.base import ShapeCell
from repro.launch import dryrun
from repro.launch.mesh import make_debug_mesh

out = {}
for arch, kind in [("qwen3-4b", "train"), ("rwkv6-7b", "train"),
                   ("moonshot-v1-16b-a3b", "train"),
                   ("qwen3-4b", "decode"), ("rwkv6-7b", "prefill")]:
    cfg = get_reduced_config(arch)
    cell = ShapeCell("tiny", 64, 8, kind)
    mesh = make_debug_mesh(multi_pod=(kind == "train"))
    rec = dryrun._corrected_record(cfg, cell, mesh,
                                   consensus=(kind == "train"))
    key = f"{arch[:8]}_{kind}"
    out[key] = {
        "flops": rec["flops_per_device"],
        "uncorrected": rec["uncorrected"]["flops_per_device"],
        "wire": rec["collectives"]["wire_total"],
    }
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def recs():
    return run_result(_SCRIPT, timeout=1800)


def test_all_cells_lower_and_compile(recs):
    assert len(recs) == 5
    for k, v in recs.items():
        assert v["flops"] > 0, k


def test_trip_count_correction_increases_flops(recs):
    """Corrected FLOPs must exceed the while-body-once raw count."""
    for k, v in recs.items():
        assert v["flops"] >= v["uncorrected"] * 0.999, (k, v)
    # the 2-layer reduced configs still gain from the layer extrapolation
    assert recs["qwen3-4b_train"]["flops"] > \
        recs["qwen3-4b_train"]["uncorrected"]


def test_multi_pod_train_has_cross_pod_wire(recs):
    assert recs["qwen3-4b_train"]["wire"] > 0
