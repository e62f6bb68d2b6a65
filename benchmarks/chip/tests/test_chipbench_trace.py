"""The reduction from a profiler trace to numbers."""
import json
import types
from pathlib import Path

import pytest

from chipbench import flops, manifest
from chipbench import trace as tr

FIXTURE = Path(__file__).parent / "data" / "ring_tiny.xplane.pb"


def test_interval_arithmetic_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.total([(0, 3), (5, 8)]) == 6
    assert tr.minus([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == [
        (0, 2), (4, 8), (22, 30)]
    assert tr.minus([(0, 10)], []) == [(0, 10)]


def test_self_time_and_leaves_of_nested_ops():
    chip = tr.Chip(modules=[], ops=[
        ("%while.1 = (s32[]) while(...)", 0, 100),
        ("%fusion.2 = f32[8] fusion(...)", 10, 30),
        ("%fusion.3 = f32[8] fusion(...)", 40, 60),
        ("%collective-permute-done.4 = bf16[8] ...", 120, 130)])
    own = {tr.op_name(n): t for n, _, _, t in chip.self_ns()}
    assert own == {"while.1": 60, "fusion.2": 20, "fusion.3": 20,
                   "collective-permute-done.4": 10}
    assert [tr.op_name(n) for n, _, _ in chip.leaves()] == [
        "fusion.2", "fusion.3", "collective-permute-done.4"]


def test_idle_gaps_are_named_by_the_covering_host_span():
    chip = tr.Chip(modules=[], ops=[("%a.1 = x", 0, 10), ("%b.2 = x", 50, 60),
                                    ("%c.3 = x", 65, 70)])
    red = tr.Reduced(chips=[chip], window_s=1e-7, busy_s=25e-9,
                     host_spans=[("bench/batch", 5, 30),
                                 ("bench/readback", 30, 52)])
    gaps = red.idle_gaps(chip)
    assert gaps[0][0] == "bench/batch" and gaps[0][1] == pytest.approx(4e-8)
    assert gaps[1][0] == "none" and gaps[1][1] == pytest.approx(5e-9)


def test_recorded_ring_trace():
    """A trace of the ring at a test size (``fault_run.py qwen3-4b.ring4.h2
    sound 1``), recorded on a four-chip v5e and cut to its first two
    rounds: the device lines the reduction reads and the harness's host
    spans, without event stats."""
    red = tr.reduce(FIXTURE, host_window=(0.0, 1.0))
    assert len(red.chips) == 4
    assert {n for n, _, _ in red.host_spans} >= {
        "bench/batch", "bench/dispatch", "bench/readback", "bench/round"}
    for chip in red.chips:
        steps = red.module_ns(chip, "jit_train_step")
        rounds = red.module_ns(chip, "jit_consensus_step")
        assert len(steps) == 2 * len(rounds) > 0
        # ops on one core run one at a time: the self times of all ops
        # add up to the union of their intervals
        busy = tr.total(tr.union((s, e) for _, s, e in chip.ops))
        own = sum(t for *_, t in chip.self_ns())
        assert own == pytest.approx(busy, rel=1e-3)
        assert 0 < tr.kernel_ns(chip) < sum(rounds)
        leaves = [tr.op_name(n) for n, _, _ in chip.leaves()]
        assert any(n.startswith(tr.COLLECTIVE) for n in leaves)
    assert 0 < red.busy_s < 1.0
    bd = red.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10


def test_readers_on_the_recorded_trace():
    red = tr.reduce(FIXTURE, host_window=(0.0, 0.5))
    with open(manifest.HERE / "tests" / "data" / "tiny.json") as f:
        model = json.load(f)
    run = types.SimpleNamespace(
        counted=4, h=2, nodes=4, model=model, device_kind="TPU v5 lite",
        mix={"batch_per_node": 2, "seq_len": 32},
        round_bytes=flops.round_kernel_bytes(4096, 2, 2, 8192),
        batch_s=[(0.1, 0.002), (0.2, 0.003), (0.7, 1.0)], window_t0=0.0,
        window_t1=0.5, compiles=[0.25, 0.9])
    ctx = tr.Context(run=run, red=red)
    got = {m: tr.load_reader(m)(ctx) for m in (
        "host_input_ms", "window_compiles", "local_step_ms", "mfu",
        "round_ms", "exchange_exposed_ms", "fused_round_roofline",
        "idle_frac")}
    assert got["host_input_ms"] == pytest.approx(1e3 * 0.005 / 4)
    assert got["window_compiles"] == 1.0
    assert got["idle_frac"] == pytest.approx(100 * (1 - red.busy_s / 0.5))
    chips = red.chips
    assert got["round_ms"] == pytest.approx(1e-6 * sum(
        sum(red.module_ns(c, "jit_consensus_step")) for c in chips) / 4 / 2)
    for name, value in got.items():
        assert value is not None and value >= 0, name
    assert 0 < got["exchange_exposed_ms"] < got["round_ms"]
