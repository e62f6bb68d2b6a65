"""End-to-end launcher tests: train -> checkpoint -> crash -> resume,
straggler handling, and elastic node-drop (subprocess: needs 8 devices)."""

import pytest

from script_result import run_result


def _run(script: str, timeout=1200):
    return run_result(script, timeout=timeout)


_RESUME = r"""
import os, json, shutil
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
ck = "/tmp/repro_test_resume"
shutil.rmtree(ck, ignore_errors=True)
from repro.launch.train import main
# phase 1: 8 steps, checkpoint every 4
main(["--arch", "qwen3-4b", "--reduced", "--steps", "8", "--ckpt-dir", ck,
      "--ckpt-every", "4", "--scheme", "nap", "--local-steps", "4"])
from repro.checkpoint import latest_steps
steps_after_1 = latest_steps(ck)
# phase 2 simulates a restart: same command, more steps -> resumes from 8
main(["--arch", "qwen3-4b", "--reduced", "--steps", "12", "--ckpt-dir", ck,
      "--ckpt-every", "4", "--scheme", "nap", "--local-steps", "4"])
steps_after_2 = latest_steps(ck)
print("RESULT " + json.dumps({"p1": steps_after_1, "p2": steps_after_2}))
"""


def test_train_checkpoint_resume():
    out = _run(_RESUME)
    assert 8 in out["p1"], out
    assert max(out["p2"]) == 12, out


_ELASTIC = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.core.graph import build_graph
from repro.core.penalty import PenaltyConfig, init_penalty_state
from repro.runtime import ElasticController, StragglerMonitor

# straggler detection drives the elastic drop
mon = StragglerMonitor(4, threshold=2.0, patience=2)
g = build_graph("ring", 4)
pen = init_penalty_state(PenaltyConfig(scheme="nap"), 4)
ctl = ElasticController(g)
victims = []
for step in range(6):
    durations = np.array([1.0, 1.0, 1.0, 1.0 if step < 2 else 9.0])
    slow = mon.observe(durations)
    for v in slow:
        if ctl.graph.num_nodes > 2 and not victims:
            g2, pen = ctl.drop(v, pen, step)
            victims.append(v)
print("RESULT " + json.dumps({
    "victims": victims,
    "nodes": ctl.graph.num_nodes,
    "connected": ctl.graph.is_connected(),
    "pen_shape": list(np.asarray(pen.eta).shape),
}))
"""


def test_straggler_to_elastic_pipeline():
    out = _run(_ELASTIC, timeout=600)
    assert out["victims"] == [3]
    assert out["nodes"] == 3 and out["connected"]
    assert out["pen_shape"] == [3, 3]


_LOCAL_MESH = r"""
import contextlib, io, json, math, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from repro.launch import train
argv = ["--arch", "qwen3-4b", "--reduced", "--mesh", "local",
        "--n-layers", "1", "--vocab", "128", "--steps", "4",
        "--local-steps", "2", "--seq", "16", "--batch-per-node", "2"]
buf = io.StringIO()
with contextlib.redirect_stdout(buf):
    rc = train.main(argv)
cfg, tr = train.build_trainer(train.parse_args(argv))
state = tr.init_state(jax.random.PRNGKey(0))
owners = {sh.index[0].start: sh.device.id
          for sh in jax.tree_util.tree_leaves(state.params)[0]
          .addressable_shards}
print("RESULT " + json.dumps({
    "rc": rc, "lines": buf.getvalue().splitlines(), "J": tr.num_nodes,
    "layers": cfg.n_layers, "vocab": cfg.vocab, "d_model": cfg.d_model,
    "owners": sorted(owners.items())}))
"""


def test_local_mesh_runs_ring_with_depth_and_vocab_cuts():
    out = _run(_LOCAL_MESH, timeout=600)
    assert out["rc"] == 0
    lines = out["lines"]
    assert "cut: n_layers 2 -> 1 (qwen3-4b, widths unchanged)" in lines
    assert "cut: vocab 256 -> 128 (qwen3-4b, widths unchanged)" in lines
    # one node per device: J = 4 on a (4, 1, 1) mesh, node i on device i
    assert out["J"] == 4
    assert out["owners"] == [[i, i] for i in range(4)]
    assert (out["layers"], out["vocab"], out["d_model"]) == (1, 128, 64)
    steps = [ln for ln in lines if ln.startswith("step ")]
    assert len(steps) == 4
    for ln in steps:
        loss = float(ln.split()[3])
        assert loss == loss and abs(loss) < 1e3, ln
    assert sum("consensus r=" in ln for ln in steps) == 2


def test_no_multi_pod_reaches_the_mesh(monkeypatch):
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    assert train.parse_args([]).multi_pod is True
    seen = []

    def fake_debug_mesh(*, multi_pod):
        # the debug mesh's axes on the one device this process has
        seen.append(multi_pod)
        return make_mesh((1, 1, 1) if multi_pod else (1, 1),
                         (("pod",) if multi_pod else ()) + ("data", "model"))

    monkeypatch.setattr(train, "make_debug_mesh", fake_debug_mesh)
    _, tr = train.build_trainer(
        train.parse_args(["--reduced", "--no-multi-pod"]))
    assert seen == [False]
    assert not tr.has_pod and tr.num_nodes == 1
