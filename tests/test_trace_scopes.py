"""The program's own trace structure: every compiled op of the local step
and of the synchronous round lies under exactly one named phase
(``obs.trace``), and the launcher's loop writes its host spans, one
``train/step`` per step with its ``step_num``, into a profiler trace.

The compiles and the launcher run in subprocesses on 4 fake CPU devices
(the device count must be set before jax starts), kept to two cores so
that they do not starve other tests' multi-device scripts past XLA:CPU's
collective timeout in a full suite run.
"""
import os

import pytest

from hlo_scopes import audit, phases_of
from script_result import run_result

TESTS = os.path.dirname(os.path.abspath(__file__))

_TWO_CORES = """
import os
os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[-2:])
"""

_COMPILE = _TWO_CORES + r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, TESTS)
import jax
from hlo_scopes import audit
from repro.configs import get_reduced_config
from repro.core.penalty import PenaltyConfig
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.obs import ObsConfig
from repro.optim import ConsensusConfig, ConsensusTrainer
from repro.optim.adamw import AdamWConfig

cfg = get_reduced_config("qwen3-4b")
model = build_model(cfg)

def trainer(mesh, obs=None):
    return ConsensusTrainer(model, mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=PenaltyConfig(scheme="nap", eta0=0.1),
            topology="ring", local_steps=2, obs=obs))

def data(tr):
    return SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      batch_per_node=2,
                                      num_nodes=tr.num_nodes))

ring = make_mesh((4, 1, 1), ("pod", "data", "model"))
out = {}
for tag, obs in (("consensus_step", None),
                 ("consensus_step_obs", ObsConfig(ring_capacity=8))):
    tr = trainer(ring, obs)
    st = tr.init_state(jax.random.PRNGKey(0))
    out[tag] = audit(jax.jit(tr.consensus_step).lower(
        st, data(tr).batch(0, probe=True)).compile().as_text())
for tag, mesh in (("train_step_j4", ring),
                  ("train_step_j1", make_mesh((1, 1, 1),
                                              ("pod", "data", "model"))),
                  ("train_step_one_node", make_mesh((1, 1),
                                                    ("data", "model")))):
    tr = trainer(mesh)
    st = tr.init_state(jax.random.PRNGKey(0))
    out[tag] = audit(jax.jit(tr.train_step).lower(
        st, data(tr).batch(0)).compile().as_text())
print("RESULT " + json.dumps(out))
""".replace("TESTS", repr(TESTS))

ROUND_PHASES = {"consensus/pack", "consensus/exchange", "consensus/probe",
                "consensus/fused_round", "consensus/penalty"}
EXPECT = {
    "consensus_step": ROUND_PHASES,
    "consensus_step_obs": ROUND_PHASES | {"obs/ring"},
    "train_step_j4": {"local/grad", "local/optimizer"},
    "train_step_j1": {"local/grad", "local/optimizer"},
    "train_step_one_node": {"local/grad", "local/optimizer"},
}


@pytest.fixture(scope="module")
def compiled():
    return run_result(_COMPILE, timeout=900)


@pytest.mark.parametrize("program", sorted(EXPECT))
def test_every_op_lies_under_exactly_one_phase(compiled, program):
    got = compiled[program]
    assert got["bad"] == [], got["bad"][:10]
    # every phase is there, and the program's own ops outnumber the
    # compiler's (the audit is not vacuous)
    assert set(got["phases"]) == EXPECT[program]
    assert sum(got["phases"].values()) > sum(got["compiler_made"].values())


def test_audit_by_hand():
    hlo = """
%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %convert.1 = bf16[8]{0} convert(%param_0), metadata={op_name="jit(f)/consensus/pack/convert"}
  ROOT %bitcast.1 = f32[8]{0} bitcast(%convert.1)
}

ENTRY %main (x: f32[8]) -> (f32[8], f32[8], f32[8], f32[8]) {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %iota.1 = s32[8]{0} iota(), iota_dimension=0, metadata={op_name="jit(f)/iota"}
  %cmp.1 = pred[8]{0} compare(%iota.1, %iota.1), direction=LE, metadata={op_name="jit(f)/le"}
  %a = f32[8]{0} add(%x, %x), metadata={op_name="jit(f)/vmap(local/grad)/transpose(jvp())/add"}
  %b = f32[8]{0} multiply(%x, %x), metadata={op_name="jit(f)/consensus/probe/mul"}
  %c = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %d = f32[8]{0} subtract(%x, %a), metadata={op_name="jit(f)/sub"}
  %e = f32[8]{0} copy(%d)
  %g = f32[8]{0} negate(%x), metadata={op_name="jit(f)/consensus/probe/consensus/pack/neg"}
  ROOT %t = (f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}) tuple(%a, %b, %c, %e)
}
"""
    got = audit(hlo)
    # a, b, c (through its fused root) counted; iota/compare are a
    # compile-time constant; the copy is the compiler's; d is under no
    # phase and g under two
    assert got["phases"] == {"local/grad": 1, "consensus/probe": 1,
                             "consensus/pack": 1}
    assert got["compiler_made"] == {"copy": 1}
    assert [(op, ph) for op, _, ph in got["bad"]] == [
        ("subtract", []), ("negate", ["consensus/pack", "consensus/probe"])]
    assert got["ops"] == 6


def test_phase_names_inside_transforms():
    assert phases_of("jit(f)/vmap(local/optimizer)/add") == {
        "local/optimizer"}
    assert phases_of("jit(f)/consensus/exchange/off1/wire/decode/x") == {
        "consensus/exchange"}
    assert phases_of("jit(f)/local/gradient/x") == set()
    assert phases_of("jit(f)/consensus/probe") == {"consensus/probe"}


_LAUNCHER = _TWO_CORES + r"""
import os, sys, json, glob
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
from jax.profiler import ProfileData
from repro.launch.train import main
with jax.profiler.trace(OUT):
    main(["--arch", "qwen3-4b", "--reduced", "--mesh", "local", "--steps",
          "4", "--local-steps", "2", "--seq", "32", "--batch-per-node", "2"])
path = sorted(glob.glob(os.path.join(OUT, "**", "*.xplane.pb"),
                        recursive=True))[-1]
spans = []
for plane in ProfileData.from_file(path).planes:
    if not plane.name.startswith("/host:"):
        continue
    for line in plane.lines:
        for ev in line.events:
            if ev.name.startswith(("train/", "round/")):
                stats = dict(ev.stats)
                spans.append([ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns,
                              stats.get("step_num")])
print("RESULT " + json.dumps(spans))
"""


def test_launcher_writes_its_step_spans(tmp_path):
    spans = run_result(_LAUNCHER.replace("OUT", repr(str(tmp_path))),
                       timeout=900)
    steps = sorted((s for s in spans if s[0] == "train/step"),
                   key=lambda s: s[1])
    assert [int(s[3]) for s in steps] == [0, 1, 2, 3]

    def inside(step, name):
        _, t0, t1, _ = step
        return [s for s in spans if s[0] == name and t0 <= s[1]
                and s[2] <= t1]

    for k, step in enumerate(steps):
        round_step = k % 2 == 1            # --local-steps 2: steps 1 and 3
        assert len(inside(step, "train/dispatch")) == 1
        assert len(inside(step, "train/batch")) == (2 if round_step else 1)
        assert len(inside(step, "train/wait")) == (2 if round_step else 1)
        assert len(inside(step, "round/sync")) == (1 if round_step else 0)
    assert len([s for s in spans if s[0] == "round/sync"]) == 2
    assert not [s for s in spans if s[0] == "round/async"]
