"""Round-pipeline bit-identity: pipelined == sequential on every axis.

The latency-hiding pipeline (``ConsensusConfig.pipeline_offsets``) is a pure
REORDERING of the round: offset k+1's collective-permute is issued while
offset k decodes/probes/fuses, but every value consumed is unchanged — so
any pipeline depth must be BIT-identical (exact float equality, not
tolerance) to the sequential loop on params, duals, bar, penalty state,
ledger and metrics.

Covering matrix (one subprocess, shared model/mesh): every penalty scheme,
every wire codec {native, int8, fp8_e4m3}, both layouts {replicated,
sharded}, every edge scheduler {static, budget-gated, stale/async} and both
round paths (sync ``consensus_step``, async ``consensus_step_async`` with
partial arrivals holding ledger rows) appear in at least one case, with the
interesting interactions paired up — budget gating exercises the
dead-offset skip (``needs == 0`` holds the in-flight row unissued), churn
enables the kick path with pending zero-kicks against early-issued
permutes, async arrival gaps exercise held-vs-landed merge rows. The full
cross product would be ~84 trainer pairs x ~40-270 s each — cost-prohibited
for tier 1; the matrix keeps every axis value and the risky pairs.

Runs on a 4-pod mesh (ring offsets [1, 3]) so depth > 1 is non-trivial, and
sweeps intermediate bounded depths (2) as well as full depth (>= deg).
"""
import pytest

from script_result import run_result

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_reduced_config
from repro.core.penalty import PenaltyConfig
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import ConsensusConfig, ConsensusTrainer
from repro.optim.adamw import AdamWConfig
from repro.async_exec.ledger import AsyncConfig
from repro.topology import TopologyConfig

mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
cfg = get_reduced_config("qwen3-4b")
model = build_model(cfg)
data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  batch_per_node=2, num_nodes=4))
probe = data.batch(0, probe=True)

def make(pipe, scheme, codec, sharded, topo, async_cfg):
    return ConsensusTrainer(
        model, mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=PenaltyConfig(scheme=scheme, eta0=0.1),
            topology="ring", local_steps=1, wire_codec=codec,
            shard_consensus=sharded, dyn_topology=topo,
            async_exec=async_cfg, pipeline_offsets=pipe))

# one shared local step diverges the node replicas; independent of pipe
base = make(1, "fixed", "native", False, TopologyConfig(), None)
st0 = base.init_state(jax.random.PRNGKey(0))
st0, _ = jax.jit(base.train_step)(st0, data.batch(0))
assert len(base.offsets) >= 2, base.offsets      # depth > 1 must be real

def leaves(tr, st):
    out = [np.asarray(x, np.float32)
           for x in jax.tree_util.tree_leaves(st.params)]
    out += [np.asarray(x) for x in jax.tree_util.tree_leaves(
        tr.layout.unpack(st.lam))]
    out += [np.asarray(x) for x in jax.tree_util.tree_leaves(
        tr.layout.unpack(st.theta_bar_prev))]
    out.append(np.asarray(st.penalty.eta))
    if st.ledger is not None:
        # the pipelined sync path persists its in-flight rows in the
        # ledger; sequential-vs-pipelined ledgers may differ (that IS the
        # double buffer), so only the async path — where both maintain
        # it — pins ledger bytes
        if tr.async_cfg is not None:
            out.append(np.asarray(st.ledger.wires))
            out.append(np.asarray(st.ledger.w_prev))
    return out

# round-2 arrival schedule with gaps: nodes 1 and 3 never land on offset 0,
# offset 1 lands everywhere — exercises held ledger rows under pipelining
def arrivals(tr, r):
    deg, j = len(tr.offsets), tr.num_nodes
    if r == 0:
        return jnp.ones((deg, j), bool)
    a = np.ones((deg, j), bool)
    a[0, 1] = a[0, 3] = False
    return jnp.asarray(a)

def run(tr, rounds=2):
    st = tr.init_state(jax.random.PRNGKey(0))
    st = st._replace(params=st0.params, opt=st0.opt, step=st0.step)
    if tr.async_cfg is not None:
        cons = jax.jit(tr.consensus_step_async)
        for r in range(rounds):
            st, m = cons(st, probe, arrivals(tr, r))
    else:
        cons = jax.jit(tr.consensus_step)
        for r in range(rounds):
            st, m = cons(st, probe)
    return st, {k: float(v) for k, v in m.items()}

STATIC = TopologyConfig()
# gate_tol big enough that edges actually gate OFF within two rounds ->
# the dead-offset skip holds in-flight rows that were never issued
BUDGET = TopologyConfig(scheduler="budget", gate_tol=1e2,
                        skip_dead_offsets=True)
BUDGET_KICK = TopologyConfig(scheduler="budget", gate_tol=1e2,
                             skip_dead_offsets=True, churn=True)
STALE = TopologyConfig(scheduler="stale")
ASYNC = AsyncConfig(max_staleness=1)

# scheme, codec, sharded, topo, async, depths-to-pin (vs depth 1)
CASES = {
    "fixed_native_repl_static":   ("fixed", "native", False, STATIC, None,
                                   (2, 4)),
    "vp_int8_repl_static":        ("vp", "int8", False, STATIC, None, (4,)),
    "ap_fp8_repl_static":         ("ap", "fp8_e4m3", False, STATIC, None,
                                   (4,)),
    "nap_fp8_repl_budget_kick":   ("nap", "fp8_e4m3", False, BUDGET_KICK,
                                   None, (4,)),
    "vp_nap_int8_repl_budget":    ("vp_nap", "int8", False, BUDGET, None,
                                   (2,)),
    "vp_ap_native_repl_stale":    ("vp_ap", "native", False, STALE, ASYNC,
                                   (4,)),
    "nap_int8_shard_static":      ("nap", "int8", True, STATIC, None, (4,)),
    "vp_nap_fp8_shard_stale":     ("vp_nap", "fp8_e4m3", True, STALE,
                                   ASYNC, (2,)),
}

out = {}
for name, (scheme, codec, sharded, topo, acfg, depths) in CASES.items():
    ref_tr = make(1, scheme, codec, sharded, topo, acfg)
    ref_st, ref_m = run(ref_tr)
    ref_lv = leaves(ref_tr, ref_st)
    for depth in depths:
        tr = make(depth, scheme, codec, sharded, topo, acfg)
        st, m = run(tr)
        lv = leaves(tr, st)
        err = max((float(np.max(np.abs(a - b))) if a.size else 0.0)
                  for a, b in zip(ref_lv, lv))
        merr = max(abs(ref_m[k] - m[k]) for k in ref_m)
        out[f"{name}_d{depth}"] = {"max_err": err, "metric_err": merr,
                                   "n_buffers": len(lv)}
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def pipeline_results():
    return run_result(_SCRIPT, timeout=3000)


def test_matrix_covers_every_axis_value():
    """Vacuity guard on the covering matrix itself."""
    import re
    cases = re.findall(r'"(\w+)":\s+\("(\w+)", "(\w+)", (\w+),',
                       _SCRIPT)
    schemes = {c[1] for c in cases}
    codecs = {c[2] for c in cases}
    sharded = {c[3] for c in cases}
    assert schemes == {"fixed", "vp", "ap", "nap", "vp_ap", "vp_nap"}
    assert codecs == {"native", "int8", "fp8_e4m3"}
    assert sharded == {"False", "True"}
    for sched in ("STATIC", "BUDGET", "STALE", "ASYNC", "BUDGET_KICK"):
        assert f" {sched}," in _SCRIPT or f"{sched})" in _SCRIPT


def test_pipelined_bit_identical_to_sequential(pipeline_results):
    """EXACT equality at every depth, every case — params, duals, bar,
    penalty state, (async) ledger bytes, and round metrics."""
    assert len(pipeline_results) >= 9, sorted(pipeline_results)
    bad = {k: v for k, v in pipeline_results.items()
           if v["max_err"] != 0.0 or v["metric_err"] != 0.0}
    assert not bad, bad


def test_async_cases_pin_ledger_buffers(pipeline_results):
    """The async cases' comparisons must include the ledger arrays (wires
    + w_prev) on top of params/lam/bar/eta — catches a pipeline that gets
    the outputs right but corrupts the double buffer it hands the next
    round."""
    sync = pipeline_results["fixed_native_repl_static_d4"]["n_buffers"]
    for k in ("vp_ap_native_repl_stale_d4", "vp_nap_fp8_shard_stale_d2"):
        assert pipeline_results[k]["n_buffers"] == sync + 2, \
            (k, pipeline_results[k])
