"""Engine pins for the fp8 wire codecs (repro.wire): fused == reference.

Three subprocesses on the 8-fake-device mesh (J=4 pods, 2-way in-pod
sharding) sweep fused-vs-reference and sharded-vs-unsharded rounds with
the fp8 codecs across the gating modes (split in three so each stays well
inside the CI subprocess timeout on 2-core runners, and so pytest-xdist
workers can run them side by side):

  * ``static`` (subprocess A) — all 6 penalty schemes x both fp8 codecs x
    {reference, fused, fused+sharded}, one sync round each at f32
    round-off (identical wire bytes in), plus the roofline wire-bytes
    contract;
  * ``budget`` (subprocess B) — forced-exhaustion budget gating on the
    complete graph (zero initial budget + huge gate_tol gates every chord
    after round 1, round 2 absorbs the parked kicks) for the
    budget-capable schemes (nap, vp_nap — the budget scheduler REJECTS
    non-budget penalties by construction, so the other four schemes
    cannot run this mode), e4m3 on all three paths + an e5m2 spot check;
  * ``stale`` (subprocess C) — bounded-staleness async rounds (complete
    graph, sender 0 lands only at tick 0 => its edges age 0,1,2 and gate
    with an in-round ledger zero-kick at tick 2) for all 6 schemes with
    fp8_e4m3 {ref, fused} + sharded and e5m2 spot checks. The two fp8
    codecs share every line of codec/kernel code except the dtype
    constant and its finite-range clamp — both already pinned bit-exact
    by the roundtrip property harness in test_flatten_sharded.py — so the
    e5m2 spot checks carry the same evidence as a full sweep. Revival
    after gating is wire-format-independent executor logic, pinned at
    int8/native precision in test_async_exec.py.

Documented fp8 tolerance: both paths decode the SAME wire bytes each
round, so single-round fused-vs-ref differences are f32 round-off; over
multiple rounds the paths may drift by bf16 param-storage ulps which the
next encode amplifies to one fp8 LSB of the per-block absmax scale
(e4m3: absmax * 2^-4) — hence rtol 1e-2 with an atol of one wire LSB,
mirroring the int8 staleness pins in test_async_exec.py. Sharded vs
unsharded stays at f32 exactness (1e-5): per-block scales are slab-local,
so the sharded engine consumes byte-identical wire.
"""

import pytest

from script_result import run_result

_PREAMBLE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from repro.async_exec import AsyncConfig
from repro.configs import get_reduced_config
from repro.core.penalty import SCHEMES, PenaltyConfig, init_penalty_state
from repro.data import DataConfig, SyntheticTokens
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import ConsensusConfig, ConsensusTrainer
from repro.optim.adamw import AdamWConfig
from repro.topology import TopologyConfig

mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
cfg = get_reduced_config("qwen3-4b")
model = build_model(cfg)
data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=16,
                                  batch_per_node=1, num_nodes=4))
probe = data.batch(0, probe=True)
FP8 = ("fp8_e4m3", "fp8_e5m2")
out = {}

def make(codec, scheme="nap", fused=True, sharded=False, topology="ring",
         dyn=None, async_cfg=None, penalty=None):
    return ConsensusTrainer(
        model, mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=penalty or PenaltyConfig(scheme=scheme, eta0=0.1),
            topology=topology, local_steps=1, wire_codec=codec,
            use_fused_kernel=fused, shard_consensus=sharded,
            dyn_topology=dyn or TopologyConfig(), async_exec=async_cfg))

base = make("fp8_e4m3")
state0 = base.init_state(jax.random.PRNGKey(0))
state0, _ = jax.jit(base.train_step)(state0, data.batch(0))

def leaves(tr, st):
    # layout-independent view (params + per-leaf lam/bar + penalties)
    return ([np.asarray(x, np.float32)
             for x in jax.tree_util.tree_leaves(st.params)]
            + [np.asarray(x) for x in jax.tree_util.tree_leaves(
                tr.layout.unpack(st.lam))]
            + [np.asarray(x) for x in jax.tree_util.tree_leaves(
                tr.layout.unpack(st.theta_bar_prev))]
            + [np.asarray(st.penalty.eta)])

def sync_rounds(tr, rounds=2):
    st = jax.tree_util.tree_map(lambda x: x, state0)
    flat = (tr.num_nodes, tr.layout.total)
    st = st._replace(
        lam=jnp.zeros(flat, jnp.float32),
        theta_bar_prev=jnp.zeros(flat, jnp.float32),
        penalty=init_penalty_state(tr.ccfg.penalty, tr.num_nodes),
        topo=tr.topo_rt.init_state(),
        ledger=None)
    cons = jax.jit(tr.consensus_step)
    m = {}
    for _ in range(rounds):
        st, m = cons(st, probe)
    return leaves(tr, st), {k: float(v) for k, v in m.items()}, st

def errs(a, b):
    lerr = max(float(np.max(np.abs(x - y))) for x, y in zip(a[0], b[0]))
    merr = max(abs(a[1][k] - b[1][k]) / (abs(b[1][k]) + 1.0) for k in b[1])
    return {"max_err": lerr, "metric_rel_err": merr}

def close(a, b, atol):
    return bool(all(np.allclose(x, y, rtol=1e-2, atol=atol)
                    for x, y in zip(a[0], b[0])))

# one wire LSB of the per-block absmax scale at the observed param range
ATOL = {"fp8_e4m3": 3e-2, "fp8_e5m2": 6e-2}
"""

_STATIC = _PREAMBLE + r"""
# --- static: 6 schemes x 2 fp8 codecs x {ref, fused, fused+sharded} ------
# ONE round: fused and reference consume byte-identical wire, so the RAW
# f32 flat state (lam, theta_bar_prev, eta) pins at f32 round-off; the
# bf16-STORED params may legitimately differ by one storage ulp when a
# ~1e-8 f32 difference lands on a bf16 rounding boundary. (Comparing the
# flat state through a bf16-casting view would quantize that same 1e-8
# into a full bf16 ulp — hence the raw views here. Multi-round drift is
# the wire-precision regime the budget/stale pins cover.)
T0 = base.layout.total          # common width: sharded layouts pad MORE

def fviews(st):                 # raw f32 flat state, common-width slice
    return [np.asarray(st.lam)[:, :T0],
            np.asarray(st.theta_bar_prev)[:, :T0],
            np.asarray(st.penalty.eta)]

def pviews(st):
    return [np.asarray(x, np.float32)
            for x in jax.tree_util.tree_leaves(st.params)]

def static_errs(a, b):
    return {
        "flat_err": max(float(np.max(np.abs(x - y)))
                        for x, y in zip(fviews(a[2]), fviews(b[2]))),
        "param_err": max(float(np.max(np.abs(x - y)))
                         for x, y in zip(pviews(a[2]), pviews(b[2]))),
        "metric_rel_err": errs(a, b)["metric_rel_err"]}

out["static"] = {}
for scheme in SCHEMES:
    for codec in FP8:
        ref = sync_rounds(make(codec, scheme, fused=False), rounds=1)
        fus = sync_rounds(make(codec, scheme), rounds=1)
        shd = sync_rounds(make(codec, scheme, sharded=True), rounds=1)
        out["static"][f"{scheme}_{codec}"] = {
            "fused_vs_ref": static_errs(fus, ref),
            "sharded_vs_fused": static_errs(shd, fus)}

# --- wire contract: fp8 roofline bytes = 1 B/param + 4 B/block -----------
from repro.launch.dryrun import fused_round_roofline
out["wire"] = {}
for codec in FP8:
    tr = make(codec)
    rl = fused_round_roofline(model, mesh, compression=codec)
    out["wire"][codec] = {
        "roofline_row_bytes": rl["wire_bytes_per_round"]
        // max(rl["active_offsets"], 1),
        "expected_row_bytes": tr.layout.total + 4 * tr.layout.num_blocks,
        "trainer_row_bytes": tr.codec.wire_bytes(),
        "native_row_bytes": fused_round_roofline(
            model, mesh, compression="native")["wire_bytes_per_round"]
        // max(rl["active_offsets"], 1),
    }
print("RESULT " + json.dumps(out))
"""

_BUDGET = _PREAMBLE + r"""
# --- budget-gated: forced exhaustion on the complete graph ---------------
# (budget-capable schemes only: the scheduler validates uses_budget)
out["budget"] = {}
bdyn = TopologyConfig(scheduler="budget", gate_tol=1e9)
budget_grid = [("nap", "fp8_e4m3", True), ("vp_nap", "fp8_e4m3", True),
               ("nap", "fp8_e5m2", False)]
for scheme, codec, with_sharded in budget_grid:
    bpen = PenaltyConfig(scheme=scheme, eta0=0.1, budget_init=0.0)
    kw = dict(topology="complete", dyn=bdyn, penalty=bpen)
    ref = sync_rounds(make(codec, scheme, fused=False, **kw))
    fus = sync_rounds(make(codec, scheme, **kw))
    rec = {"fused_vs_ref": errs(fus, ref),
           "fused_vs_ref_close": close(fus, ref, ATOL[codec]),
           "gated": fus[1]["active_edges"] < 1.0}
    if with_sharded:
        shd = sync_rounds(make(codec, scheme, sharded=True, **kw))
        rec["sharded_vs_fused"] = errs(shd, fus)
    out["budget"][f"{scheme}_{codec}"] = rec
print("RESULT " + json.dumps(out))
"""

_STALE = _PREAMBLE + r"""
# --- stale: bounded-staleness gating + in-round ledger kick --------------
def arrivals_for(tr, tick):
    deg = len(tr.offsets)
    j = tr.num_nodes
    idx = np.arange(j)
    arr = np.zeros((deg, j), bool)
    for d, off in enumerate(tr.offsets):
        senders = (idx + off) % j
        arr[d] = (senders != 0) | (tick % 3 == 0)
    return jnp.asarray(arr)

def stale_rounds(tr, ticks=3):
    # 3 ticks: sender 0 lands at t0 only, so its edges age 0,1,2 — past
    # the bound at t2, gating + the in-round ledger zero-kick (the codec-
    # dependent halves); revival is format-independent executor logic
    st = tr.init_state(jax.random.PRNGKey(0))
    st, _ = jax.jit(tr.train_step)(st, data.batch(0))
    step = jax.jit(tr.consensus_step_async)
    m = {}
    for t in range(ticks):
        st, m = step(st, probe, arrivals_for(tr, t), None)
    return leaves(tr, st), {k: float(v) for k, v in m.items()}

out["stale"] = {}
acfg = AsyncConfig(max_staleness=1)
sdyn = TopologyConfig(scheduler="stale", max_staleness=1)
stale_grid = [(s, "fp8_e4m3", s == "nap") for s in SCHEMES] \
    + [("nap", "fp8_e5m2", True)]
for scheme, codec, with_sharded in stale_grid:
    kw = dict(topology="complete", dyn=sdyn, async_cfg=acfg)
    ref = stale_rounds(make(codec, scheme, fused=False, **kw))
    fus = stale_rounds(make(codec, scheme, **kw))
    rec = {"fused_vs_ref": errs(fus, ref),
           "fused_vs_ref_close": close(fus, ref, ATOL[codec]),
           "gating_seen": max(fus[1]["stale_edges"],
                              ref[1]["stale_edges"]) > 0}
    if with_sharded:
        shd = stale_rounds(make(codec, scheme, sharded=True, **kw))
        rec["sharded_vs_fused"] = errs(shd, fus)
    out["stale"][f"{scheme}_{codec}"] = rec
print("RESULT " + json.dumps(out))
"""


def _run(script):
    return run_result(script, timeout=1800)


@pytest.fixture(scope="module")
def static_results():
    return _run(_STATIC)


# budget and stale sweeps run as separate scripts so two workers can run
# them at once
@pytest.fixture(scope="module")
def budget_results():
    return _run(_BUDGET)


@pytest.fixture(scope="module")
def stale_results():
    return _run(_STALE)


def test_static_fp8_fused_matches_reference_all_schemes(static_results):
    """All 6 schemes x both fp8 codecs: a static sync round through the
    fused engine == the jnp reference. The raw f32 flat state (lam, bar,
    eta) pins at f32 round-off — both paths decode the same fp8 wire
    bytes, so no quantization term enters the bound; the bf16-STORED
    params get one storage-ulp of slack (a ~1e-8 f32 difference on a
    bf16 rounding boundary flips the stored bit)."""
    cases = static_results["static"]
    assert len(cases) == 12, sorted(cases)
    bad = {k: v for k, v in cases.items()
           if v["fused_vs_ref"]["flat_err"] > 1e-5
           or v["fused_vs_ref"]["param_err"] > 4e-3      # one bf16 ulp
           or v["fused_vs_ref"]["metric_rel_err"] > 1e-5}
    assert not bad, bad


def test_static_fp8_sharded_matches_unsharded_all_schemes(static_results):
    """Sharded == unsharded at f32 exactness on the fp8 wire: per-block
    scales are slab-local, so the slab engine consumes byte-identical
    payloads (metrics go through the residual psum => looser bound)."""
    cases = static_results["static"]
    bad = {k: v for k, v in cases.items()
           if v["sharded_vs_fused"]["flat_err"] > 1e-5
           or v["sharded_vs_fused"]["param_err"] > 1e-5
           or v["sharded_vs_fused"]["metric_rel_err"] > 5e-4}
    assert not bad, bad


def test_fp8_roofline_wire_bytes_shrink(static_results):
    """Acceptance pin: the dryrun roofline reads fp8 wire volume from the
    codec — exactly 1 B/param + 4 B per block of per-block f32 scale, and
    strictly smaller than the native wire."""
    for codec, rec in static_results["wire"].items():
        assert rec["roofline_row_bytes"] == rec["expected_row_bytes"], rec
        assert rec["trainer_row_bytes"] == rec["expected_row_bytes"], rec
        assert rec["roofline_row_bytes"] < rec["native_row_bytes"], rec


def test_budget_gated_fp8_fused_matches_reference(budget_results):
    """Forced-exhaustion budget gating (gate + parked-kick absorption)
    through the fp8 wire: fused == reference at wire precision, sharded ==
    unsharded at f32 exactness, and gating actually fired."""
    cases = budget_results["budget"]
    assert len(cases) == 3, sorted(cases)
    for k, v in cases.items():
        assert v["gated"], (k, v)
        assert v["fused_vs_ref_close"], (k, v)
        assert v["fused_vs_ref"]["metric_rel_err"] < 1e-2, (k, v)
        if "sharded_vs_fused" in v:
            assert v["sharded_vs_fused"]["max_err"] <= 1e-5, (k, v)


def test_stale_fp8_fused_matches_reference(stale_results):
    """Bounded-staleness rounds (ledger fallback, staleness gating,
    in-round zero-kick) through the fp8 wire: fused == reference at the
    documented wire precision for all 6 schemes."""
    cases = stale_results["stale"]
    assert len(cases) == 7, sorted(cases)
    for k, v in cases.items():
        assert v["gating_seen"], (k, v)
        assert v["fused_vs_ref_close"], (k, v)
        assert v["fused_vs_ref"]["metric_rel_err"] < 1e-2, (k, v)


def test_stale_fp8_sharded_matches_unsharded(stale_results):
    """The sharded stale round (per-shard fp8 ledger rows, slab-local
    scale decode) == the unsharded fused round at f32 exactness."""
    cases = {k: v for k, v in stale_results["stale"].items()
             if "sharded_vs_fused" in v}
    assert len(cases) == 2, sorted(stale_results["stale"])
    for k, v in cases.items():
        assert v["sharded_vs_fused"]["max_err"] <= 1e-5, (k, v)
