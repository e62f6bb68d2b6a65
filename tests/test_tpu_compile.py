"""Compiles for a TPU v5e that is described, not attached.

Every chip compile of the test suite lives in this one file: the blocked
fused consensus round at the smoke run's real width (qwen3-4b at published
widths, cut to 4 layers and an 18,944-row vocabulary: 452,526,080 elements
per node row), in the three variants the trainer selects. Nothing runs;
the TPU compiler refuses here what it would refuse on the chip (tiling,
SMEM, VMEM), and the compiled text must hold the Mosaic kernel. The
reduced ring's local step and round, compiled for the four chips of a
v5e:2x2, keep every op of the program under one named phase.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture (never at import) and every compile
happens in the test's own process.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import consensus_update as cu
from repro.models import build_model
from repro.optim import flatten

DEG = 2          # ring: offsets +1 and -1
J = 1            # one node row per device, as under the trainer's shard_map


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def layout():
    cfg = dataclasses.replace(get_config("qwen3-4b"), n_layers=4,
                              vocab=18944)
    ap = build_model(cfg).abstract_params()
    lay = flatten.FlatLayout.for_tree(
        ap, block_size=flatten.auto_block_size(ap), node_axis=False)
    assert lay.total == 452_526_080 and lay.block_size == 65_536
    return lay


def _compile(one_chip, lay, wire_dtype, *, masked=False, kick=False,
             per_block=False):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    total = lay.total
    width = lay.num_blocks if per_block else lay.num_leaves
    args = [sds((J, total), lay.wire_dtype), sds((J, total), jnp.float32),
            sds((J, total), jnp.float32), sds((DEG, J, total), wire_dtype),
            sds((DEG, J, width), jnp.float32), sds((DEG, J), jnp.float32),
            sds((J,), jnp.float32), sds((J,), jnp.float32),
            sds((J,), jnp.float32)]
    kw = {}
    if masked:
        kw = {"bar_w": sds((DEG, J), jnp.float32),
              "inv_deg": sds((J,), jnp.float32)}
    if kick:
        kw["kick_w"] = sds((DEG, J), jnp.float32)

    def fused(*a, **k):
        return cu.consensus_round(
            *a, block_leaf=tuple(lay.block_leaf.tolist()),
            block_size=lay.block_size, interpret=False,
            scales_per_block=per_block, **k)

    return jax.jit(fused).lower(*args, **kw).compile().as_text()


def test_fused_round_compiles_plain_native_wire(one_chip, layout):
    assert "tpu_custom_call" in _compile(one_chip, layout, layout.wire_dtype)


def test_fused_round_compiles_masked_kick_int8_wire(one_chip, layout):
    text = _compile(one_chip, layout, jnp.int8, masked=True, kick=True)
    assert "tpu_custom_call" in text


def test_fused_round_compiles_per_block_fp8_wire(one_chip, layout):
    # the fp8 codecs' float8 loads lower on v5e (no native fp8 math: the
    # kernel upcasts to f32 before any arithmetic)
    text = _compile(one_chip, layout, jnp.float8_e4m3fn, per_block=True)
    assert "tpu_custom_call" in text


@pytest.fixture(scope="module")
def ring_programs(topo):
    """The reduced qwen3-4b ring's compiled train_step and consensus_step
    for four described chips, one node each."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_reduced_config
    from repro.core.penalty import PenaltyConfig
    from repro.data import DataConfig, SyntheticTokens
    from repro.kernels import ops
    from repro.optim import ConsensusConfig, ConsensusTrainer
    from repro.optim.adamw import AdamWConfig

    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
                ("pod", "data", "model"))
    cfg = get_reduced_config("qwen3-4b")
    tr = ConsensusTrainer(
        build_model(cfg), mesh, adamw=AdamWConfig(lr=1e-2),
        consensus=ConsensusConfig(
            penalty=PenaltyConfig(scheme="nap", eta0=0.1),
            topology="ring", local_steps=2))
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tr.abstract_state(), tr.state_shardings())
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      batch_per_node=2, num_nodes=4))
    pod = NamedSharding(mesh, P("pod"))

    def shapes(batch):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=pod),
            batch)
    was = ops.interpret_mode
    ops.interpret_mode = lambda: False       # the backend here is the CPU
    try:
        return {
            "train_step": jax.jit(tr.train_step).lower(
                state, shapes(data.batch(0))).compile().as_text(),
            "consensus_step": jax.jit(tr.consensus_step).lower(
                state, shapes(data.batch(0, probe=True))).compile().as_text()}
    finally:
        ops.interpret_mode = was


@pytest.mark.parametrize("program,phases", [
    ("train_step", {"local/grad", "local/optimizer"}),
    ("consensus_step", {"consensus/pack", "consensus/exchange",
                        "consensus/probe", "consensus/fused_round",
                        "consensus/penalty"})])
def test_chip_program_ops_lie_under_one_phase(ring_programs, program,
                                              phases):
    from hlo_scopes import audit
    got = audit(ring_programs[program])
    assert got["bad"] == [], got["bad"][:10]
    assert set(got["phases"]) == phases
    if program == "consensus_step":
        assert "tpu_custom_call" in ring_programs[program]
