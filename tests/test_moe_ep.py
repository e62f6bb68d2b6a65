"""MoE expert-parallel path vs dense reference (subprocess: needs 8 devices)."""

import pytest

from script_result import run_result

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import dataclasses as dc
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_reduced_config
from repro.configs.base import MoEConfig
from repro.models import moe as moe_lib
from repro.models.params import materialize
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_reduced_config("moonshot-v1-16b-a3b")
# high capacity factor so the fixed-shape dispatch drops nothing
cfg = dc.replace(cfg, moe=MoEConfig(num_experts=8, top_k=2, expert_d_ff=32,
                                    capacity_factor=8.0))
p = materialize(jax.random.PRNGKey(0), moe_lib.moe_defs(cfg, jnp.float32))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

ref = moe_lib.moe_ref(cfg, p, x)

rules = shd.default_rules(mesh)
out = {}
with shd.use_mesh(mesh, rules):
    ep = jax.jit(lambda p_, x_: moe_lib.moe_apply(cfg, p_, x_))(p, x)
    err = float(jnp.max(jnp.abs(ep - ref)))
    out["a2a_err"] = err
    scale = float(jnp.abs(ref).max())
    out["scale"] = scale
    # decode path (replicated tokens, psum combine)
    dec = jax.jit(lambda p_, x_: moe_lib.moe_apply(cfg, p_, x_,
                                                   decode=True))(p, x)
    out["repl_err"] = float(jnp.max(jnp.abs(dec - ref)))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def ep_results():
    return run_result(_SCRIPT, timeout=900)


def test_a2a_dispatch_matches_reference(ep_results):
    """all_to_all EP (sharded tokens) == dense masked reference."""
    tol = 1e-4 * (1 + ep_results["scale"])
    assert ep_results["a2a_err"] < tol, ep_results


def test_replicated_dispatch_matches_reference(ep_results):
    """decode-path EP (replicated tokens, psum combine) == reference."""
    tol = 1e-4 * (1 + ep_results["scale"])
    assert ep_results["repl_err"] < tol, ep_results
