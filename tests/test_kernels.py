"""Per-kernel allclose tests: shape/dtype sweeps vs the pure-jnp oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as fa_raw
from repro.kernels.rwkv6_scan import rwkv6_scan as rw_raw

from proptest import sweep


# ------------------------------------------------------------ flash attn ----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,h,kh,s,hd,causal,window,bq,bk",
    [
        (1, 2, 2, 128, 32, True, 0, 64, 64),
        (2, 4, 2, 256, 64, True, 0, 128, 128),
        (1, 4, 1, 256, 32, True, 64, 64, 64),
        (1, 2, 2, 128, 32, False, 0, 32, 64),
        (1, 8, 2, 128, 128, True, 0, 128, 64),
    ])
def test_flash_attention_matches_ref(b, h, kh, s, hd, causal, window, bq, bk,
                                     dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, kh, s, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, kh, s, hd)), dtype)
    out = fa_raw(q, k, v, causal=causal, window=window, block_q=bq,
                 block_k=bk, interpret=ops.interpret_mode())
    n_rep = h // kh
    kr, vr = jnp.repeat(k, n_rep, 1), jnp.repeat(v, n_rep, 1)
    expect = ref.flash_attention_ref(q, kr, vr, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


def test_flash_attention_property_sweep():
    def prop(rng, i):
        b = int(rng.integers(1, 3))
        kh = int(rng.choice([1, 2, 4]))
        h = kh * int(rng.choice([1, 2]))
        s = int(rng.choice([64, 128, 192]))
        hd = int(rng.choice([16, 32, 64]))
        q = jnp.asarray(rng.normal(size=(b, h, s, hd)).astype(np.float32))
        k = jnp.asarray(rng.normal(size=(b, kh, s, hd)).astype(np.float32))
        v = jnp.asarray(rng.normal(size=(b, kh, s, hd)).astype(np.float32))
        out = fa_raw(q, k, v, causal=True, block_q=64, block_k=64,
                     interpret=ops.interpret_mode())
        n_rep = h // kh
        expect = ref.flash_attention_ref(q, jnp.repeat(k, n_rep, 1),
                                         jnp.repeat(v, n_rep, 1), causal=True)
        assert float(jnp.max(jnp.abs(out - expect))) < 2e-5
    sweep(prop, cases=6, seed=11)


def test_flash_model_layout_wrapper_matches_model_ref():
    from repro.models.attention import flash_ref as model_ref
    rng = np.random.default_rng(3)
    b, s, h, hd = 2, 128, 4, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    out = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    expect = model_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


# ----------------------------------------------------------------- rwkv -----
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,t,hd,chunk", [
    (1, 2, 64, 16, 16), (2, 3, 128, 32, 32), (1, 1, 96, 8, 32),
    (1, 4, 256, 64, 64),
])
def test_rwkv6_matches_ref(b, h, t, hd, chunk, dtype):
    rng = np.random.default_rng(1)
    r = jnp.asarray(rng.normal(size=(b, h, t, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, h, t, hd)) * 0.5, dtype)
    v = jnp.asarray(rng.normal(size=(b, h, t, hd)), dtype)
    lw = jnp.asarray(-np.exp(rng.normal(size=(b, h, t, hd)) * 0.5),
                     jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, hd)) * 0.1, jnp.float32)
    s0 = jnp.asarray(rng.normal(size=(b, h, hd, hd)) * 0.1, jnp.float32)
    y, sf = rw_raw(r, k, v, lw, u, s0, chunk=chunk,
                   interpret=ops.interpret_mode())
    yr, sr = ref.rwkv6_scan_ref(r, k, v, lw, u, s0)
    scale = float(np.abs(np.asarray(yr, np.float32)).max()) + 1e-6
    rtol = 3e-5 if dtype == jnp.float32 else 8e-3   # bf16: ~3 digits
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=rtol * scale)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               atol=max(rtol * scale, 1e-3))


def test_rwkv6_chunk_invariance():
    """Chunk size is a tiling knob — results must not depend on it."""
    rng = np.random.default_rng(5)
    b, h, t, hd = 1, 2, 128, 16
    r = jnp.asarray(rng.normal(size=(b, h, t, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, h, t, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, h, t, hd)).astype(np.float32))
    lw = jnp.asarray(-np.exp(rng.normal(size=(b, h, t, hd)) * 0.3)
                     .astype(np.float32))
    u = jnp.zeros((h, hd), jnp.float32)
    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    y16, s16 = rw_raw(r, k, v, lw, u, s0, chunk=16,
                      interpret=ops.interpret_mode())
    y64, s64 = rw_raw(r, k, v, lw, u, s0, chunk=64,
                      interpret=ops.interpret_mode())
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y64), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s64), atol=2e-4)


def test_rwkv6_model_integration_kernel_vs_scan():
    """time_mix(use_kernel=True) must equal the lax.scan reference path."""
    from repro.configs import get_reduced_config
    from repro.models import rwkv6 as rl
    from repro.models.params import materialize
    cfg = get_reduced_config("rwkv6-7b")
    p = materialize(jax.random.PRNGKey(0), rl.rwkv_defs(cfg, jnp.float32))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    y_ref, s_ref_, _ = rl.time_mix(cfg, p, x, None, use_kernel=False)
    y_ker, s_ker, _ = rl.time_mix(cfg, p, x, None, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_ker),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(s_ref_), np.asarray(s_ker),
                               atol=2e-3)


# ------------------------------------------------------------- consensus ----
@pytest.mark.parametrize("n,bs", [(1024, 256), (4096, 4096), (65536, 16384)])
def test_consensus_update_matches_ref(n, bs):
    rng = np.random.default_rng(2)
    args = [jnp.asarray(rng.normal(size=n).astype(np.float32))
            for _ in range(5)]
    kw = dict(eta_sum=3.0, eta_node=2.0, step_size=0.01)
    t1, l1, r1, s1 = ops.consensus_update(*args, block_size=bs, **kw)
    t2, l2, r2, s2 = ref.consensus_update_ref(*args, **kw)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
    assert abs(float(r1 - r2)) / (float(r2) + 1e-9) < 1e-5
    assert abs(float(s1 - s2)) / (float(s2) + 1e-9) < 1e-5


def test_consensus_update_property_sweep():
    def prop(rng, i):
        n = int(rng.choice([256, 512, 2048]))
        args = [jnp.asarray(rng.normal(size=n).astype(np.float32))
                for _ in range(5)]
        kw = dict(eta_sum=float(rng.uniform(0.1, 20)),
                  eta_node=float(rng.uniform(0.1, 20)),
                  step_size=float(rng.uniform(1e-4, 0.1)))
        t1, l1, r1, s1 = ops.consensus_update(*args, block_size=n, **kw)
        t2, l2, r2, s2 = ref.consensus_update_ref(*args, **kw)
        assert float(jnp.max(jnp.abs(t1 - t2))) < 1e-4
        assert float(jnp.max(jnp.abs(l1 - l2))) < 1e-4
    sweep(prop, cases=8, seed=13)


@pytest.mark.parametrize("n,bs", [(1000, 256), (37, 64), (513, 128),
                                  (65537, 65536)])
def test_consensus_update_non_block_multiple(n, bs):
    """Regression: odd N must zero-pad internally, not assert (and the
    padded residual reductions must equal the unpadded oracle's)."""
    rng = np.random.default_rng(7)
    args = [jnp.asarray(rng.normal(size=n).astype(np.float32))
            for _ in range(5)]
    kw = dict(eta_sum=1.7, eta_node=0.9, step_size=0.05)
    t1, l1, r1, s1 = ops.consensus_update(*args, block_size=bs, **kw)
    t2, l2, r2, s2 = ref.consensus_update_ref(*args, **kw)
    assert t1.shape == (n,) and l1.shape == (n,)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
    assert abs(float(r1 - r2)) / (float(r2) + 1e-9) < 1e-5
    assert abs(float(s1 - s2)) / (float(s2) + 1e-9) < 1e-5


# ---------------------------------------------------- fused round kernel ----
def _round_case(rng, *, j, deg, nleaves, bs):
    sizes = [int(rng.integers(1, 4 * bs)) for _ in range(nleaves)]
    padded = [-(-s // bs) * bs for s in sizes]
    total = sum(padded)
    block_leaf, pieces = [], []
    for li, (s, p) in enumerate(zip(sizes, padded)):
        block_leaf += [li] * (p // bs)
        seg = np.zeros((j, p), np.float32)
        seg[:, :s] = rng.normal(size=(j, s))
        pieces.append(seg)
    theta = jnp.asarray(np.concatenate(pieces, axis=1))
    lam = jnp.asarray(rng.normal(size=(j, total)).astype(np.float32))
    barp = jnp.asarray(rng.normal(size=(j, total)).astype(np.float32))
    wires = jnp.asarray(
        rng.integers(-127, 128, size=(deg, j, total)).astype(np.int8))
    scales = jnp.asarray(
        rng.uniform(1e-3, 0.1, size=(deg, j, nleaves)).astype(np.float32))
    e_sym = jnp.asarray(
        rng.uniform(0.1, 3.0, size=(deg, j)).astype(np.float32))
    eta_sum = e_sym.sum(axis=0)
    alpha = 0.5 / (1.0 + 2.0 * eta_sum)
    eta_node = eta_sum / deg
    return (theta, lam, barp, wires, scales, e_sym, alpha, eta_sum,
            eta_node, tuple(block_leaf))


@pytest.mark.parametrize("whole_rows", [True, False])
@pytest.mark.parametrize("j,deg,nleaves,bs", [
    (2, 1, 3, 128), (4, 2, 5, 64), (3, 3, 1, 256),
])
def test_consensus_round_matches_ref(j, deg, nleaves, bs, whole_rows):
    """Both tilings — TPU-blocked grid and interpreter whole-row — vs ref."""
    rng = np.random.default_rng(11)
    (theta, lam, barp, wires, scales, e_sym, alpha, eta_sum, eta_node,
     block_leaf) = _round_case(rng, j=j, deg=deg, nleaves=nleaves, bs=bs)
    out_k = ops.consensus_round(theta, lam, barp, wires, scales, e_sym,
                                alpha, eta_sum, eta_node,
                                block_leaf=block_leaf, block_size=bs,
                                whole_rows=whole_rows)
    out_r = ref.consensus_round_ref(theta, lam, barp, wires, scales, e_sym,
                                    alpha, eta_sum, eta_node,
                                    block_leaf=block_leaf, block_size=bs)
    for a, b, name in zip(out_k, out_r,
                          ("theta", "lam", "bar", "r_sq", "s_sq")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_consensus_round_float_wire_property_sweep():
    """Uncompressed (f32 wire, unit scales) fused round == oracle."""
    def prop(rng, i):
        j = int(rng.integers(2, 5))
        deg = int(rng.integers(1, min(j, 3) + 1))
        bs = int(rng.choice([64, 128]))
        nleaves = int(rng.integers(1, 4))
        (theta, lam, barp, _, _, e_sym, alpha, eta_sum, eta_node,
         block_leaf) = _round_case(rng, j=j, deg=deg, nleaves=nleaves, bs=bs)
        total = theta.shape[1]
        wires = jnp.asarray(
            rng.normal(size=(deg, j, total)).astype(np.float32))
        scales = jnp.ones((deg, j, nleaves), jnp.float32)
        out_k = ops.consensus_round(theta, lam, barp, wires, scales, e_sym,
                                    alpha, eta_sum, eta_node,
                                    block_leaf=block_leaf, block_size=bs)
        out_r = ref.consensus_round_ref(theta, lam, barp, wires, scales,
                                        e_sym, alpha, eta_sum, eta_node,
                                        block_leaf=block_leaf, block_size=bs)
        for a, b in zip(out_k, out_r):
            scale = 1.0 + float(jnp.max(jnp.abs(b)))
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * scale
    sweep(prop, cases=6, seed=23)
