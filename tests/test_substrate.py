"""Substrate tests: optimizer, data, checkpoint, fault tolerance, compression."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import latest_steps, restore, save, save_async, \
    wait_pending
from repro.core.graph import build_graph
from repro.core.penalty import PenaltyConfig, init_penalty_state
from repro.data import DataConfig, Prefetcher, SyntheticTokens
from repro.optim import adamw as al
from repro.optim import compression as cl
from repro.runtime import (ElasticController, RetryPolicy, StragglerMonitor,
                           shrink_penalty_state, with_retries)


# ---------------------------------------------------------------- adamw -----
def _quad_problem():
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3), "m": jnp.ones((4, 5))}

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2) + jnp.sum(p["m"] ** 2)

    return params, loss, target


@pytest.mark.parametrize("factored", [False, True])
def test_adamw_minimizes(factored):
    cfg = al.AdamWConfig(lr=0.05, weight_decay=0.0, factored=factored)
    params, loss, target = _quad_problem()
    state = al.init(cfg, params)
    for _ in range(300):
        grads = jax.grad(loss)(params)
        params, state, _ = al.update(cfg, state, params, grads)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)
    assert float(jnp.abs(params["m"]).max()) < 0.05


def test_adamw_factored_memory_shapes():
    cfg = al.AdamWConfig(factored=True)
    params = {"mat": jnp.zeros((64, 32)), "vec": jnp.zeros(16)}
    st = al.init(cfg, params)
    vr, vc = st.v["mat"]
    assert vr.shape == (64,) and vc.shape == (32,)
    assert st.v["vec"].shape == (16,)


def test_grad_clip_bounds_update():
    cfg = al.AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    st = al.init(cfg, params)
    huge = {"w": jnp.full(4, 1e6)}
    p2, st, m = al.update(cfg, st, params, huge)
    assert float(m["grad_norm"]) > 1e5
    assert float(jnp.abs(p2["w"]).max()) < 20.0   # clip kept it sane


# ----------------------------------------------------------------- data -----
def test_data_deterministic_and_distinct():
    cfg = DataConfig(vocab=128, seq_len=16, batch_per_node=4, num_nodes=3,
                     seed=7)
    src = SyntheticTokens(cfg)
    b1 = src.batch(5)
    b2 = src.batch(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = src.batch(6)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    # nodes see different data
    t = np.asarray(b1["tokens"])
    assert not np.array_equal(t[0], t[1])
    # probe stream is held out
    p = src.batch(5, probe=True)
    assert not np.array_equal(np.asarray(p["tokens"]), np.asarray(b1["tokens"]))
    # labels are next-token with masked tail
    lbl = np.asarray(b1["labels"])
    np.testing.assert_array_equal(lbl[:, :, :-1], t[:, :, 1:])
    assert np.all(lbl[:, :, -1] == -1)


def test_prefetcher_yields_in_order():
    cfg = DataConfig(vocab=64, seq_len=8, batch_per_node=2, num_nodes=1)
    pf = Prefetcher(SyntheticTokens(cfg), start_step=3, depth=2)
    it = iter(pf)
    steps = [next(it)[0] for _ in range(4)]
    pf.close()
    assert steps == [3, 4, 5, 6]


# ----------------------------------------------------------- checkpoint -----
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    save(str(tmp_path), 10, tree, metadata={"step": 10, "note": "x"})
    restored, meta = restore(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]))
    assert meta["step"] == 10 and meta["note"] == "x"


def test_checkpoint_keep_k_and_latest(tmp_path):
    tree = {"w": jnp.zeros(3)}
    for s in (1, 2, 3, 4):
        save(str(tmp_path), s, tree, keep=2)
    assert latest_steps(str(tmp_path)) == [3, 4]


def test_checkpoint_rejects_wrong_structure(tmp_path):
    save(str(tmp_path), 1, {"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        restore(str(tmp_path), {"a": jnp.zeros(3), "b": jnp.zeros(2)})
    with pytest.raises(ValueError):
        restore(str(tmp_path), {"a": jnp.zeros(4)})


def test_checkpoint_async(tmp_path):
    tree = {"w": jnp.full((8,), 3.0)}
    save_async(str(tmp_path), 5, tree, metadata={"step": 5})
    wait_pending()
    restored, meta = restore(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    """A crash mid-write (tmp dir left behind) must not corrupt restore."""
    tree = {"w": jnp.zeros(3)}
    save(str(tmp_path), 1, tree)
    os.makedirs(str(tmp_path / "tmp.2"))          # simulated dead write
    (tmp_path / "tmp.2" / "junk").write_text("partial")
    assert latest_steps(str(tmp_path)) == [1]
    restore(str(tmp_path), tree)


# ------------------------------------------------------- fault tolerance ----
def test_with_retries_recovers():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    out = with_retries(flaky, RetryPolicy(max_retries=3, backoff_s=0.0),
                       sleep=lambda _: None)()
    assert out == "ok" and calls["n"] == 3


def test_with_retries_exhausts():
    def always_bad():
        raise RuntimeError("permanent")

    with pytest.raises(RuntimeError):
        with_retries(always_bad, RetryPolicy(max_retries=2, backoff_s=0.0),
                     sleep=lambda _: None)()


def test_with_retries_prints_every_retry(capsys):
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    with_retries(flaky, RetryPolicy(max_retries=3, backoff_s=0.0),
                 sleep=lambda _: None)()
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("retry ")]
    assert lines == ["retry 1/3: RuntimeError: transient",
                     "retry 2/3: RuntimeError: transient"]


def test_with_retries_does_not_retry_out_of_memory():
    calls = {"n": 0}

    def oom():
        calls["n"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        with_retries(oom, RetryPolicy(max_retries=3, backoff_s=0.0),
                     sleep=lambda _: None)()
    assert calls["n"] == 1


def test_straggler_monitor_flags_slow_node():
    mon = StragglerMonitor(4, threshold=2.0, patience=2)
    base = np.array([1.0, 1.0, 1.0, 1.0])
    assert mon.observe(base) == []
    slow = np.array([1.0, 1.0, 5.0, 1.0])
    assert mon.observe(slow) == []          # first strike
    assert mon.observe(slow) == [2]         # patience reached


def test_elastic_drop_preserves_adaptation_history():
    g = build_graph("ring", 5)
    pen = init_penalty_state(PenaltyConfig(scheme="nap"), 5)
    pen = pen._replace(eta=pen.eta.at[0, 1].set(42.0))
    ctl = ElasticController(g)
    g2, pen2 = ctl.drop(3, pen, step=100)
    assert g2.num_nodes == 4 and g2.is_connected()
    assert pen2.eta.shape == (4, 4)
    assert float(pen2.eta[0, 1]) == 42.0    # surviving edge kept its eta
    assert ctl.events[0].victim == 3


# ------------------------------------------------------------ compression ---
def test_int8_roundtrip_error_small():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=256).astype(np.float32))
    q, s = cl.compress_int8(x)
    back = cl.decompress_int8(q, s)
    assert float(jnp.abs(back - x).max()) <= float(s) * 0.51


def test_error_feedback_accumulates():
    cfg = cl.CompressionConfig(kind="topk", topk_frac=0.25)
    delta = {"w": jnp.asarray([10.0, 0.1, 0.2, 0.05])}
    err = cl.init_error(delta)
    sent, err, stats = cl.encode(cfg, delta, err)
    # only the top element got through; the rest is carried
    assert float(sent["w"][0]) == 10.0
    assert float(jnp.abs(err["w"][1:]).sum()) > 0
    # carried error is re-applied next round
    delta2 = {"w": jnp.zeros(4)}
    sent2, err2, _ = cl.encode(cfg, delta2, err)
    assert float(jnp.abs(sent2["w"]).sum()) > 0


def test_compression_ratio_reported():
    cfg = cl.CompressionConfig(kind="int8")
    delta = {"w": jnp.ones((128,))}
    _, _, stats = cl.encode(cfg, delta, cl.init_error(delta))
    assert stats["compression_ratio"] > 3.0


def test_checkpoint_bf16_roundtrip(tmp_path):
    """Extended dtypes (bf16) survive the npz round-trip via uint views."""
    import jax.numpy as jnp2
    tree = {"w": jnp2.asarray([1.5, -2.25, 0.007], jnp2.bfloat16),
            "m": jnp2.ones((4,), jnp2.float32)}
    save(str(tmp_path), 2, tree, metadata={"step": 2})
    restored, _ = restore(str(tmp_path), tree)
    assert restored["w"].dtype == jnp2.bfloat16
    np.testing.assert_array_equal(
        np.asarray(restored["w"], np.float32), np.asarray(tree["w"],
                                                          np.float32))
