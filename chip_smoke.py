#!/usr/bin/env python3
"""Smoke run of the consensus trainer on a TPU: the quickest proof it starts.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # a four-chip host: the J = 4 ring only

One chip runs two phases, (b) first:
  (a) the fused consensus round, compiled by Mosaic, at the flat width of
      qwen3-4b cut to 4 layers and an 18,944-row vocabulary (~452 M
      elements per node row), against ``kernels/ref.py`` for native and
      int8 wires;
  (b) ``repro.launch.train.main`` at the published qwen3-4b widths with
      the same depth and vocabulary cut, on ``--mesh local`` (J = 1).
``--chips 4`` runs only the ring: (b) at J = 4 (one node per chip, two
consensus rounds), the node placement, and one fused round against the
unfused reference round on the same state.

Every number printed names the device it came from. The last line of
standard output is ``{"ok": true, "device": {...}}``. Any failure, and a
run where JAX finds no TPU, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

# the cut of qwen3-4b both phases use: depth and vocabulary only
ONE_CHIP_LAYERS = 4
# four chips: the round's bf16 wire rows [deg, 1, total] pad 2x in the TPU
# layout, and at 4 layers the compiled round needs 16.27 GB of 15.75 GB
FOUR_CHIP_LAYERS = 3
VOCAB = 18944
SEQ = 2048
BATCH = 2           # per node; 2 leaves ~2 GB of HBM headroom at 4 layers
PUBLISHED = {"d_model": 2560, "n_heads": 32, "n_kv_heads": 8,
             "head_dim": 128, "d_ff": 9728}

# Tolerances of the Mosaic kernel against the f32 oracle, which runs on the
# host CPU. Both do the same f32 arithmetic; only the division and the
# order of fused multiply-adds may differ, by a few f32 ulps (2^-24) of the
# largest value. A bf16 accumulation would be off by ~2^-8 and fails both.
F32_REL = 2.0 ** -16     # f32 outputs (lam, bar): max |diff| / max |ref|
SUM_REL = 1e-4           # r_sq, s_sq: f32 sums of 452 M terms
# theta_new is stored in bf16: a few-ulp f32 difference may round to the
# neighbouring bf16 value, one bf16 ulp (2^-7 relative at most) away; where
# the update cancels to near zero, the f32 difference itself shows.
BF16_ULP = 2.0 ** -7


def bf16_close(k, r):
    """Elementwise: within one bf16 ulp, plus F32_REL of the row's scale."""
    import numpy as np
    k, r = np.asarray(k, np.float32), np.asarray(r, np.float32)
    d = np.abs(k - r)
    ok = d <= BF16_ULP * np.abs(r) + F32_REL * float(np.abs(r).max())
    return bool(ok.all()), float(d.max())


class Tee:
    """Copy what is written to stdout into a list of lines as well."""

    def __init__(self, out):
        self.out, self.buf = out, []

    def write(self, s):
        self.out.write(s)
        self.buf.append(s)

    def flush(self):
        self.out.flush()

    def lines(self):
        return "".join(self.buf).splitlines()


def say(tag, msg):
    print(f"[{tag}] {msg}", flush=True)


def main_argv(layers, seed, steps):
    # lr 3e-4: the launcher's default 1e-2 suits the reduced CPU model and
    # makes AdamW diverge at published widths
    return ["--arch", "qwen3-4b", "--mesh", "local", "--n-layers",
            str(layers), "--vocab", str(VOCAB), "--seq", str(SEQ),
            "--local-steps", "2", "--scheme", "nap", "--steps", str(steps),
            "--batch-per-node", str(BATCH), "--lr", "3e-4",
            "--seed", str(seed)]


# ------------------------------------------------------------ phase (a) ----
def kernel_at_width(tag, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.launch import train
    from repro.models import build_model
    from repro.optim import flatten
    from repro import wire

    assert not ops.interpret_mode(), "interpret mode on a TPU"
    cfg, _ = train.arch_config(train.parse_args(
        main_argv(ONE_CHIP_LAYERS, seed, 1)))
    ap = build_model(cfg).abstract_params()
    lay = flatten.FlatLayout.for_tree(ap, block_size=flatten.auto_block_size(
        ap), node_axis=False)
    total, bs, deg = lay.total, lay.block_size, 2
    say(tag, f"(a) flat row {total} elements, block {bs}, "
             f"{lay.num_blocks} blocks, {lay.num_leaves} leaves")
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def normal(k, scale, dtype):
        return (scale * jax.random.normal(k, (1, total), jnp.float32)
                ).astype(dtype)

    # per-node scalars as host arrays: both the kernel and the oracle on
    # the CPU read them
    alpha = np.full((1,), 0.05, np.float32)
    eta_sum = np.full((1,), 2.0, np.float32)
    eta_node = np.full((1,), 1.5, np.float32)
    e_sym = np.full((deg, 1), 1.0, np.float32)
    cpu = jax.devices("cpu")[0]
    for codec_name in ("native", "int8"):
        codec = wire.get_codec(codec_name, lay)
        rows, scale_rows = [], []
        for d in range(deg):
            payload, sc = codec.decode(codec.encode(
                normal(keys[3 + d], 1.0, lay.wire_dtype)))
            rows.append(payload)
            scale_rows.append(jnp.ones((1, lay.num_leaves), jnp.float32)
                              if sc is None else sc)
        wires = jnp.stack(rows)
        scales = jnp.stack(scale_rows)
        del rows, payload
        theta = normal(keys[0], 1.0, lay.wire_dtype)
        lam = normal(keys[1], 0.1, jnp.float32)
        barp = normal(keys[2], 1.0, jnp.float32)
        host_in = jax.device_get((theta, lam, barp, wires, scales))

        def fused(theta, lam, barp, wires, scales):
            return ops.consensus_round(
                theta, lam, barp, wires, scales, e_sym, alpha, eta_sum,
                eta_node, block_leaf=tuple(lay.block_leaf.tolist()),
                block_size=bs)

        t0 = time.time()
        compiled = jax.jit(fused, donate_argnums=(0, 1, 2)).lower(
            theta, lam, barp, wires, scales).compile()
        t_compile = time.time() - t0
        assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
        jax.block_until_ready(compiled(theta, lam, barp, wires, scales))
        theta, lam, barp = jax.block_until_ready(     # donated above
            jax.device_put(host_in[:3]))
        t0 = time.time()
        out = compiled(theta, lam, barp, wires, scales)
        jax.block_until_ready(out)
        t_run = time.time() - t0
        del wires, scales
        k_theta, k_lam, k_bar, k_rsq, k_ssq = jax.device_get(out)
        del out

        # the oracle, on the host CPU, in chunks of whole blocks
        h_theta, h_lam, h_barp, h_wires, h_scales = host_in
        err = {"theta": 0.0, "lam": 0.0, "bar": 0.0}
        r_sq = s_sq = 0.0
        chunk = 512 * bs
        with jax.default_matmul_precision("highest"), \
                jax.default_device(cpu):
            for lo in range(0, total, chunk):
                hi = min(lo + chunk, total)
                sl = slice(lo, hi)
                r_theta, r_lam, r_bar, r_r, r_s = jax.device_get(
                    ref.consensus_round_ref(
                        h_theta[:, sl], h_lam[:, sl], h_barp[:, sl],
                        h_wires[:, :, sl], h_scales, e_sym, alpha,
                        eta_sum, eta_node, block_size=bs,
                        block_leaf=tuple(
                            lay.block_leaf[lo // bs:hi // bs].tolist())))
                r_sq += float(r_r[0])
                s_sq += float(r_s[0])
                ok, d_theta = bf16_close(k_theta[:, sl], r_theta)
                assert ok, f"{codec_name}: theta_new off by over a bf16 ulp"
                err["theta"] = max(err["theta"], d_theta)
                for name, k, r in (("lam", k_lam[:, sl], r_lam),
                                   ("bar", k_bar[:, sl], r_bar)):
                    rel = float(np.abs(k - r).max()) / max(
                        float(np.abs(r).max()), 1e-30)
                    err[name] = max(err[name], rel)
        for name in ("lam", "bar"):
            assert err[name] <= F32_REL, (codec_name, name, err[name])
        rel_r = abs(float(k_rsq[0]) - r_sq) / r_sq
        rel_s = abs(float(k_ssq[0]) - s_sq) / s_sq
        assert rel_r <= SUM_REL and rel_s <= SUM_REL, (rel_r, rel_s)
        say(tag, f"(a) {codec_name} wire: compiled in {t_compile:.2f}s, "
                 f"round {t_run * 1e3:.3f} ms (host clock, one call); vs "
                 f"f32 oracle: theta max|d| {err['theta']:.3g}, lam rel "
                 f"{err['lam']:.3g}, bar rel {err['bar']:.3g}, r_sq rel "
                 f"{rel_r:.3g}, s_sq rel {rel_s:.3g}")
        del k_theta, k_lam, k_bar, host_in


# ------------------------------------------------------------ phase (b) ----
def check_fit(tag, args):
    """Compile the steps main will run, print their memory and fit."""
    import jax
    import jax.numpy as jnp
    from repro.launch import train

    cfg, tr = train.build_trainer(args)
    for k, v in PUBLISHED.items():
        assert getattr(cfg, k) == v, (k, getattr(cfg, k), v)
    sh, ab = tr.state_shardings(), tr.abstract_state()
    st = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        ab, sh)
    batch = {k: jax.ShapeDtypeStruct(
        (tr.num_nodes, args.batch_per_node, args.seq), jnp.int32)
        for k in ("tokens", "labels")}
    flat = 2 * tr.layout.total * 4                    # lam + theta_bar_prev
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    say(tag, f"(b) {cfg.arch_id} {tr.model.param_count()} params, J = "
             f"{tr.num_nodes}, HBM limit {limit} B per device")
    t0 = time.time()
    local = jax.jit(tr.train_step).lower(
        st._replace(lam=None, theta_bar_prev=None, ledger=None),
        batch).compile()
    ma = local.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes + flat)
    say(tag, f"(b) local step compiled in {time.time() - t0:.1f}s: "
             f"args {ma.argument_size_in_bytes} out "
             f"{ma.output_size_in_bytes} temp {ma.temp_size_in_bytes} "
             f"+ flat buffers {flat} = {need} B")
    assert need < limit, f"local step needs {need} B of {limit}"
    if tr.num_nodes > 1:
        t0 = time.time()
        _, cons = tr.jit_step_fns()
        ma = cons.lower(st, batch).compile().memory_analysis()
        say(tag, f"(b) consensus round compiled in {time.time() - t0:.1f}s:"
                 f" args {ma.argument_size_in_bytes} (donated) temp "
                 f"{ma.temp_size_in_bytes}")
    return tr


def run_main(tag, argv, rounds_wanted):
    """train.main in this process; check every step and round it prints."""
    from repro.launch import train

    tee = Tee(sys.stdout)
    t0 = time.time()
    with contextlib.redirect_stdout(tee):
        rc = train.main(argv)
    wall = time.time() - t0
    assert rc == 0, rc
    lines = tee.lines()
    retries = [ln for ln in lines if ln.startswith("retry ")]
    assert not retries, f"local step retried: {retries}"
    cuts = [ln for ln in lines if ln.startswith("cut: ")]
    assert len(cuts) == 2, cuts
    steps, rounds = [], []
    for ln in lines:
        m = re.match(r"step\s+(\d+) loss (\S+) (\d+)ms", ln)
        if m:
            steps.append((int(m[1]), float(m[2]), int(m[3])))
            c = re.search(r"consensus r=(\S+) eta=(\S+)", ln)
            if c:
                rounds.append((float(c[1]), float(c[2])))
    assert steps, "no step lines"
    for i, (step, loss, ms) in enumerate(steps):
        assert math.isfinite(loss), f"step {step}: loss {loss}"
        say(tag, f"(b) step {step}: loss {loss:.4f}, {ms} ms (host clock"
                 f"{', compile included' if i == 0 else ''})")
    assert len(rounds) >= rounds_wanted, rounds
    for r, eta in rounds:
        assert math.isfinite(r) and math.isfinite(eta), (r, eta)
    if rounds:
        say(tag, f"(b) {len(rounds)} consensus rounds, r_max "
                 f"{[r for r, _ in rounds]}, eta {[e for _, e in rounds]}")
    say(tag, f"(b) train.main wall {wall:.1f}s; {' | '.join(cuts)}")


def peak_memory(tag):
    import jax
    for d in jax.devices():
        say(tag, f"device {d.id} peak_bytes_in_use "
                 f"{d.memory_stats()['peak_bytes_in_use']}")


# --------------------------------------------------------- four chips ------
def check_placement(tag, tr, seed):
    """Each node's replica on its own device, not all on device 0."""
    import jax
    state = tr.init_state(jax.random.PRNGKey(seed))
    for name, leaf in (("params", jax.tree_util.tree_leaves(state.params)[0]),
                       ("lam", state.lam)):
        owner = {}
        for sh in leaf.addressable_shards:
            node = sh.index[0]
            assert node.stop - node.start == 1, (name, sh.index)
            owner[node.start] = sh.device.id
        assert sorted(owner) == list(range(tr.num_nodes)), (name, owner)
        assert len(set(owner.values())) == tr.num_nodes, (name, owner)
        say(tag, f"(ring) {name}: node -> device {owner}")
    del state


def fused_vs_unfused(tag, seed):
    """One round through the fused kernel and through the jnp reference,
    from the same state, on the four-chip ring (reduced qwen3-4b: the
    reference round's [deg, J, total] f32 intermediates do not fit the
    chip at published widths)."""
    import jax
    import numpy as np
    from repro.data import DataConfig, SyntheticTokens
    from repro.launch import train
    from repro.optim import ConsensusTrainer

    args = train.parse_args(["--arch", "qwen3-4b", "--reduced", "--mesh",
                             "local", "--local-steps", "1",
                             "--seed", str(seed)])
    cfg, tr_f = train.build_trainer(args)
    tr_u = ConsensusTrainer(tr_f.model, tr_f.mesh, adamw=tr_f.acfg,
                            consensus=dataclasses.replace(
                                tr_f.ccfg, use_fused_kernel=False))
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      batch_per_node=2,
                                      num_nodes=tr_f.num_nodes, seed=seed))
    state = tr_f.init_state(jax.random.PRNGKey(seed))
    local = jax.jit(tr_f.train_step)
    for step in range(2):                 # let the node replicas diverge
        state, _ = local(state, data.batch(step))
    host = jax.device_get(state)
    probe = data.batch(0, probe=True)
    outs = []
    for tr in (tr_f, tr_u):
        st = jax.device_put(host, tr.state_shardings())
        _, cons = tr.jit_step_fns()
        st, m = cons(st, probe)
        outs.append(jax.device_get((st, m)))
    (sf, mf), (su, mu) = outs
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(sf.params),
                    jax.tree_util.tree_leaves(su.params)):
        ok, d = bf16_close(a, b)
        assert ok, "params differ by more than one bf16 ulp"
        worst = max(worst, d)
    # f32 round-off follows the largest value in the round: the neighbour
    # mean is on the parameters' scale, the duals far below it
    scale = max(float(np.abs(su.theta_bar_prev).max()),
                float(np.abs(su.lam).max()))
    rel = {}
    for name in ("lam", "theta_bar_prev"):
        a, b = getattr(sf, name), getattr(su, name)
        rel[name] = float(np.abs(a - b).max()) / scale
        assert rel[name] <= F32_REL, (name, rel[name])
    for k in ("r_max", "eta_mean"):
        d = abs(float(mf[k]) - float(mu[k])) / (abs(float(mu[k])) + 1e-30)
        assert d <= SUM_REL, (k, float(mf[k]), float(mu[k]))
    say(tag, f"(ring) fused vs unfused round, J = {tr_f.num_nodes}, "
             f"{tr_f.layout.total} elements/node: params max|d| {worst:.3g},"
             f" lam rel {rel['lam']:.3g}, theta_bar_prev rel "
             f"{rel['theta_bar_prev']:.3g}, r_max {float(mf['r_max']):.6g} "
             f"vs {float(mu['r_max']):.6g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    import jax
    from repro.kernels import ops
    from repro.launch import train

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {len(devs)} "
              f"{devs[0].platform} device(s))", file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              f"TPU devices", file=sys.stderr)
        return 2
    assert not ops.interpret_mode()
    tag = f"{devs[0].platform} {devs[0].device_kind} x{len(devs)}"
    t_all = time.time()
    if args.chips == 1:
        # (b) first, so the first peak printed is the main path's own
        argv_b = main_argv(ONE_CHIP_LAYERS, args.seed, steps=4)
        check_fit(tag, train.parse_args(argv_b))
        run_main(tag, argv_b, rounds_wanted=0)
        peak_memory(tag)
        kernel_at_width(tag, args.seed)
        peak_memory(tag)
    else:
        argv_b = main_argv(FOUR_CHIP_LAYERS, args.seed, steps=4)
        tr = check_fit(tag, train.parse_args(argv_b))
        check_placement(tag, tr, args.seed)
        del tr
        run_main(tag, argv_b, rounds_wanted=2)
        peak_memory(tag)
        fused_vs_unfused(tag, args.seed)
    say(tag, f"all phases passed in {time.time() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
