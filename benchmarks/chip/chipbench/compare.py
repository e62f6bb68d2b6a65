"""The comparison that decides ``correct``.

The program's readings come from the timed path itself: the losses of its
first three steps, the first gradient as AdamW took it (from the first
moment after one step), the parameters' change after three steps (as the
fourth step receives them) and, on the ring, what its first two consensus
rounds left behind: the residuals and the probe mean they report, the
dual and the neighbour mean they store (the mean as its change from the
initial weights), and the penalties the first one set. The first round
starts from a zero dual and mean at eta0; the second, run by the round's
second compiled program, from the first's. The plain reference
(``reference.py``) follows the same steps and rounds from the same seed
on the same rows. Each number compared has its limit in
``limits/<cell>.json``.
"""
from __future__ import annotations

import functools

import numpy as np

from chipbench import reference, weights
from chipbench.traffic import ZipfTokens

STEPS = 3
ROUNDS = 2
ROUND_KEYS = ("r_max", "s_max", "f_mean")
# the name of each round key's relative gap; {} is the round's suffix
ROUND_NAMES = {"r_max": "r{}_max_rel", "s_max": "s{}_max_rel",
               "f_mean": "f{}_mean_rel"}
# the launcher probes a round after step k on its batch 10**6 + k
# (``make_batch(10**6 + step)`` in repro/launch/train.py); the harness
# checks that the program asked for those rows
PROBE_OFFSET = 10 ** 6
# a leaf whose reference gradient is under this share of the median leaf's
# moves under AdamW by round-off alone; it is left out of the gaps
DEAD_LEAF = 1e-3


def probe_step(round_index: int, h: int) -> int:
    """The batch the launcher probes in round ``round_index`` (from 0)."""
    return PROBE_OFFSET + (round_index + 1) * h - 1


def ring_edges(eta: np.ndarray) -> np.ndarray:
    """[J, 2]: each node's penalty on its edge to i+1 and to i-1."""
    i = np.arange(eta.shape[0])
    return np.stack([eta[i, (i + 1) % len(i)], eta[i, (i - 1) % len(i)]], 1)


def program_readings(run) -> dict:
    out = {"loss": [float(x) for x in run.losses[:STEPS]],
           "grad": np.asarray(run.readings["grad"], np.float64),
           "update": np.asarray(run.readings["update"], np.float64)}
    if run.nodes > 1:
        want = [probe_step(k, run.h) for k in range(ROUNDS)]
        if run.probe_steps[:ROUNDS] != want:
            raise RuntimeError(f"the program probed rows {run.probe_steps}, "
                               f"the reference probes {want}")
        out["rounds"] = []
        for k, rd in enumerate(run.rounds[:ROUNDS]):
            got = {key: float(rd[key]) for key in ROUND_KEYS}
            got["lam"] = np.asarray(rd["lam"], np.float64)
            got["bar"] = np.asarray(rd["bar"], np.float64)
            if k == 0:
                got["eta"] = ring_edges(np.asarray(rd["eta"], np.float64))
            out["rounds"].append(got)
    return out


def reference_readings(cell: dict, seed: int, devices,
                       precision: str = "f32", fault: str = "") -> dict:
    """Follow the first steps and rounds by the book.

    ``fault`` plants one of the faults the comparison must catch in the
    reference, for reading it at the cell's own size (``control.py``):
    ``half_batch`` (half of every batch left out) or ``no_exchange``
    (each node's round sees its own row as its neighbours').
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    model, mix = cell["model"], cell["traffic_mix"]
    a = reference.arch(model)
    opt = model["optimizer"]
    fp8 = precision == "fp8"
    nodes = mix["nodes"]
    h = mix["local_steps"] if nodes > 1 else 1
    mesh = Mesh(np.array(devices[:nodes]), ("node",))
    by_node = NamedSharding(mesh, P("node"))
    shapes = reference.param_shapes(a)
    key = weights.base_key(seed)
    gen = ZipfTokens(mix, a.vocab, nodes, seed)
    exchange = fault != "no_exchange"
    tmap = jax.tree_util.tree_map

    def init(k):
        p = weights.make(k, shapes)
        stack = lambda x: jnp.broadcast_to(x[None], (nodes,) + x.shape)  # noqa
        zeros = lambda x: jnp.zeros((nodes,) + x.shape, jnp.float32)     # noqa
        return tmap(stack, p), tmap(zeros, p), tmap(zeros, p)

    def probes(p, b):
        """Each node's probe loss at its own and its neighbours' params."""
        f = jax.vmap(lambda q, bb: reference.loss(
            a, fp8, q, bb["tokens"], bb["labels"]))
        shift = (lambda k: tmap(lambda x: jnp.roll(x, k, axis=0), p)) \
            if exchange else (lambda k: p)
        return f(p, b), f(shift(-1), b), f(shift(1), b)

    def change(q, k):
        p0 = weights.make(k, shapes)
        return jax.vmap(lambda qj: reference.leaf_norms(tmap(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            qj, p0)))(q)

    def put(b):
        if fault == "half_batch":
            b = {k: x[:, :x.shape[1] // 2] for k, x in b.items()}
        return jax.device_put(b, by_node)

    with jax.default_matmul_precision("highest"):
        p, m, v = jax.jit(init, out_shardings=by_node)(key)
        if nodes > 1:       # the dual and the neighbour mean start at zero
            lam, bar = jax.jit(lambda q: [tmap(lambda x: jnp.zeros(
                x.shape, jnp.float32), q)] * 2, out_shardings=by_node)(p)
            eta_up = eta_down = jnp.full((nodes,), float(mix["eta0"]))
        step = jax.jit(jax.vmap(functools.partial(
            reference.local_step, a, opt, fp8), in_axes=(None, 0, 0, 0, 0)),
            donate_argnums=(1, 2, 3))
        probes, change = jax.jit(probes), jax.jit(change)
        ring = jax.jit(functools.partial(reference.ring_round, mix,
                                         exchange=exchange))
        norms = jax.jit(jax.vmap(reference.leaf_norms))
        last = max(STEPS, ROUNDS * h) if nodes > 1 else STEPS
        losses, out, rounds = [], {}, []
        for t in range(last):
            if t == STEPS:
                out["update"] = change(p, key)
            p, m, v, lval, gn = step(float(t), p, m, v, put(gen.batch(t)))
            if t < STEPS:
                losses.append(lval)
            if t == 0:
                out["grad"] = gn
            if nodes > 1 and (t + 1) % h == 0 and len(rounds) < ROUNDS:
                f_self, f_up, f_down = probes(
                    p, put(gen.batch(probe_step(len(rounds), h))))
                p, lam, bar, r, s = ring(p, lam, bar, eta_up, eta_down)
                rd = {"r_max": r.max(), "s_max": s.max(),
                      "f_mean": f_self.mean(), "lam": norms(lam),
                      "bar": change(bar, key)}
                if not rounds:
                    eta_up, eta_down = reference.nap_first_eta(
                        mix["eta0"], f_self, f_up, f_down)
                    rd["eta"] = jnp.stack([eta_up, eta_down], axis=1)
                rounds.append(rd)
        if "update" not in out:
            out["update"] = change(p, key)
    res = {"loss": [float(jnp.mean(x)) for x in losses],
           "grad": np.asarray(out["grad"], np.float64),
           "update": np.asarray(out["update"], np.float64)}
    if rounds:
        res["rounds"] = [{k: (float(x) if k in ROUND_KEYS
                              else np.asarray(x, np.float64))
                          for k, x in rd.items()} for rd in rounds]
    return res


def leaf_gap(prog: np.ndarray, ref: np.ndarray, live: np.ndarray) -> float:
    """Worst leaf of |norm_prog - norm_ref| over max(norm_ref, median)."""
    med = np.median(ref, axis=-1, keepdims=True)
    gap = np.abs(prog - ref) / np.maximum(ref, med)
    return float(np.max(np.where(live, gap, 0.0)))


def live_leaves(ref: dict) -> np.ndarray:
    """[J, leaves] bool: leaves whose reference gradient is not nought to
    rounding (a thousandth of the median leaf's or more)."""
    return ref["grad"] >= DEAD_LEAF * np.median(ref["grad"], axis=-1,
                                                keepdims=True)


def numbers(program: dict, ref: dict) -> dict:
    """Every number of the comparison. Round k's carry the suffix k (none
    for the first): ``r``/``s``/``f`` relative gaps of the largest primal
    and dual residual and of the probe mean, ``lam``/``bar`` worst-leaf
    gaps of the dual and of the neighbour mean's change from the initial
    weights, as the round stored them, and ``eta_gap`` the
    largest gap of an edge's penalty after the first round, relative to
    the reference's."""
    live = live_leaves(ref)
    rel = lambda p, r: abs(p - r) / abs(r)                  # noqa: E731
    losses = [rel(p, r) for p, r in zip(program["loss"], ref["loss"])]
    out = {
        "first_loss_rel": losses[0],
        "loss_rel": max(losses),
        "grad_gap": leaf_gap(program["grad"], ref["grad"], live),
        "update_gap": leaf_gap(program["update"], ref["update"], live),
    }
    for k, rr in enumerate(ref.get("rounds", [])):
        pr, tag = program["rounds"][k], "" if k == 0 else str(k + 1)
        for key in ROUND_KEYS:
            out[ROUND_NAMES[key].format(tag)] = rel(pr[key], rr[key])
        out[f"lam{tag}_gap"] = leaf_gap(pr["lam"], rr["lam"], live)
        out[f"bar{tag}_gap"] = leaf_gap(pr["bar"], rr["bar"], live)
        if "eta" in rr:
            out["eta_gap"] = float(np.max(np.abs(pr["eta"] - rr["eta"])
                                          / rr["eta"]))
    return out


def checks(program: dict, ref: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} of the numbers compared.

    Every number needs an entry in the limits: a limit, or ``null`` for a
    number that is read but not compared (PERF.md says why); every limit
    needs its number."""
    got = numbers(program, ref)
    missing = set(got) - set(limits)
    if missing:
        raise KeyError(f"no limit for {sorted(missing)}")
    unread = {k for k, v in limits.items() if v is not None} - set(got)
    if unread:
        raise KeyError(f"no reading for {sorted(unread)}")
    return {k: {"value": v, "limit": limits[k]} for k, v in got.items()
            if limits[k] is not None}
