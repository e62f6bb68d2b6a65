"""Runtime fault tolerance: retries, straggler detection, elastic rescale.

A unique property of consensus-ADMM training (vs. a global all-reduce): the
optimizer *tolerates a missing neighbor* — dropping an edge or a node leaves
a smaller but still-valid consensus problem. Two elastic paths exploit that:

  * **layout-preserving** (preferred, ``ElasticController.drop_preserving``):
    the lost pod becomes a masked ghost row in the dynamic-topology state
    (``repro.topology``) — array shapes, jit caches and the fused step all
    survive untouched; the runtime rewires the surviving nodes through the
    compiled offset superset and asserts connectivity. A node loss is a
    topology epoch, not a crash.
  * **shrinking** (legacy, ``ElasticController.drop``): rebuild the graph at
    J-1 (``core.graph.drop_node``) and remap the surviving eta/budget edges
    — a restart from checkpoint into the smaller mesh; a synchronous-DP
    framework would have to abort the step either way.

Wall-clock monitoring is injectable (``clock``) so straggler logic is unit-
testable on CPU without real slow hosts.

Under the async executor (``repro.async_exec``) straggler detection and
churn UNIFY: a straggler is just a node whose edges aged out. The
bounded-staleness clocks (``TopologyState.age``) already gate a slow
node's edges round by round — transiently, with zero-kick absorption, and
self-healing on the next arrival. ``aged_out_nodes`` reads those same
clocks at a patience multiple of the staleness bound: a node that stays
aged out that long has effectively left the fleet, and ghosting it via
``ElasticController.drop_preserving`` merely makes permanent (and
backbone-repairs) what the staleness gates were already doing. No second
wall-clock heuristic, one signal for both mechanisms.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

from repro.core.graph import Graph, drop_node
from repro.core.penalty import PenaltyState


@dataclasses.dataclass
class RetryPolicy:
    max_retries: int = 3
    backoff_s: float = 0.5
    backoff_mult: float = 2.0
    retryable: tuple = (RuntimeError, OSError)


def with_retries(fn: Callable, policy: RetryPolicy,
                 *, on_retry: Callable[[int, Exception], None] | None = None,
                 sleep: Callable[[float], None] = time.sleep):
    """Wrap a step function in bounded retry-with-backoff.

    Every retry is printed (``retry k/n: <error>``), never silent. An
    out-of-memory error (XLA's ``RESOURCE_EXHAUSTED``) is not retried: the
    same step on the same state would fail the same way.
    """
    def wrapped(*args, **kwargs):
        delay = policy.backoff_s
        for attempt in range(policy.max_retries + 1):
            try:
                return fn(*args, **kwargs)
            except policy.retryable as e:
                if attempt == policy.max_retries \
                        or "RESOURCE_EXHAUSTED" in str(e):
                    raise
                print(f"retry {attempt + 1}/{policy.max_retries}: "
                      f"{type(e).__name__}: {e}", flush=True)
                if on_retry is not None:
                    on_retry(attempt, e)
                sleep(delay)
                delay *= policy.backoff_mult
        raise AssertionError("unreachable")
    return wrapped


class StragglerMonitor:
    """EMA step-time tracker with outlier flagging per node.

    In a real deployment each host reports its step wall time; here the
    ``observe`` call takes the per-node durations (tests inject synthetic
    delays). A node whose EMA exceeds ``threshold`` x the fleet median is
    flagged; the caller decides between (a) dropping its edges for the next
    consensus round and (b) a full elastic rescale.
    """

    def __init__(self, num_nodes: int, *, alpha: float = 0.3,
                 threshold: float = 2.0, patience: int = 3):
        self.ema = np.zeros(num_nodes)
        self.alpha = alpha
        self.threshold = threshold
        self.patience = patience
        self.strikes = np.zeros(num_nodes, dtype=int)
        self._initialized = False

    def observe(self, durations: np.ndarray) -> list[int]:
        durations = np.asarray(durations, dtype=float)
        if not self._initialized:
            self.ema = durations.copy()
            self._initialized = True
        else:
            self.ema = (1 - self.alpha) * self.ema + self.alpha * durations
        med = float(np.median(self.ema))
        slow = self.ema > self.threshold * max(med, 1e-9)
        self.strikes = np.where(slow, self.strikes + 1, 0)
        return [int(i) for i in np.nonzero(
            self.strikes >= self.patience)[0]]


def aged_out_nodes(topo_state, *, max_staleness: int,
                   patience: int = 4) -> list[int]:
    """Nodes whose EVERY active edge has aged past ``patience x bound``.

    The async executor's staleness clocks (``TopologyState.age``) are the
    straggler signal: an edge older than ``max_staleness`` is already
    transiently gated by the executor; a node whose freshest edge is
    ``patience`` times older than the bound is not late, it is gone —
    return it for a layout-preserving ghost drop. Symmetrized ages (max of
    both directions) so a half-broken link counts as broken.
    """
    age = np.asarray(topo_state.age)
    age = np.maximum(age, age.T)
    mask = np.asarray(topo_state.mask)
    alive = np.asarray(topo_state.node_alive)
    cutoff = patience * max(max_staleness, 1)
    out = []
    for i in range(age.shape[0]):
        if not alive[i]:
            continue
        edges = mask[i] & alive
        edges[i] = False
        if edges.any() and age[i][edges].min() > cutoff:
            out.append(i)
    return out


def shrink_penalty_state(state: PenaltyState, victim: int) -> PenaltyState:
    """Remove a node's rows/cols from the [J, J] penalty state.

    Surviving edges keep their eta / spent budget / top-up counters — the
    adaptation history is preserved across the rescale.
    """
    import jax.numpy as jnp
    keep = jnp.asarray([i for i in range(state.eta.shape[0]) if i != victim])

    def cut(x):
        if x.ndim == 2:
            return x[jnp.ix_(keep, keep)]
        if x.ndim == 1:
            return x[keep]
        return x

    return PenaltyState(eta=cut(state.eta), cum_tau=cut(state.cum_tau),
                        budget=cut(state.budget), n_incr=cut(state.n_incr),
                        f_prev=cut(state.f_prev), t=state.t)


@dataclasses.dataclass
class ElasticEvent:
    step: int
    victim: int
    old_nodes: int
    new_nodes: int
    mode: str = "shrink"          # shrink | preserve


class ElasticController:
    """Drives the consensus-problem rescale when a node is lost.

    Two modes (module docstring): ``drop`` shrinks the graph and penalty
    state to J-1 (the launcher restarts into the smaller mesh); with a
    ``topology`` runtime attached, ``drop_preserving`` instead ghosts the
    victim in the traced TopologyState — shapes, jit caches and the fused
    step survive, so training continues without a restart. The controller
    decides *what the new consensus problem is* either way.
    """

    def __init__(self, graph: Graph, *, topology=None):
        self.graph = graph
        self.topology = topology          # optional TopologyRuntime
        self.events: list[ElasticEvent] = []

    def drop(self, victim: int, penalty: PenaltyState, step: int
             ) -> tuple[Graph, PenaltyState]:
        old = self.graph.num_nodes
        self.graph = drop_node(self.graph, victim)
        new_pen = shrink_penalty_state(penalty, victim)
        self.events.append(ElasticEvent(step=step, victim=victim,
                                        old_nodes=old,
                                        new_nodes=self.graph.num_nodes))
        return self.graph, new_pen

    def drop_preserving(self, victim: int, topo_state, step: int):
        """Layout-preserving drop -> new TopologyState (no shapes change).

        The penalty state is NOT shrunk: the engine masks ghost rows/cols
        out of the penalty adjacency, preserving surviving edges' full
        adaptation history at the original [J, J] layout.
        """
        if self.topology is None:
            raise ValueError("drop_preserving needs a TopologyRuntime "
                             "(ElasticController(graph, topology=...))")
        new_state = self.topology.drop_node(topo_state, victim)
        alive = int(np.asarray(new_state.node_alive).sum())
        self.events.append(ElasticEvent(step=step, victim=victim,
                                        old_nodes=self.graph.num_nodes,
                                        new_nodes=alive, mode="preserve"))
        return new_state
