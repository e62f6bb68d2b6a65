"""Host-side driver for bounded-staleness consensus rounds.

``AsyncExecutor`` glues the three traced/host pieces together:

  * the trainer's ``consensus_step_async`` (the traced round: wire ledger,
    staleness clocks, masked fused kernel with zero-kick absorption),
  * the ``RoundClock`` event model (which nodes advance this fleet tick,
    which payloads landed — in a real deployment these come from the
    double buffer's DMA completion bits instead),
  * wall-clock accounting (modeled async elapsed vs the synchronous
    barrier equivalent for the same amount of consensus progress).

The executor is deliberately thin: all numerics live in the trainer, all
timing in the clock. It exists so the launcher and the benchmarks drive
async training through one object with one contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.async_exec.clock import RoundClock


class AsyncExecutor:
    """Drives a ``ConsensusTrainer`` with bounded-staleness rounds.

    Args:
      trainer: a ``repro.optim.ConsensusTrainer`` built with
        ``ConsensusConfig(async_exec=AsyncConfig(...))``.
      clock: a ``RoundClock``; None builds a homogeneous fleet (every
        payload always arrives — the no-straggler fast path).
    """

    def __init__(self, trainer, clock: RoundClock | None = None):
        if trainer.async_cfg is None:
            raise ValueError("trainer was built without ConsensusConfig."
                             "async_exec — nothing to execute")
        self.trainer = trainer
        self.cfg = trainer.async_cfg
        if clock is None:
            clock = RoundClock(
                compute_s=np.ones(trainer.num_nodes),
                wire_s=0.0, offsets=tuple(trainer.offsets))
        if clock.num_nodes != trainer.num_nodes:
            raise ValueError(f"clock models {clock.num_nodes} nodes, "
                             f"trainer has {trainer.num_nodes}")
        self.clock = clock
        self._cons = trainer.jit_async_step_fns()

    # ------------------------------------------------------------ state ----
    def init_state(self, key: jax.Array):
        return self.trainer.init_state(key)

    # ------------------------------------------------------------ steps ----
    def consensus_round(self, state, probe_batch):
        """One fleet tick: clock -> (arrivals, advance) -> traced round.

        With ``max_staleness=0`` the executor waits for everything — every
        payload is marked arrived and every node advances, which is the
        synchronous round bit-for-bit.
        """
        j = self.trainer.num_nodes
        deg = max(len(self.trainer.offsets), 1)
        if self.cfg.max_staleness == 0:
            arrivals = jnp.ones((deg, j), bool)
            advance = None
            self.clock.time_s += self.clock.sync_round_s
            self.clock.ticks += 1
        else:
            arr_np, adv_np = self.clock.tick()
            arrivals = jnp.asarray(arr_np)
            advance = jnp.asarray(adv_np)
        return self._cons(state, probe_batch, arrivals, advance)

    # ------------------------------------------------------- accounting ----
    @property
    def async_elapsed_s(self) -> float:
        """Modeled wall-clock spent so far (clock conventions).

        There is deliberately no "sync equivalent" counterpart: an async
        fleet tick advances only the nodes whose rounds completed, so
        tick counts and synchronous round counts are NOT interchangeable
        — compare executors by progress-to-target, the way
        ``benchmarks/async_staleness.py`` does.
        """
        return float(self.clock.time_s)

    def summary(self) -> dict:
        c = self.clock
        rounds = np.asarray(c.rounds_done, dtype=np.int64)
        # per-node lag behind the fleet's front-runner, in consensus
        # rounds — the straggler scorer (obs.health) reads the percentiles
        lag = (rounds.max() - rounds) if rounds.size else rounds
        return {
            "ticks": int(c.ticks),
            "rounds_done": rounds.tolist(),
            "round_lag": lag.tolist(),
            "lag_p50": float(np.percentile(lag, 50)) if lag.size else 0.0,
            "lag_p90": float(np.percentile(lag, 90)) if lag.size else 0.0,
            "lag_p100": float(lag.max()) if lag.size else 0.0,
            "async_elapsed_s": round(self.async_elapsed_s, 6),
            "sync_round_s": round(c.sync_round_s, 6),
            "tick_s": round(c.tick_s, 6),
            "max_staleness": self.cfg.max_staleness,
        }

    def export_timeline(self, path: str) -> str:
        """Write the clock's modeled timeline as a Chrome/Perfetto trace.

        Per-node compute and wire tracks reconstructed from the clock's
        event model (``repro.obs.export``) — load the JSON in
        https://ui.perfetto.dev next to a measured ``--profile-rounds``
        trace to compare modeled and actual compute/wire overlap.
        """
        from repro.obs.export import write_roundclock_trace
        return write_roundclock_trace(self.clock, path)
