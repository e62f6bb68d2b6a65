"""Beyond-paper ablation: penalty schemes on LM consensus training.

Trains the reduced qwen3 config across 2 simulated pods with each penalty
scheme and reports loss after N steps + replica divergence — the paper's
D-PPCA comparison transplanted to the LM trainer. Needs 8 devices.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import require_devices, write_csv


def run(steps: int = 16, local_steps: int = 2) -> list[dict]:
    import jax
    require_devices("lm_scheme_ablation", 8)
    import jax.numpy as jnp
    from repro.configs import get_reduced_config
    from repro.core.penalty import PenaltyConfig, SCHEMES
    from repro.data import DataConfig, SyntheticTokens
    from repro.launch.mesh import make_debug_mesh
    from repro.models import build_model
    from repro.optim import ConsensusConfig, ConsensusTrainer
    from repro.optim.adamw import AdamWConfig

    mesh = make_debug_mesh(multi_pod=True)
    cfg = get_reduced_config("qwen3-4b")
    model = build_model(cfg)
    data = SyntheticTokens(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      batch_per_node=4, num_nodes=2))
    rows = []
    for scheme in SCHEMES:
        tr = ConsensusTrainer(
            model, mesh, adamw=AdamWConfig(lr=1e-2),
            consensus=ConsensusConfig(
                penalty=PenaltyConfig(scheme=scheme, eta0=0.1),
                topology="ring", local_steps=local_steps))
        state = tr.init_state(jax.random.PRNGKey(0))
        train = jax.jit(tr.train_step)
        cons = jax.jit(tr.consensus_step)
        losses = []
        for step in range(steps):
            state, m = train(state, data.batch(step))
            losses.append(float(m["loss"]))
            if tr.should_sync(step):
                state, cm = cons(state, data.batch(step, probe=True))
        leaf = jax.tree_util.tree_leaves(state.params)[0]
        div = float(jnp.abs(leaf[0] - leaf[1]).max())
        rows.append({"scheme": scheme,
                     "final_loss": round(losses[-1], 4),
                     "mean_last4": round(float(np.mean(losses[-4:])), 4),
                     "replica_divergence": round(div, 5),
                     "eta_mean": round(float(cm["eta_mean"]), 4)})
        print(f"lm_ablation {scheme:8s} loss={losses[-1]:.4f} "
              f"div={div:.5f}", flush=True)
    write_csv("lm_scheme_ablation.csv", rows)
    return rows


if __name__ == "__main__":
    run()
