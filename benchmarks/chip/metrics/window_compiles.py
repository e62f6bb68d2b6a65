"""Backend compilations (or loads from the compile cache) inside the
window, counted through ``jax.monitoring``. Should be 0."""


def read(ctx):
    run = ctx.run
    return float(sum(1 for t in run.compiles
                     if run.window_t0 <= t <= run.window_t1))
