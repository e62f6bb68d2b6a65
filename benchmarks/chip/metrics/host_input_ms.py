"""Host time per step spent making and placing batches (the benchmark's
span around the launcher's batch source; training and probe batches)."""


def read(ctx):
    run = ctx.run
    spent = sum(d for t, d in run.batch_s
                if run.window_t0 <= t <= run.window_t1)
    return 1e3 * spent / run.counted
