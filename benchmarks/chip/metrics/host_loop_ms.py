"""Host time per step in the launcher's ``train/step`` outside its
``train/wait`` spans: the host's serial share of a step (batches,
dispatch, formatting, printing)."""
from chipbench import program_trace


def read(ctx):
    run, pt = ctx.run, program_trace.for_run(ctx)
    steps = pt.window(run.warmup, run.counted, run.h)
    if not steps:
        return None
    waits = pt.inside(steps, ("train/wait",))
    return 1e-6 * sum(b - a - w for (_, a, b), w in zip(steps, waits)) \
        / len(steps)
