"""Host time per step in the launcher's ``train/dispatch`` and
``round/*`` spans: the calls of the local step and of the round."""
from chipbench import program_trace


def read(ctx):
    run, pt = ctx.run, program_trace.for_run(ctx)
    steps = pt.window(run.warmup, run.counted, run.h)
    if not steps:
        return None
    spent = pt.inside(steps, ("train/dispatch", "round/"))
    return 1e-6 * sum(spent) / len(steps)
