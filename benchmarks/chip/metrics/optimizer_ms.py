"""Device time per step of the ops under the program's ``local/optimizer``
scope (AdamW: clip, moments, decay), mean over chips."""
from chipbench import program_trace


def read(ctx):
    run, pt = ctx.run, program_trace.for_run(ctx)
    per_chip = pt.phase_ns("local/optimizer",
                           until_ns=pt.end_ns(run.warmup, run.counted))
    if not per_chip or not any(per_chip):
        return None
    return 1e-6 * sum(per_chip) / len(per_chip) / run.counted
