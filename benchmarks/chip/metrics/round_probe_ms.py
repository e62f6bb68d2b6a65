"""Device time per round of the ops under the program's
``consensus/probe`` scope (the objective probes' forwards), mean over
chips."""
from chipbench import program_trace


def read(ctx):
    run, pt = ctx.run, program_trace.for_run(ctx)
    rounds = run.counted // run.h
    per_chip = pt.phase_ns("consensus/probe",
                           until_ns=pt.end_ns(run.warmup, run.counted))
    if not rounds or not per_chip or not any(per_chip):
        return None
    return 1e-6 * sum(per_chip) / len(per_chip) / rounds
