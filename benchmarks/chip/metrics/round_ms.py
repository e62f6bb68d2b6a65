"""Device time of ``jit_consensus_step`` per round, mean over chips."""


def read(ctx):
    red, run = ctx.red, ctx.run
    rounds = run.counted // run.h
    per_chip = [sum(red.module_ns(c, "jit_consensus_step"))
                for c in red.chips]
    if not rounds or not any(per_chip):
        return None
    return 1e-6 * sum(per_chip) / len(per_chip) / rounds
