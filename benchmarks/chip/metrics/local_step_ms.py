"""Device time of ``jit_train_step`` per step, mean over chips."""


def read(ctx):
    red, run = ctx.red, ctx.run
    per_chip = [sum(red.module_ns(c, "jit_train_step")) for c in red.chips]
    if not any(per_chip):
        return None
    return 1e-6 * sum(per_chip) / len(per_chip) / run.counted
