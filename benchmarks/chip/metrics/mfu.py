"""Model FLOPs of the window's training tokens over window x chips x the
chip's bf16 peak (probe forwards and recomputation do not count)."""
from chipbench import flops, peaks, reference


def read(ctx):
    run, red = ctx.run, ctx.red
    a = reference.arch(run.model)
    mix = run.mix
    tokens = run.nodes * mix["batch_per_node"] * mix["seq_len"] * run.counted
    work = tokens * flops.train_flops_per_token(a, mix["seq_len"])
    peak = peaks.of(run.device_kind)["bf16_flops"]
    return 100.0 * work / (red.window_s * len(red.chips) * peak)
