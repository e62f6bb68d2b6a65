"""The fused consensus kernel's share of its HBM roofline: the least time
for the bytes it must move (``flops.round_kernel_bytes`` at the flat
layout's row width and the codec's wire row, taken when the trainer is
built) over its device time per call, mean over chips."""
from chipbench import peaks
from chipbench.trace import kernel_ns


def read(ctx):
    run, red = ctx.run, ctx.red
    rounds = run.counted // run.h
    per_chip = [kernel_ns(c) for c in red.chips]
    if not rounds or not all(per_chip):
        return None
    t = 1e-9 * sum(per_chip) / len(per_chip) / rounds
    need = run.round_bytes / peaks.of(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / t
