"""Collective time per round during which no other operation runs on that
chip, mean over chips."""
from chipbench.trace import COLLECTIVE, minus, op_name, total, union


def read(ctx):
    red, run = ctx.red, ctx.run
    rounds = run.counted // run.h
    if not rounds:
        return None
    exposed, seen = 0, False
    for c in red.chips:
        leaves = [(op_name(n), s, e) for n, s, e in c.leaves()]
        coll = union((s, e) for n, s, e in leaves
                     if n.startswith(COLLECTIVE))
        other = union((s, e) for n, s, e in leaves
                      if not n.startswith(COLLECTIVE))
        seen = seen or bool(coll)
        exposed += total(minus(coll, other))
    if not seen:
        return None
    return 1e-6 * exposed / len(red.chips) / rounds
