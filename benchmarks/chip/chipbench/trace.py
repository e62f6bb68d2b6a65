"""Reduce a profiler trace (``.xplane.pb``) of the window to numbers.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line
holds one event per run of a jitted program (``jit_train_step``,
``jit_consensus_step``) and the ``XLA Ops`` line one event per operation.
The host's ``bench/...`` spans (``harness.py``) sit on host threads of the
same trace, on the same clock.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

from chipbench import manifest

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
COLLECTIVE = ("collective-permute", "all-reduce", "all-gather",
              "reduce-scatter", "all-to-all")


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


def union(intervals) -> list[tuple[int, int]]:
    """Merged [start, end) intervals, sorted."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def minus(a, b) -> list[tuple[int, int]]:
    """Parts of the merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


@dataclasses.dataclass
class Chip:
    modules: list      # (name, start_ns, end_ns)
    ops: list          # (HLO text, start_ns, end_ns), nested ones included

    def self_ns(self) -> list[tuple[str, int, int, int]]:
        """(HLO text, start, end, self time): an op's time less that of
        the ops nested in it (a loop's body runs inside the loop's op)."""
        out, stack = [], []
        for i, (n, s, e) in enumerate(sorted(self.ops,
                                             key=lambda o: (o[1], -o[2]))):
            while stack and stack[-1][1] <= s:
                stack.pop()
            out.append([n, s, e, e - s])
            if stack:
                out[stack[-1][0]][3] -= e - s
            stack.append((i, e))
        return [tuple(o) for o in out]

    def leaves(self) -> list[tuple[str, int, int]]:
        """The ops with nothing nested in them."""
        return [(n, s, e) for n, s, e, own in self.self_ns() if own == e - s]


@dataclasses.dataclass
class Reduced:
    chips: list        # Chip per device, in device order
    host_spans: list   # (name, start_ns, end_ns) of the harness's spans
    window_s: float    # host-clock length of the traced window
    busy_s: float      # mean over chips of the union of op time

    def module_ns(self, chip: Chip, prefix: str) -> list[int]:
        return [e - s for n, s, e in chip.modules if n.startswith(prefix)]

    def idle_gaps(self, chip: Chip, top: int = 10):
        """The longest gaps between device ops, each named by the host
        span that covers most of it."""
        busy = union((s, e) for _, s, e in chip.ops)
        gaps = sorted(((e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])),
                      key=lambda g: g[0] - g[1])[:top]
        out = []
        for s, e in gaps:
            best, cover = "none", 0
            for name, hs, he in self.host_spans:
                c = min(e, he) - max(s, hs)
                if c > cover:
                    best, cover = name, c
            out.append((best, (e - s) * 1e-9))
        return out

    def breakdown(self) -> dict:
        ops: dict[str, float] = {}
        for chip in self.chips:
            for n, _, _, own in chip.self_ns():
                n = op_name(n)
                ops[n] = ops.get(n, 0.0) + own * 1e-9 / len(self.chips)
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, v] for n, v in top],
                "idle_gaps": [[n, v] for n, v in self.idle_gaps(
                    self.chips[0])]}


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def reduce(path: Path, host_window: tuple[float, float]) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    chips, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            chips.append((plane.name, Chip(
                modules=list(_events(lines[MODULES_LINE]))
                if MODULES_LINE in lines else [],
                ops=list(_events(lines[OPS_LINE]))
                if OPS_LINE in lines else [])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith("bench/")]
    chips = [c for _, c in sorted(chips, key=lambda nc: int(
        nc[0].rsplit(":", 1)[1]))]
    if not chips or not any(c.ops for c in chips):
        raise ValueError(f"{path}: no device operations in the trace")
    window = host_window[1] - host_window[0]
    busy = sum(total(union((s, e) for _, s, e in c.ops))
               for c in chips) / len(chips) * 1e-9
    return Reduced(chips=chips, host_spans=spans, window_s=window,
                   busy_s=busy)


def kernel_ns(chip: Chip, module: str = "jit_consensus_step") -> int:
    """Device time of the Pallas calls (``tpu_custom_call``) that run
    inside ``module`` on a chip."""
    inside = union((s, e) for n, s, e in chip.modules
                   if n.startswith(module))
    calls = union((s, e) for n, s, e in chip.ops if "tpu_custom_call" in n)
    return total(calls) - total(minus(calls, inside))


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    run: object        # harness.Run: counts, host times, the cell
    red: Reduced


def load_reader(name: str):
    """``metrics/<name>.py``'s ``read(ctx) -> float | None``."""
    path = manifest.HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
