"""The program's own spans and scopes in the traced window's ``.xplane.pb``.

The launcher (``repro.launch.train``) writes host spans on the
profiler's clock: ``train/step`` once per loop iteration with its
``step_num``, and inside it ``train/batch``, ``train/dispatch``,
``train/wait`` and ``round/sync``. The compiled ops carry the program's
named scopes (``repro.obs.trace``) in their ``op_name`` metadata:
``local/grad``, ``local/optimizer`` and the five ``consensus/*`` phases.

An op's scope is read from its own ``tf_op`` stat where that holds a
path the program wrote (one that starts at its ``jit(``); otherwise
from the HLO protos of the executables that the profiler puts on the
``/host:metadata`` plane (one per module, named as the ``XLA Modules``
events are), by the op's instruction name. A fusion with no such path
stands for the nearest one in its fused computation. Both come from the
trace file alone, read after the window closed; nothing is added to the
timed run. The file is decoded once per process.

A program without these spans or scopes (one older than them) yields
none, and the readers that need them return ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import re
from pathlib import Path

from chipbench import trace as trace_lib

# a phase is a pair of path elements; transforms wrap it (vmap(local/grad))
# and a tf_op stat may end in ":<op type>"
PHASE = re.compile(r"(?:^|[/(])(local/(?:grad|optimizer)|consensus/(?:pack|"
                   r"exchange|probe|fused_round|penalty)|obs/ring)(?=[/):]|$)")
STEP = "train/step"
SCOPE_STATS = ("tf_op",)
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def phase_of(scope: str | None) -> str | None:
    """The one phase a scope path lies under, else None."""
    found = set(PHASE.findall(scope or ""))
    return found.pop() if len(found) == 1 else None


# ------------------------------------------------- the file's messages ----
@functools.lru_cache(maxsize=None)
def _messages():
    """XSpace (tsl/profiler/protobuf/xplane.proto) and the part of HloProto
    (xla/service/hlo.proto) read here, by their field numbers."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    txt, raw, msg = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    shapes = {
        "XStat": [("metadata_id", 1, i64, one), ("double_value", 2, dbl, one),
                  ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
                  ("str_value", 5, txt, one), ("bytes_value", 6, raw, one),
                  ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one), ("stats", 4, "XStat", many)],
        "XLine": [("name", 2, txt, one), ("timestamp_ns", 3, i64, one),
                  ("events", 4, "XEvent", many)],
        "XEventMetadata": [("id", 1, i64, one), ("name", 2, txt, one),
                           ("display_name", 4, txt, one),
                           ("stats", 5, "XStat", many)],
        "XStatMetadata": [("id", 1, i64, one), ("name", 2, txt, one)],
        "EventMetadataEntry": [("key", 1, i64, one),
                               ("value", 2, "XEventMetadata", one)],
        "StatMetadataEntry": [("key", 1, i64, one),
                              ("value", 2, "XStatMetadata", one)],
        "XPlane": [("name", 2, txt, one), ("lines", 3, "XLine", many),
                   ("event_metadata", 4, "EventMetadataEntry", many),
                   ("stat_metadata", 5, "StatMetadataEntry", many)],
        "XSpace": [("planes", 1, "XPlane", many)],
        "OpMetadata": [("op_name", 2, txt, one)],
        "HloInstructionProto": [("name", 1, txt, one),
                                ("opcode", 2, txt, one),
                                ("metadata", 7, "OpMetadata", one),
                                ("id", 35, i64, one),
                                ("operand_ids", 36, i64, many),
                                ("called_computation_ids", 38, i64, many)],
        "HloComputationProto": [("name", 1, txt, one),
                                ("instructions", 2, "HloInstructionProto",
                                 many),
                                ("id", 5, i64, one), ("root_id", 6, i64, one)],
        "HloModuleProto": [("name", 1, txt, one),
                           ("computations", 3, "HloComputationProto", many)],
        "HloProto": [("hlo_module", 1, "HloModuleProto", one)],
    }
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_program_trace.proto", package="chipbench",
        syntax="proto3")
    for name, fields in shapes.items():
        m = fdp.message_type.add(name=name)
        for fname, number, kind, label in fields:
            f = m.field.add(name=fname, number=number, label=label)
            if isinstance(kind, str):
                f.type, f.type_name = msg, f".chipbench.{kind}"
            else:
                f.type = kind
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"chipbench.{name}")) for name in shapes}


def _stat_value(stat, names: dict):
    if stat.ref_value:
        return names.get(stat.ref_value, "")
    for field in ("str_value", "int64_value", "uint64_value",
                  "double_value", "bytes_value"):
        v = getattr(stat, field)
        if v:
            return v
    return 0


def program_path(name: str | None) -> str | None:
    """A scope path that the program wrote (it starts at its jit), else
    None: the compiler names what it makes after an argument, another
    instruction or a reducer's op (``add``)."""
    return name if name and name.startswith("jit(") else None


def hlo_scopes(blob: bytes) -> dict[str, str]:
    """Instruction name -> scope path (``op_name``) of one HLO proto. A
    fusion without a program path takes the nearest one in its fused
    computation: from the root through operands, then inside the
    computations those call."""
    mod = _messages()["HloProto"].FromString(blob).hlo_module
    comps = {c.id: c for c in mod.computations}
    by_id = {c.id: {i.id: i for i in c.instructions} for c in comps.values()}

    def root_name(cid: int, depth: int = 0) -> str:
        ins, todo, seen, calls = by_id[cid], [comps[cid].root_id], set(), []
        while todo:
            i = ins.get(todo.pop(0))
            if i is None or i.id in seen:
                continue
            seen.add(i.id)
            if program_path(i.metadata.op_name):
                return i.metadata.op_name
            todo += list(i.operand_ids)
            calls += [c for c in i.called_computation_ids if c in comps]
        for sub in calls if depth < 4 else []:
            if name := root_name(sub, depth + 1):
                return name
        return ""

    out = {}
    for c in comps.values():
        for i in c.instructions:
            name = program_path(i.metadata.op_name) or ""
            if not name and i.opcode == "fusion":
                for sub in i.called_computation_ids:
                    if sub in comps and (name := root_name(sub)):
                        break
            out[i.name] = name
    return out


# ---------------------------------------------------------- the reading ----
@dataclasses.dataclass
class ProgramTrace:
    steps: list        # (step_num, start_ns, end_ns) of each train/step
    spans: list        # (name, start_ns, end_ns): train/* and round/*
    chips: list        # per chip, in device order: [(phase, start, self)]
    scoped_by: str     # "tf_op", "hlo_proto" or "none"

    def window(self, warmup: int, counted: int, h: int) -> list:
        """The counted steps' spans, in whole round cycles only: the
        window's first step opened before the trace started, so its
        cycle has no span and drops out."""
        want = {s: (a, b) for s, a, b in self.steps
                if warmup <= s < warmup + counted}
        out = []
        for first in range(warmup, warmup + counted, h):
            cycle = range(first, first + h)
            if all(s in want for s in cycle):
                out += [(s,) + want[s] for s in cycle]
        return out

    def end_ns(self, warmup: int, counted: int) -> int | None:
        """End of the last counted step's span: every device op of the
        counted steps ends before it (the step waits for its loss and
        reads its round's metrics)."""
        ends = [e for s, _, e in self.steps if warmup <= s < warmup + counted]
        return max(ends) if ends else None

    def inside(self, steps, names) -> list[int]:
        """Per step, the host time of its child spans named ``names``
        (a prefix ending in ``/`` names a family)."""
        def named(n):
            return any(n == x or (x.endswith("/") and n.startswith(x))
                       for x in names)
        return [sum(e - s for n, s, e in self.spans
                    if named(n) and a <= s and e <= b)
                for _, a, b in steps]

    def phase_ns(self, phase: str, until_ns: int | None = None
                 ) -> list[int] | None:
        """Per chip, the device self time of ops under ``phase`` that
        start before ``until_ns``; None where no op has any phase (a
        program without scopes)."""
        if not any(p for chip in self.chips for p, _, _ in chip):
            return None
        out = []
        for chip in self.chips:
            out.append(sum(own for p, s, own in chip
                           if p == phase and (until_ns is None
                                              or s < until_ns)))
        return out


def _self_times(ops):
    """(phase, start, self ns) per op: its time less that of the ops
    nested in it (a loop's body runs inside the loop's op)."""
    chip = trace_lib.Chip(modules=[], ops=[(i, s, e) for i, (_, s, e)
                                           in enumerate(ops)])
    return [(ops[i][0], s, own) for i, s, _, own in chip.self_ns()]


def decode(path: Path) -> ProgramTrace:
    msgs = _messages()
    space = msgs["XSpace"].FromString(Path(path).read_bytes())
    protos: dict[str, dict] = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        names = {e.key: e.value.name for e in plane.stat_metadata}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if names.get(st.metadata_id) == HLO_PROTO_STAT \
                        and st.bytes_value:
                    protos[e.value.name] = hlo_scopes(st.bytes_value)
    bases: dict[str, list] = {}
    for name, table in protos.items():
        bases.setdefault(name.split("(", 1)[0], []).append(table)

    steps, spans, chips, by_stat, by_proto = [], [], [], 0, 0
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: e.value for e in plane.event_metadata}
        cache: dict = {}
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            modules = sorted((s, e, ev) for ev, s, e, _, _ in _events(
                lines.get(trace_lib.MODULES_LINE), meta))
            starts = [m[0] for m in modules]
            ops = []                        # (instruction, start, end, scope)
            for ev, s, e, raw, md in _events(lines.get(trace_lib.OPS_LINE),
                                             meta):
                stats = _stats(raw, md, names, cache)
                ops.append((trace_lib.op_name(ev), s, e, next(
                    (stats[k] for k in SCOPE_STATS
                     if program_path(stats.get(k))), None)))
            by_stat += sum(op[3] is not None for op in ops)
            # each op's module: the module event that holds it
            inside: dict[int, list] = {}
            for n, op in enumerate(ops):
                k = bisect.bisect_right(starts, op[1]) - 1
                if k >= 0 and op[2] <= modules[k][1]:
                    inside.setdefault(k, []).append(n)
            scopes = [op[3] for op in ops]
            for k, members in inside.items():
                table = _table(modules[k][2], protos, bases,
                               [ops[n][0] for n in members])
                for n in members:
                    if scopes[n] is None and table:
                        scopes[n] = table.get(ops[n][0])
                        by_proto += bool(scopes[n])
            chips.append((int(plane.name.rsplit(":", 1)[1]), _self_times(
                [(phase_of(sc), s, e)
                 for (_, s, e, _), sc in zip(ops, scopes)])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev, s, e, raw, md in _events(ln, meta):
                    if ev == STEP:
                        stats = _stats(raw, md, names, cache)
                        if "step_num" in stats:
                            steps.append((int(stats["step_num"]), s, e))
                    elif ev.startswith(("train/", "round/")):
                        spans.append((ev, s, e))
    return ProgramTrace(
        steps=sorted(steps, key=lambda x: x[1]), spans=spans,
        chips=[c for _, c in sorted(chips)],
        scoped_by="tf_op" if by_stat else "hlo_proto" if by_proto
        else "none")


def _table(module: str, protos: dict, bases: dict, names: list):
    """The scope table of a module event: the HLO proto of that name, else
    the one of the same program (``jit_train_step`` of any fingerprint)
    that holds the most of the event's instruction names."""
    if module in protos:
        return protos[module]
    same = bases.get(module.split("(", 1)[0], [])
    if not same:
        return None
    return max(same, key=lambda t: sum(n in t for n in names))


def _events(line, meta):
    """(name, start_ns, end_ns, event, metadata) per event of a line."""
    if line is None:
        return
    base = line.timestamp_ns * 1000
    for ev in line.events:
        md = meta.get(ev.metadata_id)
        yield (md.name if md is not None else "",
               (base + ev.offset_ps) // 1000,
               (base + ev.offset_ps + ev.duration_ps) // 1000, ev, md)


def _stats(ev, md, names: dict, cache: dict) -> dict:
    """An event's stats over those of its metadata."""
    if md is None:
        out = {}
    else:
        if md.id not in cache:
            cache[md.id] = {names.get(st.metadata_id, ""):
                            _stat_value(st, names) for st in md.stats}
        out = cache[md.id]
    if len(ev.stats):
        out = dict(out)
        out.update((names.get(st.metadata_id, ""), _stat_value(st, names))
                   for st in ev.stats)
    return out


@functools.lru_cache(maxsize=4)
def _cached(path: str, mtime_ns: int) -> ProgramTrace:
    return decode(Path(path))


def load(root: Path | None = None) -> ProgramTrace:
    """The traced window's file, decoded once per process."""
    if root is None:
        from chipbench.harness import TRACE_DIR
        root = TRACE_DIR
    path = trace_lib.find_xplane(root)
    return _cached(str(path), path.stat().st_mtime_ns)


def for_run(ctx) -> ProgramTrace:
    """What a reader reads: ``ctx.program`` where a test put one there,
    else the traced run's file."""
    return getattr(ctx, "program", None) or load()
