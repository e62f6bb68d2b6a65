"""Which ``obs.trace`` phase each instruction of a compiled HLO module
lies under, read from its text (``compiled.as_text()``).

An instruction's scope path is its ``op_name`` metadata. A fusion that
carries none stands for its fused computation: the nearest named
instructions from its root down. Instructions with no op_name from the
program (one that starts at ``jit(``) are made by the compiler: layout
copies and prefetches, split reductions, rewritten dots, named after an
argument or another instruction if at all. No scope reaches them; they
are counted apart. Parameters, tuples, constants and bitcasts do no
work, nor does an instruction whose value is a compile-time constant
(all its inputs are constants or iotas: JAX hoists such ops out of
scanned bodies without their scope).
"""
from __future__ import annotations

import collections
import re

# a phase is a path element pair; transforms wrap it: vmap(local/grad)
PHASE = re.compile(r"(?:^|[/(])(local/(?:grad|optimizer)|consensus/(?:pack|"
                   r"exchange|probe|fused_round|penalty)|obs/ring)(?=[/)]|$)")
NO_WORK = {"parameter", "tuple", "get-tuple-element", "constant", "bitcast"}
NOT_CONSTANT = {"parameter", "partition-id", "replica-id", "rng",
                "rng-get-and-update-state", "after-all", "infeed"}
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_HEADER = re.compile(r"^(ENTRY )?%(\S+) ")


def phases_of(op_name: str) -> set[str]:
    return set(PHASE.findall(op_name))


def _after_shape(rest: str) -> str:
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                return rest[i + 1:].lstrip()
    return rest.split(" ", 1)[1]


def _operands(call: str) -> list[str]:
    depth, out = 1, []
    for c in call.split("(", 1)[1]:
        depth += (c == "(") - (c == ")")
        if depth == 0:
            break
        out.append(c)
    return re.findall(r"%([\w.\-]+)", "".join(out))


def parse(text: str) -> dict:
    """{computation: {"entry", "root", "ins": {name: instruction}}}."""
    comps, cur = {}, None
    for ln in text.splitlines():
        m = _HEADER.match(ln)
        if m and ln.rstrip().endswith("{"):
            cur = comps[m.group(2)] = {"entry": bool(m.group(1)),
                                      "root": None, "ins": {}}
            continue
        mi = _INSTR.match(ln) if cur is not None else None
        if not mi:
            continue
        name, rest = mi.groups()
        call = _after_shape(rest)
        on = re.search(r'op_name="([^"]*)"', ln)
        cur["ins"][name] = {
            "op": call.split("(", 1)[0], "args": _operands(call),
            "calls": re.findall(r"calls=%([\w.\-]+)", ln),
            "name": on.group(1) if on else None, "line": ln}
        if ln.lstrip().startswith("ROOT "):
            cur["root"] = name
    return comps


def _root_names(comps: dict, comp: str, depth: int = 0) -> list[str]:
    """The nearest program paths in a fused computation: from its root
    through operands, then inside the computations those call."""
    c = comps[comp]
    todo, seen, out, calls = [c["root"]], set(), [], []
    while todo:
        n = todo.pop(0)
        if n in seen or n not in c["ins"]:
            continue
        seen.add(n)
        ins = c["ins"][n]
        if _program(ins["name"]):
            out.append(ins["name"])
            continue
        todo += ins["args"]
        calls += [sub for sub in ins["calls"] if sub in comps]
    for sub in calls if not out and depth < 4 else []:
        out += _root_names(comps, sub, depth + 1)
    return out


def _program(name: str | None) -> bool:
    """A path the program wrote starts at its jit; the compiler names what
    it makes after an argument, another instruction or a reducer's op."""
    return bool(name) and name.startswith("jit(")


def _executed(comps: dict) -> list[str]:
    """The entry computation and the bodies of its control flow (fused
    computations are part of their fusion)."""
    todo = [c for c, v in comps.items() if v["entry"]]
    seen: list[str] = []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.append(c)
        for ins in comps[c]["ins"].values():
            if ins["op"] not in ("while", "conditional", "call"):
                continue
            for key in ("body", "condition", "true_computation",
                        "false_computation", "to_apply"):
                todo += re.findall(key + r"=%([\w.\-]+)", ins["line"])
            m = re.search(r"branch_computations=\{([^}]*)\}", ins["line"])
            if m:
                todo += [x.strip().lstrip("%") for x in m.group(1).split(",")]
    return seen


def audit(text: str) -> dict:
    """Per instruction that does work: the phases its scope path holds.

    Returns ``{"ops": n, "compiler_made": {opcode: n}, "phases": {phase:
    n}, "bad": [(opcode, op_names, phases)]}``; ``bad`` lists every
    instruction under no phase or under more than one.
    """
    comps = parse(text)
    out = {"ops": 0, "compiler_made": collections.Counter(),
           "phases": collections.Counter(), "bad": []}
    for c in _executed(comps):
        ins = comps[c]["ins"]
        const: dict[str, bool] = {}
        for name, i in ins.items():          # operands precede their users
            const[name] = i["op"] in ("constant", "iota") or (
                i["op"] not in NOT_CONSTANT
                and (bool(i["args"]) or i["op"] == "fusion")
                and all(const.get(a, False) for a in i["args"]))
        for name, i in ins.items():
            if i["op"] in NO_WORK or const[name]:
                continue
            out["ops"] += 1
            names = [i["name"]] if _program(i["name"]) else [
                n for sub in i["calls"] for n in _root_names(comps, sub)]
            if not names:
                out["compiler_made"][i["op"]] += 1
                continue
            found = set().union(*(phases_of(n) for n in names))
            if len(found) == 1:
                out["phases"][found.pop()] += 1
            else:
                out["bad"].append((i["op"], names[:2], sorted(found)))
    out["compiler_made"] = dict(out["compiler_made"])
    out["phases"] = dict(out["phases"])
    return out
