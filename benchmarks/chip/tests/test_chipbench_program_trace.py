"""The program's own spans and scopes, read from a trace: the decoder on
hand-built files, the step window, and the five readers on hand-built
spans and ops."""
import types
from pathlib import Path

import pytest

from chipbench import program_trace as pt
from chipbench import trace as tr

FIXTURE = Path(__file__).parent / "data" / "ring_tiny.xplane.pb"
SOLO = Path(__file__).parent / "data" / "solo_scoped.xplane.pb"
READERS = ("optimizer_ms", "round_probe_ms", "round_exchange_ms",
           "dispatch_ms", "host_loop_ms")


def test_phase_of_scope_paths():
    assert pt.phase_of("jit(train_step)/vmap(local/optimizer)/add") == \
        "local/optimizer"
    assert pt.phase_of("jit(f)/local/grad/transpose(jvp())/dot") == \
        "local/grad"
    assert pt.phase_of("jit(consensus_step)/consensus/exchange/off1/"
                       "wire/decode/convert") == "consensus/exchange"
    assert pt.phase_of("jit(f)/consensus/probe/consensus/pack/x") is None
    assert pt.phase_of("jit(f)/consensus/penalty:add") == "consensus/penalty"
    assert pt.phase_of("jit(f)/le") is None
    assert pt.phase_of(None) is None


def _program(steps, spans, chips):
    return pt.ProgramTrace(steps=steps, spans=spans, chips=chips,
                           scoped_by="hlo_proto")


def _ctx(program, warmup=6, counted=4, h=2):
    return types.SimpleNamespace(
        program=program,
        run=types.SimpleNamespace(warmup=warmup, counted=counted, h=h))


# steps 6..9 are counted (warmup 6, counted 4, h 2): step 6 opened before
# the trace started, so its span is missing and its cycle (6, 7) drops
# out; step 5 is warm-up, step 10 the step that closed the window
STEPS = [(5, 0, 90), (7, 200, 300), (8, 300, 400), (9, 400, 500),
         (10, 500, 520)]
SPANS = [("train/dispatch", 5, 10),                      # step 5: not counted
         ("train/batch", 201, 203), ("train/dispatch", 203, 205),
         ("train/wait", 205, 280), ("round/sync", 282, 290),
         ("train/wait", 290, 295),                       # step 7's round
         ("train/batch", 301, 304), ("train/dispatch", 304, 310),
         ("train/wait", 310, 390),                       # step 8
         ("train/batch", 401, 402), ("train/dispatch", 402, 404),
         ("train/wait", 404, 480), ("round/sync", 481, 491),
         ("train/wait", 491, 496),                       # step 9's round
         ("train/dispatch", 501, 519)]                   # step 10
# per chip: (phase, start, self ns); one op under no phase, one after the
# last counted step
CHIPS = [[("local/optimizer", 210, 30), ("local/grad", 240, 50),
          ("consensus/probe", 283, 12), ("consensus/exchange", 286, 6),
          (None, 292, 100), ("local/optimizer", 510, 1000)],
         [("local/optimizer", 212, 50), ("consensus/probe", 284, 8),
          ("consensus/exchange", 287, 4)]]


def test_window_keeps_whole_cycles_of_counted_steps():
    p = _program(STEPS, SPANS, CHIPS)
    assert p.window(6, 4, 2) == [(8, 300, 400), (9, 400, 500)]
    assert p.window(6, 4, 1) == [(7, 200, 300), (8, 300, 400),
                                 (9, 400, 500)]
    assert p.end_ns(6, 4) == 500
    assert p.inside(p.window(6, 4, 2), ("train/dispatch", "round/")) == [
        6, 2 + 10]
    assert p.inside(p.window(6, 4, 2), ("train/wait",)) == [80, 76 + 5]


def test_phase_time_per_chip_and_the_scopeless_op():
    p = _program(STEPS, SPANS, CHIPS)
    assert p.phase_ns("local/optimizer", until_ns=500) == [30, 50]
    assert p.phase_ns("local/optimizer") == [1030, 50]
    # the op under no phase counts under none of them
    total = sum(sum(p.phase_ns(ph)) for ph in (
        "local/grad", "local/optimizer", "consensus/probe",
        "consensus/exchange", "consensus/pack", "consensus/fused_round",
        "consensus/penalty"))
    assert total == sum(own for chip in CHIPS for _, _, own in chip) - 100
    unscoped = _program(STEPS, SPANS, [[(None, 0, 5)]])
    assert unscoped.phase_ns("local/grad") is None


def test_readers_on_hand_built_spans_and_ops():
    ctx = _ctx(_program(STEPS, SPANS, CHIPS))
    got = {m: tr.load_reader(m)(ctx) for m in READERS}
    # 4 counted steps, 2 rounds; device times are means over the chips
    assert got["optimizer_ms"] == pytest.approx(1e-6 * (30 + 50) / 2 / 4)
    assert got["round_probe_ms"] == pytest.approx(1e-6 * (12 + 8) / 2 / 2)
    assert got["round_exchange_ms"] == pytest.approx(1e-6 * (6 + 4) / 2 / 2)
    # host: the window's whole cycle (8, 9)
    assert got["dispatch_ms"] == pytest.approx(1e-6 * (6 + 12) / 2)
    assert got["host_loop_ms"] == pytest.approx(
        1e-6 * ((100 - 80) + (100 - 81)) / 2)


def test_readers_find_nothing_in_a_program_without_spans_or_scopes():
    """The recorded ring trace is of a program older than the spans and
    the scopes: every reader returns None, and none raises."""
    program = pt.decode(FIXTURE)
    assert program.steps == [] and program.scoped_by == "none"
    assert len(program.chips) == 4
    ctx = _ctx(program, counted=4, h=2)
    assert {m: tr.load_reader(m)(ctx) for m in READERS} == dict.fromkeys(
        READERS)


def _hlo_proto() -> bytes:
    """One module: a scoped dot, and a fusion with no op_name whose fused
    root is a metadata-less bitcast of a scoped add."""
    m = pt._messages()
    proto = m["HloProto"]()
    mod = proto.hlo_module
    mod.name = "jit_train_step"
    fused = mod.computations.add(name="fused_computation", id=2, root_id=22)
    fused.instructions.add(name="param_0", opcode="parameter", id=20)
    add = fused.instructions.add(name="add.1", opcode="add", id=21,
                                 operand_ids=[20, 20])
    add.metadata.op_name = "jit(train_step)/vmap(local/optimizer)/add"
    fused.instructions.add(name="bitcast.1", opcode="bitcast", id=22,
                           operand_ids=[21])
    entry = mod.computations.add(name="main", id=1, root_id=12)
    dot = entry.instructions.add(name="dot.3", opcode="dot", id=10)
    dot.metadata.op_name = "jit(train_step)/vmap(local/grad)/jvp()/dot"
    entry.instructions.add(name="add_fusion", opcode="fusion", id=11,
                           called_computation_ids=[2], operand_ids=[10])
    entry.instructions.add(name="copy.4", opcode="copy", id=12,
                           operand_ids=[11])
    return proto.SerializeToString()


def test_hlo_scopes_of_a_proto():
    assert pt.hlo_scopes(_hlo_proto()) == {
        "param_0": "", "add.1": "jit(train_step)/vmap(local/optimizer)/add",
        "bitcast.1": "",
        "dot.3": "jit(train_step)/vmap(local/grad)/jvp()/dot",
        "add_fusion": "jit(train_step)/vmap(local/optimizer)/add",
        "copy.4": ""}


def test_hlo_scopes_skip_the_compilers_names():
    """A fusion whose root is a scatter: its reducer's ``add`` is the
    compiler's name, the scatter's operand holds the program's path."""
    m = pt._messages()
    proto = m["HloProto"]()
    mod = proto.hlo_module
    region = mod.computations.add(name="region", id=3, root_id=31)
    region.instructions.add(name="add.89", opcode="add", id=31)
    region.instructions[0].metadata.op_name = "add"
    fused = mod.computations.add(name="fused", id=2, root_id=21)
    fused.instructions.add(name="param_0", opcode="parameter", id=20)
    grad = fused.instructions.add(name="reshape.5", opcode="reshape", id=22,
                                  operand_ids=[20])
    grad.metadata.op_name = "jit(f)/vmap(local/grad)/transpose(jvp())/while"
    fused.instructions.add(name="scatter.4", opcode="scatter", id=21,
                           operand_ids=[20, 22],
                           called_computation_ids=[3])
    entry = mod.computations.add(name="main", id=1, root_id=10)
    top = entry.instructions.add(name="fusion.2", opcode="fusion", id=10,
                                 called_computation_ids=[2])
    top.metadata.op_name = "add"
    got = pt.hlo_scopes(proto.SerializeToString())
    assert got["fusion.2"] == grad.metadata.op_name
    assert got["add.89"] == ""
    assert pt.program_path("add") is None


def test_module_table_by_name_else_by_its_instructions():
    a, b = {"fusion.1": "x", "dot.2": "y"}, {"fusion.1": "z", "copy.9": "w"}
    protos = {"jit_train_step(1)": a, "jit_train_step(2)": b}
    bases = {"jit_train_step": [a, b]}
    assert pt._table("jit_train_step(2)", protos, bases, []) is b
    assert pt._table("jit_train_step(77)", protos, bases,
                     ["fusion.1", "dot.2"]) is a
    assert pt._table("jit_other(1)", protos, bases, ["dot.2"]) is None


def _space(tf_op: bool) -> bytes:
    """A trace as the profiler writes one: a device plane with a module
    and its ops, the metadata plane with the module's HLO proto, and a
    host thread with a step's spans."""
    m = pt._messages()
    space = m["XSpace"]()
    meta = space.planes.add(name=pt.METADATA_PLANE)
    meta.stat_metadata.add(key=1).value.name = pt.HLO_PROTO_STAT
    md = meta.event_metadata.add(key=1).value
    md.id, md.name = 1, "jit_train_step(42)"
    md.stats.add(metadata_id=1, bytes_value=_hlo_proto())

    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=1).value.name = "tf_op"
    names = {1: "jit_train_step(42)",
             2: "%dot.3 = bf16[8] dot(...)",
             3: "%add_fusion = bf16[8] fusion(...), calls=%fused_computation",
             4: "%copy.4 = bf16[8] copy(%add_fusion)"}
    for k, n in names.items():
        e = dev.event_metadata.add(key=k).value
        e.id, e.name = k, n
    if tf_op:         # the scope as a stat of the op's metadata
        e = dev.event_metadata[1].value        # the dot's
        e.stats.add(metadata_id=1, str_value="jit(f)/consensus/probe/dot")
    mods = dev.lines.add(name=tr.MODULES_LINE, timestamp_ns=1000)
    mods.events.add(metadata_id=1, offset_ps=0, duration_ps=100_000)
    ops = dev.lines.add(name=tr.OPS_LINE, timestamp_ns=1000)
    for k, (t0, t1) in {2: (0, 40), 3: (40, 70), 4: (70, 80)}.items():
        ops.events.add(metadata_id=k, offset_ps=t0 * 1000,
                       duration_ps=(t1 - t0) * 1000)

    host = space.planes.add(name="/host:CPU")
    host.stat_metadata.add(key=1).value.name = "step_num"
    host.stat_metadata.add(key=2).value.name = "_r"
    for k, n in {1: "train/step", 2: "train/dispatch", 3: "bench/round",
                 4: "dispatch"}.items():
        e = host.event_metadata.add(key=k).value
        e.id, e.name = k, n
    thread = host.lines.add(name="python3", timestamp_ns=990)
    step = thread.events.add(metadata_id=1, offset_ps=0,
                             duration_ps=200_000)
    step.stats.add(metadata_id=1, int64_value=7)
    step.stats.add(metadata_id=2, int64_value=1)
    for k in (2, 3, 4):
        thread.events.add(metadata_id=k, offset_ps=5_000, duration_ps=1_000)
    return space.SerializeToString()


@pytest.mark.parametrize("tf_op", [False, True], ids=["hlo_proto", "tf_op"])
def test_decode_a_written_trace(tmp_path, tf_op):
    path = tmp_path / "host.xplane.pb"
    path.write_bytes(_space(tf_op))
    program = pt.decode(path)
    assert program.scoped_by == ("tf_op" if tf_op else "hlo_proto")
    assert program.steps == [(7, 990, 1190)]
    assert program.spans == [("train/dispatch", 995, 996)]
    dot_phase = "consensus/probe" if tf_op else "local/grad"
    assert program.chips == [[(dot_phase, 1000, 40),
                              ("local/optimizer", 1040, 30),
                              (None, 1070, 10)]]
    assert pt.load(tmp_path) == program          # decoded once, then kept
    assert pt.load(tmp_path) is pt.load(tmp_path)


def test_recorded_solo_trace_with_scopes():
    """Two steps of the solo cell's traced window, recorded on a v5e and
    cut to chip 0's modules and ops (their ``tf_op`` stats kept), the
    program's host spans and the step's HLO proto: the scopes come as
    ``tf_op`` stats, where one names a reducer the HLO proto answers, and
    the two local phases hold nearly all of the step."""
    program = pt.decode(SOLO)
    assert program.scoped_by == "tf_op"
    assert [s for s, _, _ in program.steps] == [10, 11]
    assert {n for n, _, _ in program.spans} == {
        "train/batch", "train/dispatch", "train/wait"}
    own = {}
    for phase, _, t in program.chips[0]:
        own[phase] = own.get(phase, 0) + t
    total = sum(own.values())
    assert set(own) == {"local/grad", "local/optimizer", None}
    assert own[None] < 0.01 * total
    assert 0.05 < own["local/optimizer"] / total < 0.2
    ctx = _ctx(program, warmup=10, counted=2, h=1)
    got = {m: tr.load_reader(m)(ctx) for m in READERS}
    assert got["optimizer_ms"] == pytest.approx(
        1e-6 * own["local/optimizer"] / 2)
    assert got["round_probe_ms"] is None and got["round_exchange_ms"] is None
    steps = program.window(10, 2, 1)
    assert got["host_loop_ms"] == pytest.approx(1e-6 * sum(
        b - a - w for (_, a, b), w in zip(
            steps, program.inside(steps, ("train/wait",)))) / 2)
    assert 0 < got["dispatch_ms"] < got["host_loop_ms"]
