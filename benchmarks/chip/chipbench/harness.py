"""One run of one cell: warm-up, the measured window, the comparison.

The window runs the launcher's own loop, ``repro.launch.train.main``. The
harness reaches into it only through the names ``main`` looks up in its
module: ``build_trainer`` (to put the benchmark's seeded weights in place
of the model's init and to see each consensus round), ``with_retries``
(to see each step start, end the window and keep the state) and
``SyntheticTokens`` (to serve the benchmark's own traffic). Step
completions are stamped from the launcher's ``step k ...`` lines. Only
the traffic file's settings reach the launcher; ``check_settings`` refuses
a trainer that runs what the reference does not model.
"""
from __future__ import annotations

import gc
import io
import json
import shutil
import statistics
import sys
import time
import traceback

import numpy as np

from chipbench import compare, flops, manifest, peaks
from chipbench.traffic import ZipfTokens

SPAN_BATCH = "bench/batch"
SPAN_DISPATCH = "bench/dispatch"
SPAN_READBACK = "bench/readback"
SPAN_ROUND = "bench/round"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_DIR = manifest.ROOT / ".bench_trace"


class NoChip(RuntimeError):
    pass


class WindowClosed(Exception):
    """Raised at the start of the first step after the window closed."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def mark(what: str, t_process: float) -> None:
    log(f"t+{time.perf_counter() - t_process:.3f} s: {what}")


class LineTap(io.TextIOBase):
    """Stands in for stdout while the launcher runs: stamps its step
    lines and copies everything to stderr, so that the result's line
    stays the last line of stdout."""

    def __init__(self, run: "Run"):
        self.run, self.buf = run, ""

    def write(self, s: str) -> int:
        self.buf += s
        while "\n" in self.buf:
            line, self.buf = self.buf.split("\n", 1)
            self.run.on_line(line)
            sys.stderr.write(line + "\n")
        return len(s)

    def flush(self) -> None:
        sys.stderr.flush()


class Span:
    """A host span, written into the profiler's trace when one runs."""

    def __init__(self):
        self.open = None

    def enter(self, name: str) -> None:
        import jax
        self.exit()
        self.open = jax.profiler.TraceAnnotation(name)
        self.open.__enter__()

    def exit(self) -> None:
        if self.open is not None:
            self.open.__exit__(None, None, None)
            self.open = None


class Source:
    """The launcher's batch source, serving the benchmark's traffic."""

    def __init__(self, run: "Run", gen: ZipfTokens):
        self.run, self.gen = run, gen

    def batch(self, step: int) -> dict:
        import jax
        import jax.numpy as jnp
        t0 = time.perf_counter()
        if step >= compare.PROBE_OFFSET:
            self.run.probe_steps.append(step)
        with jax.profiler.TraceAnnotation(SPAN_BATCH):
            b = self.gen.batch(step)
            out = {k: jnp.asarray(v) for k, v in b.items()}
        self.run.batch_s.append((t0, time.perf_counter() - t0))
        return out


class Run:
    def __init__(self, cell: dict, seed: int, seconds: int, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.mix, self.model = cell["traffic_mix"], cell["model"]
        self.nodes = self.mix["nodes"]
        self.h = self.mix["local_steps"] if self.nodes > 1 else 1
        self.warmup = self.mix["warmup_steps"]
        self.stamps: list[float] = []
        self.calls = 0
        self.losses = []              # device scalars of steps 0..2
        self.readings = {}            # program-side numbers, on device
        self.rounds = []              # what the first rounds left
        self.round_fn = None
        self.probe_steps: list[int] = []
        self.batch_s: list[tuple[float, float]] = []
        self.compiles: list[float] = []
        self.cache_hits = 0
        self.retries = 0
        self.window_t0 = None
        self.trainer = None
        self.span = Span()

    # ------------------------------------------------------------ hooks ----
    def on_line(self, line: str) -> None:
        if line.startswith("step "):
            self.span.exit()
            self.stamps.append(time.perf_counter())
        elif line.startswith("retry "):
            self.retries += 1

    def on_event(self, event: str, *args, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles.append(time.perf_counter())
            log(f"compiled {kw.get('fun_name', '?')} in "
                f"{args[0] if args else 0:.3f} s (hits so far "
                f"{self.cache_hits})")
        elif event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def wrap_step(self, step_fn):
        def step(state, batch):
            self.before_step(state)
            self.span.enter(SPAN_DISPATCH)
            new, m = step_fn(state, batch)
            self.span.enter(SPAN_READBACK)
            if self.calls <= 3:
                self.losses.append(m["loss"])
            return new, m
        return step

    def wrap_round(self, cons):
        def round_fn(state, probe):
            self.span.enter(SPAN_ROUND)
            new, cm = cons(state, probe)
            self.span.enter(SPAN_READBACK)
            if len(self.rounds) < compare.ROUNDS:
                self.rounds.append(self.round_readings(new, cm))
            return new, cm
        return round_fn

    def before_step(self, state) -> None:
        import jax
        k = self.calls
        self.calls += 1
        if k <= self.warmup:
            mark(f"step {k} starts", self.t_process)
        if k == 1:
            self.readings["grad"] = self.grad_norms(state.opt.m)
        elif k == 3:
            self.readings["update"] = self.update_norms(state.params)
        if k == self.warmup:
            if self.trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0      # keep the host path's pace
                jax.profiler.start_trace(str(TRACE_DIR),
                                         profiler_options=opts)
                self.window_t0 = time.perf_counter()
            else:
                self.window_t0 = self.stamps[-1]
            self.setup_end = self.window_t0
            self.compiles_before = len(self.compiles)
            return
        if k > self.warmup and (k - self.warmup) % self.h == 0:
            length = min(self.seconds, self.mix["trace_seconds"]) \
                if self.trace else self.seconds
            if self.stamps[-1] - self.window_t0 >= length:
                self.counted = k - self.warmup
                raise WindowClosed()

    # ------------------------------------------------- program readings ----
    def round_readings(self, new, cm) -> dict:
        """The round's metrics, [J, leaves] norms of the dual and of the
        neighbour mean's change from the initial weights (flat buffers,
        cut by the layout's leaf spans) and a copy of the penalties the
        round set. Waited for: the next round takes these buffers as
        donations."""
        import jax
        import jax.numpy as jnp
        from chipbench import weights
        spans = [(lf.offset, lf.size) for lf in self.layout.leaves]
        shapes = self.single_shapes

        def fn(lam, bar, eta, key):
            p0 = [x.reshape(1, -1).astype(jnp.float32) for x in
                  jax.tree_util.tree_leaves(weights.make(key, shapes))]
            zero = [0.0] * len(spans)
            norms = lambda b, base: jnp.stack([  # noqa: E731
                jnp.sqrt(jnp.sum(jnp.square(b[:, o:o + n] - x0), axis=1))
                for (o, n), x0 in zip(spans, base)], axis=1)
            return norms(lam, zero), norms(bar, p0), eta + 0.0
        if self.round_fn is None:
            self.round_fn = jax.jit(fn)
        lam, bar, eta = jax.block_until_ready(self.round_fn(
            new.lam, new.theta_bar_prev, new.penalty.eta,
            weights.base_key(self.seed)))
        out = {k: cm[k] for k in compare.ROUND_KEYS}
        out.update(lam=lam, bar=bar, eta=eta)
        return out

    def grad_norms(self, m):
        """[J, leaves] norms of the first gradient as AdamW took it."""
        import jax
        from chipbench import reference
        b1 = self.model["optimizer"]["b1"]
        fn = jax.jit(jax.vmap(lambda t: reference.leaf_norms(
            jax.tree_util.tree_map(lambda x: x / (1.0 - b1), t))))
        return fn(m)

    def update_norms(self, params):
        """[J, leaves] norms of the parameters' change after three steps."""
        import jax
        from chipbench import reference, weights
        shapes = self.single_shapes
        key = weights.base_key(self.seed)

        def fn(p, key):
            p0 = weights.make(key, shapes)
            return jax.vmap(lambda pj: reference.leaf_norms(
                jax.tree_util.tree_map(
                    lambda a, b: a.astype(np.float32) - b.astype(np.float32),
                    pj, p0)))(p)
        return jax.jit(fn)(params, key)


def _mesh_of(devices):
    """The launcher's local mesh, over the cell's chips only: for a host
    that holds more chips than the cell asks for."""
    import jax
    return jax.make_mesh((len(devices), 1, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3,
                         devices=devices)


def launcher_argv(cell: dict, seed: int, steps: int) -> list[str]:
    """The launcher's flags: the model's cut and the traffic's settings,
    the round's only where nodes exchange, then the traffic file's own
    ``launcher_flags``."""
    from chipbench import weights
    mix, model = cell["traffic_mix"], cell["model"]
    argv = ["--arch", model["repro_arch"], "--mesh", "local",
            "--n-layers", str(model["num_hidden_layers"]),
            "--vocab", str(model["vocab_size"]),
            "--seq", str(mix["seq_len"]),
            "--batch-per-node", str(mix["batch_per_node"]),
            "--lr", str(model["optimizer"]["lr"]),
            "--steps", str(steps),
            "--seed", str(weights.launcher_seed(seed))]
    if mix["nodes"] > 1:
        argv += ["--scheme", mix["scheme"], "--topology", mix["topology"],
                 "--local-steps", str(mix["local_steps"]),
                 "--eta0", str(mix["eta0"]),
                 "--wire-codec", mix["wire_codec"]]
    return argv + [str(a) for a in mix.get("launcher_flags", [])] \
        + (["--reduced"] if model.get("repro_reduced") else [])


def check_settings(run: Run, trainer) -> None:
    """The run is the configuration as stated, or it is no run."""
    from chipbench import reference
    opt, mix = run.model["optimizer"], run.mix
    got = {"lr": trainer.acfg.lr, "b1": trainer.acfg.b1,
           "b2": trainer.acfg.b2, "eps": trainer.acfg.eps,
           "weight_decay": trainer.acfg.weight_decay,
           "grad_clip": trainer.acfg.grad_clip,
           "nodes": trainer.num_nodes}
    want = {k: opt[k] for k in ("lr", "b1", "b2", "eps", "weight_decay",
                                "grad_clip")}
    want["nodes"] = run.nodes
    if run.nodes > 1:
        # what reference.ring_round and nap_first_eta model: a static ring
        # of three or more nodes, scheme nap with the first round within
        # its budget, the native wire, sync rounds
        pen, ccfg = trainer.ccfg.penalty, trainer.ccfg
        got.update(prox_step=ccfg.prox_step, local_steps=ccfg.local_steps,
                   scheme=pen.scheme, eta0=pen.eta0,
                   first_within_budget=pen.budget_init > 0,
                   wire=trainer.codec_name,
                   offsets=sorted(int(o) for o in trainer.offsets),
                   dynamic=trainer.dynamic,
                   asynchronous=trainer.async_cfg is not None)
        want.update(prox_step=mix["prox_step"],
                    local_steps=mix["local_steps"], scheme="nap",
                    eta0=mix["eta0"], first_within_budget=True,
                    wire="native",
                    offsets=[1, run.nodes - 1] if run.nodes > 2 else [],
                    dynamic=False, asynchronous=False)
    bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
    if trainer.acfg.factored:
        bad["factored"] = (True, False)
    import jax
    mine = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)),
                                  reference.param_shapes(
                                      reference.arch(run.model)))
    theirs = jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)),
                                    trainer.model.abstract_params())
    if mine != theirs:
        bad["params"] = ("program tree", "config tree")
    if bad:
        raise RuntimeError(f"the program does not run the configuration: "
                           f"{bad}")


def drive(run: Run, devices) -> None:
    """Run the launcher's main through the window; leaves the readings."""
    import jax
    import jax.monitoring
    import repro.launch.train as T
    from chipbench import weights

    names = ["build_trainer", "with_retries", "SyntheticTokens"]
    if len(jax.devices()) > len(devices):
        names.append("make_local_mesh")
    orig = {n: getattr(T, n) for n in names}

    def build_trainer(args):
        cfg, trainer = orig["build_trainer"](args)
        mark("trainer built", run.t_process)
        check_settings(run, trainer)
        run.trainer = trainer
        run.single_shapes = trainer.model.abstract_params()
        run.node_devices = {i: int(d.id) for i, d in
                            enumerate(trainer.mesh.devices.reshape(-1))}
        run.layout = trainer.layout
        run.round_bytes = flops.round_kernel_bytes(
            trainer.layout.total, len(trainer.offsets),
            np.dtype(trainer.layout.wire_dtype).itemsize,
            trainer.codec.wire_bytes())
        object.__setattr__(trainer.model, "init", lambda key: weights.make(
            key, run.single_shapes))
        jsf = trainer.jit_step_fns

        def jit_step_fns():
            step, cons = jsf()
            return step, run.wrap_round(cons)
        trainer.jit_step_fns = jit_step_fns
        return cfg, trainer

    T.build_trainer = build_trainer
    T.with_retries = lambda fn, policy: run.wrap_step(
        orig["with_retries"](fn, policy))
    T.SyntheticTokens = lambda dcfg: Source(run, ZipfTokens(
        run.mix, dcfg.vocab, dcfg.num_nodes, run.seed))
    if "make_local_mesh" in orig:
        T.make_local_mesh = lambda: _mesh_of(devices)
    jax.monitoring.register_event_listener(run.on_event)
    jax.monitoring.register_event_duration_secs_listener(run.on_event)
    out, sys.stdout = sys.stdout, LineTap(run)
    try:
        T.main(launcher_argv(run.cell, run.seed, 10 ** 9))
        raise RuntimeError("the launcher returned before the window closed")
    except WindowClosed:
        pass
    finally:
        sys.stdout = out
        run.span.exit()
        for n, f in orig.items():
            setattr(T, n, f)
    run.window_t1 = run.stamps[-1]
    if run.trace:
        jax.profiler.stop_trace()


def peak_bytes(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def device_info(devices) -> dict:
    peak = max(peak_bytes(devices))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": int(peak)}


def end_to_end(run: Run, t_process: float) -> dict:
    mix = run.mix
    tokens = run.nodes * mix["batch_per_node"] * mix["seq_len"] * run.counted
    window = run.window_t1 - run.window_t0
    stamps = run.stamps[run.warmup - 1:]
    steps_ms = [1e3 * (b - a) for a, b in zip(stamps, stamps[1:])]
    p95 = statistics.quantiles(steps_ms, n=20, method="inclusive")[-1]
    return {"tokens_per_s": tokens / window, "step_p95_ms": p95,
            "setup_s": run.setup_end - t_process}


def per_layer(run: Run, cell: dict) -> tuple[dict, dict, dict]:
    """(metrics, device additions, breakdown) of the traced window."""
    from chipbench import trace as trace_lib
    red = trace_lib.reduce(trace_lib.find_xplane(TRACE_DIR),
                           host_window=(run.window_t0, run.window_t1))
    ctx = trace_lib.Context(run=run, red=red)
    out = {}
    for m in cell["per_layer"]:
        try:
            value = trace_lib.load_reader(m["name"])(ctx)
        except Exception:                    # one reader's fault, reported
            log(f"reader {m['name']} failed:\n{traceback.format_exc()}")
            continue
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"busy_s": red.busy_s, "window_s": red.window_s}
    return out, dev, red.breakdown()


def free_program(run: Run) -> None:
    """Drop every reference to the program's state before the reference."""
    import jax
    run.trainer = None
    gc.collect()
    jax.clear_caches()
    gc.collect()


def main(argv=None, *, t_process: float) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = manifest.load()
    manifest.check_names(bench)
    cell = manifest.cell(bench, args.workload)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < cell["chips"]:
        raise NoChip(f"cell {args.workload} needs {cell['chips']} TPU "
                     f"chip(s); JAX found {len(devices)} device(s) of "
                     f"platform {platform!r}")
    devices = devices[:cell["chips"]]
    peaks.of(devices[0].device_kind)             # unknown kind: an error
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t_process)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell: dict, seed: int, seconds: int, trace: bool, devices,
             t_process: float, keep: dict | None = None) -> dict:
    """Warm-up, window, readings, reference, comparison: the result.
    ``keep``, where given, receives the program's and the reference's
    readings (``control.py``); a reference it already holds, of the same
    cell and seed, is used and not computed again (the fault tests)."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = Run(cell, seed, seconds, trace)
    run.device_kind, run.t_process = devices[0].device_kind, t_process
    mark("devices found", t_process)
    drive(run, devices)
    mark("window closed", t_process)
    dev = device_info(devices)
    log(f"peak_bytes_in_use per chip: "
        f"{peak_bytes(devices)}")
    log(f"compiles: {len(run.compiles)} backend (cache hits "
        f"{run.cache_hits}), in the window "
        f"{len(run.compiles) - run.compiles_before}")
    log(f"node -> device: {run.node_devices}")
    if trace:
        metrics, extra, breakdown = per_layer(run, cell)
        dev.update(extra)
    else:
        e2e = end_to_end(run, t_process)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    program = compare.program_readings(run)
    counted, retries = run.counted, run.retries
    free_program(run)
    t_ref = time.perf_counter()
    ref = (keep or {}).get("ref")
    if ref is None:
        ref = compare.reference_readings(cell, seed, devices)
    if keep is not None:
        keep.update(program=program, ref=ref)
    log(f"leaves left out of the gaps (reference gradient under "
        f"{compare.DEAD_LEAF} of the median leaf's): "
        f"{int((~compare.live_leaves(ref)).sum())}")
    for name, value in compare.numbers(program, ref).items():
        if cell["limits"][name] is None:
            log(f"read, not compared: {name} {value!r}")
    checks = compare.checks(program, ref, cell["limits"])
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": counted, "failed": retries,
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
