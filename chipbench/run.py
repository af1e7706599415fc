"""One run of one cell of the benchmark of record.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: finds the TPU (or exits non-zero naming why; there
is no CPU arm), builds model, plan, loader and ``Trainer`` from the cell's
files, hands the Trainer weights made from the seed, and calls ``fit()``.
Inside that one ``fit()`` a callback drives the phases: the first three
steps (read step by step, for the correctness check), warm-up windows, the
measured span, stop.  After the span the configuration's plain reference
follows the same three steps and ``chipbench.correct`` compares.  The last
line of stdout is the result object; everything else worth reading is on
earlier lines, each tagged ``[chipbench]``.

``--rehearsal`` runs the cell's ``rehearsal`` sizes on the CPU, to debug the
harness without the chip; it never prints the result object.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)

#: steps the reference follows, each read on its own before warm-up
FIRST_STEPS = 3


class Refused(Exception):
    """The run cannot be made here; exit non-zero and print no result."""


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


# -- the cell's files ----------------------------------------------------
def load_cell(workload: str, rehearsal: bool) -> dict:
    with open(os.path.join(_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(_ROOT, conf["file"])) as f:
        cfg = json.load(f)
    if rehearsal:
        cfg = _merge(cfg, cfg.get("rehearsal", {}))
    from chipbench.traffic import generator

    mix = generator.load_mix(cell["traffic"])
    return {"bench": bench, "cell": cell, "cfg": cfg, "mix": mix}


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def metric_names(bench: dict, kind: str, workload: str) -> list[str]:
    return [m["name"] for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def device_peaks(kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise Refused(f"no published peaks for device_kind {kind!r} in chipbench/peaks.json")
    return peaks[kind]


# -- the callback that drives the phases inside fit() --------------------
def make_probe(*, trainer_mod, log_interval: int, warmup_windows: int, seconds: float,
               trace_windows: int, trace_dir: str | None, host_tracer_level: int,
               first_moment, probe_leaves, registry):
    """Build the Trainer callback.  Phases: first -> warmup -> measure -> done."""
    import jax
    import jax.numpy as jnp

    from chipbench import correct

    copy = jax.jit(lambda x: x + 0)
    norms = jax.jit(correct.leaf_norms)
    dnorms = jax.jit(correct.diff_norms)
    pick = jax.jit(lambda tree: correct.pick_leaves(tree, probe_leaves))
    spans = ("span/train/data_wait", "span/train/step", "span/train/host_block",
             "span/data/assemble", "span/data/h2d", "span/compile/wait")
    counters = ("compile/backend_compiles", "compile/cache_hits", "compile/cache_misses",
                "compile/recompiles")

    def snapshot():
        return {
            "hist": {n: (registry.histogram(n).total, registry.histogram(n).count)
                     for n in spans},
            "count": {n: registry.counter(n).value for n in counters},
        }

    class Probe(trainer_mod.Callback):
        def __init__(self):
            self.phase = "first"
            self.first_losses: list[float] = []
            self.grad_norms = self.update_norms = self.grad_probes = None
            self.dispatched: dict[int, float] = {}   # step -> host time its dispatch returned
            self.p0 = None   # the seeded weights, kept until the third step
            self.closes: list[float] = []        # drain times of the measured span
            self.window_losses: list[float] = []
            self.completions: dict[int, float] = {}   # step -> host time it completed
            self.marks: dict[str, float] = {}
            self.snap0 = self.snap1 = None
            self.warm_left = warmup_windows + 1  # +1: the ragged window after the first steps
            self.steps_at_start = 0
            self.steps_at_end = 0
            self._watch: queue.SimpleQueue = queue.SimpleQueue()
            self._watcher = threading.Thread(target=self._watch_loop, daemon=True,
                                             name="chipbench-completions")
            self._ann = None
            self.tracing = False
            self.trace_steps = 0
            self._trace_left = 0

        def _watch_loop(self):
            # every step's completion, seen from a thread of its own: the
            # training loop never blocks for a reading, so the dispatch queue
            # is as deep as the Trainer alone would keep it
            while True:
                item = self._watch.get()
                if item is None:
                    return
                n, done = item
                done.block_until_ready()
                self.completions[n] = time.perf_counter()

        # host spans on the profiler's clock, around the Trainer's own phases
        def _open(self, name):
            if self.tracing:
                self._close()
                self._ann = jax.profiler.TraceAnnotation(name)
                self._ann.__enter__()

        def _close(self):
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None

        def on_step_start(self, trainer):
            self._open("chipbench/dispatch")

        def on_step_end(self, trainer):
            n = trainer.batches_seen
            if self.phase == "first":
                if n == 1:
                    self.marks["first_step_dispatched"] = time.perf_counter()
                    moment = first_moment(trainer.state.opt_state)
                    self.grad_norms = norms(moment)
                    self.grad_probes = pick(moment)
                if n == FIRST_STEPS:
                    self.update_norms = dnorms(trainer.state.params, self.p0)
                    self.p0 = None
                return
            if self.phase == "measure":
                self.dispatched[n] = time.perf_counter()
            if self.phase in ("warmup", "measure"):
                # a copy of the step counter is ready the moment step n is done;
                # the state itself is donated to step n+1 and cannot be held
                self._watch.put((n, copy(trainer.state.step)))
            drains = n % trainer.log_interval == 0
            self._open("chipbench/host_block" if drains else "chipbench/data_wait")

        def on_batch_end(self, trainer, metrics):
            now = time.perf_counter()
            count = float(metrics.get("count", 0.0))
            loss = float(metrics.get("loss_sum", float("nan"))) / count if count else float("nan")
            if self.phase == "first":
                self.first_losses.append(loss)
                if len(self.first_losses) == FIRST_STEPS:
                    self.marks["first_steps_done"] = now
                    trainer.log_interval = log_interval
                    self.phase = "warmup"
                    # nothing the harness can stall on is left for the span:
                    # a collection of this heap holds the GIL for ~0.1 s
                    gc.collect()
                    gc.freeze()
                    gc.disable()
                    self._watcher.start()
                return
            if self.phase == "warmup":
                self.warm_left -= 1
                if self.warm_left == 0:
                    self._start(trainer)
            elif self.phase == "measure":
                self.closes.append(now)
                self.window_losses.append(loss)
                if now - self.closes[0] >= seconds:
                    self.snap1 = snapshot()
                    self.steps_at_end = trainer.batches_seen
                    gc.enable()
                    if not trace_dir:
                        return self._stop(trainer)
                    # the traced windows come after the span, so that neither the
                    # profiler's start nor its stop stalls a window that is measured
                    self.phase = "trace"
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0  # device ops and our own spans only
                    options.host_tracer_level = host_tracer_level
                    jax.profiler.start_trace(trace_dir, profiler_options=options)
                    self.tracing = True
                    # one window more than is reduced, for the profiler's own
                    # start-up; the reducer counts it out, and every pause in
                    # the windows after it reads as idle
                    self._trace_left = trace_windows + 1
            elif self.phase == "trace":
                self.window_losses.append(loss)
                self._trace_left -= 1
                if self._trace_left == 0:
                    self.trace_steps = trace_windows * trainer.log_interval
                    self._close()
                    jax.profiler.stop_trace()
                    self.tracing = False
                    return self._stop(trainer)
            self._open("chipbench/data_wait")

        def _stop(self, trainer):
            self.phase = "done"
            trainer.request_stop("chipbench: measured span complete")

        def _start(self, trainer):
            self.phase = "measure"
            self.steps_at_start = trainer.batches_seen
            self.snap0 = snapshot()
            now = time.perf_counter()
            self.marks["measure_start"] = now
            self.closes.append(now)

        def on_fit_end(self, trainer):
            self._close()
            if self._watcher.is_alive():
                self._watch.put(None)
                self._watcher.join(timeout=60)
            if self.tracing:  # fit ended early: do not leave the profiler running
                jax.profiler.stop_trace()
                self.tracing = False
            gc.enable()

    return Probe()


def find_first_moment(opt_state):
    """The optimizer's first-moment tree (optax ``TraceState.trace`` or
    ``ScaleByAdamState.mu``): after one step it is the first gradient as
    the optimizer got it, up to ``optim.first_moment_scale``."""
    import jax

    def holds(x):
        return hasattr(x, "_fields") and ("trace" in x._fields or "mu" in x._fields)

    for h in jax.tree.leaves(opt_state, is_leaf=holds):
        if holds(h):
            return h.trace if "trace" in h._fields else h.mu
    raise Refused("no first moment (trace/mu) in the optimizer state")


# -- one run -------------------------------------------------------------
def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearsal: bool = False, control: bool = False,
             out_dir: str | None = None) -> dict:
    """Drive one run; returns the result object (and ``extras`` for tests)."""
    spec = load_cell(workload, rehearsal)
    bench, cell, cfg, mix = spec["bench"], spec["cell"], spec["cfg"], spec["mix"]
    chips = int(cell["chips"])
    split: dict[str, float] = {}
    t_mark = _T0

    def lap(name: str) -> None:
        nonlocal t_mark
        now = time.perf_counter()
        split[name] = now - t_mark
        t_mark = now

    try:
        import tpuframe  # noqa: F401
    except ImportError as e:
        raise Refused(f"tpuframe is not importable from {_ROOT} ({e}): run from the root "
                      "of a checkout that holds the program") from None
    # libtpu would write its logs under a fixed /tmp path; nothing of a run
    # belongs outside its checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearsal:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_force_host_platform_device_count={chips}")
        os.environ["TPUFRAME_PALLAS_INTERPRET"] = "1"
    import jax

    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    try:
        backend = jax.default_backend()
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"no accelerator: jax could not start a backend: {e}") from None
    if not rehearsal and backend != "tpu":
        raise Refused(f"no accelerator: jax.default_backend() is {backend!r}; the benchmark "
                      "has no CPU arm (--rehearsal debugs the harness and prints no result)")
    if len(devices) != chips:
        raise Refused(f"cell {workload!r} asks for {chips} chip(s) and jax sees {len(devices)}")
    kind = devices[0].device_kind
    peaks = None if rehearsal else device_peaks(kind)
    lap("import_backend")

    import jax.numpy as jnp
    import numpy as np

    from chipbench import correct, reduce_trace, windows
    from chipbench.reference import optim
    from chipbench.traffic import generator
    from tpuframe import core, models
    from tpuframe.data import DataLoader
    from tpuframe.parallel import ParallelPlan
    from tpuframe.track import telemetry
    from tpuframe.train import Trainer
    from tpuframe.train import callbacks as trainer_callbacks

    tele = telemetry.get_telemetry()
    out_dir = out_dir or os.path.join(_ROOT, "chiprun_out", "chipbench", workload)
    t0 = time.perf_counter()
    rt = core.initialize()
    runtime_init_s = time.perf_counter() - t0
    plan = ParallelPlan(mesh=rt.mesh)
    lap("initialize")

    global_batch = int(cfg["per_chip_batch"]) * chips
    dataset = generator.make_dataset(mix, cfg, seed, global_batch)
    loader = DataLoader(dataset, batch_size=global_batch, shuffle=False, **mix["loader"])
    lap("data")

    mk = dict(cfg["model"]["kwargs"])
    for k in list(mk):
        if k.endswith("dtype") and isinstance(mk[k], str):
            mk[k] = jnp.dtype(mk[k])
    tr = cfg["trainer"]
    norm = tr.get("normalize")
    ref = correct.load_by_name("reference", cfg["name"])
    log_interval = int(mix["log_interval"])
    # the raw trace is tens of MB: it lives beside the compile cache, is
    # reduced, and is removed; only a sample and a description stay in out_dir
    trace_dir = os.path.join(_ROOT, ".cache", "chipbench", workload, "trace") if trace else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    probe = make_probe(
        trainer_mod=trainer_callbacks, log_interval=log_interval,
        warmup_windows=int(mix["warmup_windows"]), seconds=float(seconds),
        trace_windows=int(mix["trace_windows"]), trace_dir=trace_dir,
        # 0 where the host tracer itself slows the traffic (it makes every
        # 38 MB image batch take 2 s to linearize); then gaps go unattributed
        host_tracer_level=int(mix.get("trace_host_level", 1)),
        first_moment=find_first_moment,
        probe_leaves=tuple(cfg["probe_leaves"]), registry=tele.registry)
    trainer = Trainer(
        getattr(models, cfg["model"]["class"])(**mk),
        train_dataloader=loader, optimizer=tr["optimizer"], lr=tr["lr"],
        max_duration=f"{generator.STEPS_PER_EPOCH}ba", precision=tr["precision"],
        normalize=(tuple(norm["mean"]), tuple(norm["std"])) if norm else None,
        plan=plan, callbacks=[probe], log_interval=1, eval_interval=0,
        # the Trainer's own seed only feeds its initialiser (replaced below)
        # and dropout (none); it is baked into that initialiser's program as a
        # constant, so a seed that changed would recompile it in every run
        seed=0,
    )
    state = trainer.init_state()
    # the benchmark's weights, not the program's initialiser: the reference
    # is given the same ones and takes nothing the program made
    shapes = ref.param_shapes(cfg)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), state.params)
    have = jax.tree.map(lambda s: (tuple(s[0]), "float32"), shapes, is_leaf=correct._is_spec)
    if have != want:
        raise Refused("the reference's parameter tree does not match the program's")
    # one jitted generator, run twice: a copy for the program to train (its
    # step donates it) and the weights the change after three steps is taken from
    shardings = jax.tree.map(lambda like: like.sharding, state.params)
    trainer.state = state.replace(params=correct.init_params(shapes, seed, shardings))
    probe.p0 = correct.init_params(shapes, seed, shardings)
    del state
    n_params = sum(int(x.size) for x in jax.tree.leaves(probe.p0))
    jax.block_until_ready(trainer.state.params)
    lap("state_init")

    say(f"run workload={workload} config={cfg['name']} traffic={cell['traffic']} chips={chips} "
        f"seed={seed} seconds={seconds} trace={int(trace)} global_batch={global_batch} "
        f"log_interval={log_interval} params={n_params} device_kind={kind!r}"
        + (" REHEARSAL on cpu" if rehearsal else ""))
    t_fit = time.perf_counter()
    result = trainer.fit()
    t_end = time.perf_counter()
    if result.error is not None or probe.phase != "done":
        raise Refused(f"fit() ended in phase {probe.phase!r} before the span was complete "
                      f"(stopped_reason={result.stopped_reason!r})")

    # -- set-up split (everything before the measured span) ---------------
    split["compile_first_step"] = probe.marks["first_step_dispatched"] - t_fit
    split["first_steps"] = probe.marks["first_steps_done"] - probe.marks["first_step_dispatched"]
    split["warmup"] = probe.marks["measure_start"] - probe.marks["first_steps_done"]
    setup_s = probe.marks["measure_start"] - _T0
    say("setup_split_s " + json.dumps({k: round(v, 3) for k, v in split.items()})
        + f" setup_s={setup_s:.3f}")

    # -- the measured span -------------------------------------------------
    closes = probe.closes
    steps = probe.steps_at_end - probe.steps_at_start
    span_s = closes[-1] - closes[0]
    per_window = global_batch * log_interval
    rates = windows.window_rates(closes, per_window, chips)
    # the run's rate is all samples of the span over all of its time, so a
    # stall anywhere in the span lowers it; the median window (the first after
    # warm-up left out) stands beside it as trainer.window_rate_median, and
    # the two apart say that something stalled (PERF.md section 2)
    rate = windows.overall_rate(closes, per_window, chips)
    rate_median = windows.median(rates[1:] or rates)
    say(f"window_rates samples/s/chip n={len(rates)} " + " ".join(f"{r:.2f}" for r in rates))
    say(f"span steps={steps} span_s={span_s:.4f} rate_all_time={rate:.4f} "
        f"rate_window_median={rate_median:.4f} "
        f"first_window={rates[0]:.2f} min={min(rates):.2f} max={max(rates):.2f}")
    done_at = [probe.completions[n] for n in range(probe.steps_at_start, probe.steps_at_end + 1)
               if n in probe.completions]
    intervals = [1e3 * (b - a) for a, b in zip(done_at, done_at[1:])]
    if intervals:
        say(f"step_intervals_ms n={len(intervals)} median={windows.median(intervals):.3f} "
            f"p90={windows.percentile(intervals, 90):.3f} p99={windows.percentile(intervals, 99):.3f} "
            f"max={max(intervals):.3f}")
        slow = sorted(range(len(intervals)), key=lambda i: -intervals[i])[:3]
        first = probe.steps_at_start + 1
        say("slowest_steps " + "; ".join(
            f"step+{i + 1} {intervals[i]:.1f}ms queued_ahead_ms="
            f"{1e3 * (done_at[i] - probe.dispatched.get(first + i, float('nan'))):.1f}"
            for i in slow) + " (queued_ahead > 0: the step was already dispatched when the "
            "one before it completed, so the wait was not for the host's dispatch)")
    d_hist = {n: (probe.snap1["hist"][n][0] - probe.snap0["hist"][n][0],
                  probe.snap1["hist"][n][1] - probe.snap0["hist"][n][1])
              for n in probe.snap0["hist"]}
    d_count = {n: probe.snap1["count"][n] - probe.snap0["count"][n] for n in probe.snap0["count"]}
    say("span_spans_s " + json.dumps({n: round(v[0], 4) for n, v in d_hist.items()})
        + " counters " + json.dumps(d_count))

    failed = 0
    problems: list[str] = []
    bad_windows = sum(1 for v in probe.window_losses if not math.isfinite(v))
    if bad_windows:
        failed += bad_windows * log_interval
        problems.append(f"{bad_windows} window(s) with a non-finite loss")
    if d_count["compile/backend_compiles"] or d_count["compile/recompiles"]:
        failed = steps
        problems.append(f"compiles inside the span: {d_count}")
    step_now = int(jax.device_get(trainer.state.step))
    if step_now != trainer.batches_seen:
        failed = steps
        problems.append(f"step counter read back {step_now}, dispatched {trainer.batches_seen}")

    # -- memory: the compiled train step, and what the allocator saw --------
    report = trainer.precompile(wait=True) or {}
    train_entry = next((s for s in report.get("steps", []) if s.get("kind") == "train"), {})
    ma = _train_step_executable(trainer, loader).memory_analysis()
    state_bytes = sum(x.addressable_shards[0].data.nbytes
                      for x in jax.tree.leaves(trainer.state) if isinstance(x, jax.Array))
    # a persistent-cache hit reports alias 0; the step donates its whole state
    alias = ma.alias_size_in_bytes or min(state_bytes, ma.output_size_in_bytes)
    program_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes - alias
                     + ma.temp_size_in_bytes)
    stats_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices),
                     default=0)
    peak_bytes = max(program_bytes, stats_peak)
    say(f"memory per device: arguments={ma.argument_size_in_bytes} outputs={ma.output_size_in_bytes} "
        f"alias={ma.alias_size_in_bytes} (used {alias}) temporaries={ma.temp_size_in_bytes} "
        f"program={program_bytes} memory_stats_peak={stats_peak} state={state_bytes}")

    # -- correct: the reference follows the first three steps ---------------
    program = jax.device_get({"grad": probe.grad_norms, "update": probe.update_norms})
    g_scale = optim.first_moment_scale(cfg["optimizer"])
    probes = {k: v * g_scale for k, v in probe.grad_probes.items()}
    program = {"loss": probe.first_losses,
               "grad": {k: float(v) * g_scale for k, v in program["grad"].items()},
               "update": {k: float(v) for k, v in program["update"].items()}}
    trainer.state = None
    del probe.grad_norms, probe.update_norms, probe.grad_probes
    t_chk = time.perf_counter()
    batches = dataset.first_batches(FIRST_STEPS, global_batch)
    with jax.default_matmul_precision("highest"):
        reference = correct.follow_reference(ref, cfg, seed, batches)
        ref_kept = reference.pop("_kept")
        program["grad_diff"] = correct.rel_diff(probes, ref_kept)
        ctrl = None
        if control:
            # the control stands in the program's place, number for number
            ctrl = correct.follow_reference(ref, cfg, seed, batches, correct.control_wrap)
            ctrl["grad_diff"] = correct.rel_diff(ctrl.pop("_kept"), ref_kept)
        del ref_kept, probes
    limits = cfg["tolerance_rehearsal" if rehearsal else "tolerance"]
    ok, rows = correct.compare(program, reference, limits)
    for r in rows:
        say(f"correct {r['number']}={r['value']:.6g} limit={r['limit']:.6g} "
            f"{'ok' if r['ok'] else 'OUTSIDE'} ({r['at']})")
    say("correct losses program=" + " ".join(f"{v:.6f}" for v in program["loss"])
        + " reference=" + " ".join(f"{v:.6f}" for v in reference["loss"])
        + f" check_s={time.perf_counter() - t_chk:.2f}")
    ctrl_rows = None
    if ctrl is not None:
        ctrl_ok, ctrl_rows = correct.compare(ctrl, reference, limits)
        for r in ctrl_rows:
            say(f"control {r['number']}={r['value']:.6g} limit={r['limit']:.6g} "
                f"{'ok' if r['ok'] else 'OUTSIDE'} ({r['at']})")
        say(f"control correct={ctrl_ok} (must be False)")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, f"leaves_{seed}.json"), "w") as f:
            json.dump({"program": program, "reference": reference, "control": ctrl}, f)
    if problems:
        say("problems: " + "; ".join(problems))
    correct_flag = bool(ok and not problems)

    # -- metrics -----------------------------------------------------------
    ctx = {
        "cell": cell, "cfg": cfg, "mix": mix, "chips": chips, "peaks": peaks,
        "global_batch": global_batch, "steps": steps, "span_s": span_s,
        "rate": rate, "rate_window_median": rate_median, "window_rates": rates,
        "intervals_ms": intervals,
        "spans": d_hist, "counters": d_count, "runtime_init_s": runtime_init_s,
        "precompile": train_entry, "precompile_wall_s": report.get("wall_s"),
        "compile_first_step_s": split["compile_first_step"],
        "memory": {"temp": ma.temp_size_in_bytes, "program": program_bytes, "peak": peak_bytes},
        "trace": None,
    }
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    out = {"correct": correct_flag, "attempted": int(steps), "failed": int(min(failed, steps)),
           "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    if trace:
        try:
            reduced = reduce_trace.reduce_dir(trace_dir, out_dir, steps=probe.trace_steps)
            if reduced["planes"] != chips:
                raise ValueError(f"the trace holds {reduced['planes']} device plane(s), "
                                 f"the cell runs on {chips} chip(s)")
        except ValueError as e:
            # a trace that does not hold the steps the run made is no reading:
            # no result, and a code other than 0 (the CPU has no device plane)
            if not rehearsal:
                raise Refused(f"the trace cannot be reduced: {e}") from None
            say(f"rehearsal: {e}")
            reduced = {"busy_s": 0.0, "window_s": 0.0, "breakdown": {}, "planes": 0}
        else:
            ctx["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = reduced["breakdown"]
        say(f"trace steps={reduced.get('steps', 0)} window_s={reduced['window_s']:.4f} "
            f"busy_s={reduced['busy_s']:.4f} planes={reduced['planes']}")
        reduce_trace.write_sample(reduced, os.path.join(out_dir, "trace_sample.json.gz"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for name in metric_names(bench, "per_layer", workload):
            value = correct.load_by_name("layer_metrics", name).read(ctx)
            if value is not None:
                out["metrics"][name] = {"value": float(value), "unit": units[name]}
    else:
        e2e = {
            "samples_per_s_chip": rate,
            "step_ms_p90": windows.percentile(intervals, 90) if intervals else None,
            "peak_hbm_gib": peak_bytes / 2**30,
            "setup_s": setup_s,
        }
        for name in metric_names(bench, "end_to_end", workload):
            if e2e.get(name) is not None:
                out["metrics"][name] = {"value": float(e2e[name]), "unit": units[name]}
    say(f"total_s={time.perf_counter() - _T0:.2f} fit_s={t_end - t_fit:.2f}")
    out["extras"] = {"rows": rows, "control_rows": ctrl_rows, "split": split,
                     "window_rates": rates, "program": program, "reference": reference}
    return out


def _train_step_executable(trainer, loader):
    """The compiled train step, for its ``memory_analysis()``.

    The program has no public way to it yet: its precompile report carries no
    executable, and its memory-plane records are keyed by a label that cells
    of one plan share.  So this is the one place where the harness reads the
    Trainer's own attributes, ``_compiled`` (the executable its precompile
    kept) and, where it kept none (labels of rank 2: its loader template
    assumes scalars), ``_train_step``, lowered at the batch the loader feeds;
    the persistent cache answers.  PERF.md section 7 asks the program for an
    accessor; a PR that renames either has to bring this function along."""
    import jax
    import numpy as np

    from tpuframe.compile import abstract_state, loader_batch_template

    kept = [c for (kind, _), c in getattr(trainer, "_compiled", {}).items() if kind == "train"]
    if kept:
        return kept[0]
    step = getattr(trainer, "_train_step", None)
    step = getattr(step, "_inner_jit", step)
    if not hasattr(step, "lower"):
        raise Refused("the Trainer holds neither a compiled train step (_compiled) nor a jitted "
                      "one (_train_step): peak_hbm_gib cannot be read (chipbench/README.md)")
    # the batch as the loader assembles it: one sample's leaves under the
    # program's own names for them, the loader's batch size and transfer type
    names = list(loader_batch_template(trainer, train=True))
    base = trainer.plan.batch_sharding(leading_microbatch=False)
    batch = {}
    for i, (name, leaf) in enumerate(zip(names, loader.dataset[0])):
        leaf = np.asarray(leaf)
        shape = (loader.global_batch_size,) + leaf.shape
        dtype = (loader.transfer_dtype if i == 0 else None) or leaf.dtype
        spec = list(base.spec) + [None] * (len(shape) - len(base.spec))
        batch[name] = jax.ShapeDtypeStruct(
            shape, np.dtype(dtype),
            sharding=jax.sharding.NamedSharding(base.mesh, jax.sharding.PartitionSpec(*spec)))
    return step.lower(abstract_state(trainer.state), batch).compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run at the configuration's rehearsal sizes; prints no result")
    ap.add_argument("--control", action="store_true",
                    help="also run the reference in the precision below (fp8 operands) and "
                         "print its gaps: the limits must refuse it")
    args = ap.parse_args(argv)
    sys.path.insert(0, _ROOT)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                       rehearsal=args.rehearsal, control=args.control)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    out.pop("extras", None)
    if args.rehearsal:
        say("rehearsal complete: correct=%s attempted=%d failed=%d metrics=%s (no result object)"
            % (out["correct"], out["attempted"], out["failed"], sorted(out["metrics"])))
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
