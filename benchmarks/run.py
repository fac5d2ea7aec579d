"""One cell of the benchmark, in one process, on the machine it is started on.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, family, path and metrics are files
found by the names in ``BENCHMARK.json`` (``harness/spec.py``).  The system
is driven through its public entry points only.  Without a TPU, or with
another number of chips than the cell asks for, the run exits non-zero and
prints no result; ``--rehearsal`` is the ONLY way onto the CPU: it runs the
cell's labelled toy sizes, reports ``"cpu"`` as the device and prints
counts only, never a device metric.

The last stdout line is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced); earlier
lines are ``{"info": ...}`` notes.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # process start, for setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))     # the system under test
sys.path.insert(0, _HERE)                      # harness/

from harness import spec  # noqa: E402

CHECKOUT = spec.CHECKOUT
OUT_DIR = os.path.join(CHECKOUT, ".bench_out")       # git-ignored
TRACE_SECONDS_CAP = 6.0     # the profiler covers a few steps, not the run
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def note(**doc) -> None:
    print(json.dumps({"info": doc}), flush=True)


def die(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


class Tracer:
    """The profiler over the first steps of a ``--trace 1`` window."""

    def __init__(self, trace_dir: str, max_steps: int):
        self.dir, self.max_steps = trace_dir, max_steps
        self._span = None
        self._t0 = 0.0

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # spans and device ops only
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._t0 = time.perf_counter()
        self._span = jax.profiler.TraceAnnotation("bench.traced_window")
        self._span.__enter__()

    def want_more(self, n_steps: int) -> bool:
        return (n_steps < self.max_steps
                and time.perf_counter() - self._t0 < TRACE_SECONDS_CAP)

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def make_tx(opt: dict):
    """``{"name": <an optax constructor>, <its keyword arguments>}``"""
    import optax
    make = getattr(optax, opt["name"], None)
    if not callable(make):
        raise spec.SpecError(f"optax has no optimizer {opt['name']!r}")
    return make(**{k: v for k, v in opt.items() if k != "name"})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="labelled CPU toy of the control flow; counts "
                         "only, never a device metric")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under .bench_out/")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    found = spec.resolve(bench, args.workload)
    cell, config, traffic = found["cell"], found["config"], found["traffic"]
    chips = int(cell["chips"])
    seconds = (args.seconds if args.seconds is not None
               else float(bench["run_seconds"]))
    if args.rehearsal:
        config, traffic = (spec.with_rehearsal(config),
                           spec.with_rehearsal(traffic))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()

    try:
        import jax
        import byteps_tpu as bps
        from byteps_tpu.comm.mesh import get_comm
        from byteps_tpu.common.config import Config
        from byteps_tpu.utils.compile_cache import enable_compile_cache
    except ImportError as e:
        die(f"the system under test is not importable from {CHECKOUT}: {e}")

    # The cache lives inside this checkout, at a fixed path, uncapped,
    # whatever the environment names: a run's programs must be found again
    # by the next run HERE and shared with no other checkout.  (PR 22: the
    # chip machines export a 192 MiB LRU cap under which one cell's ~90 MB
    # of executables evicted each other and every run compiled anew.)
    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        die(f"JAX found no backend: {e}")
    platform = devices[0].platform
    if not args.rehearsal and platform != "tpu":
        die(f"no TPU: JAX reports platform {platform!r}. There is no CPU "
            f"fallback; --rehearsal runs a labelled toy.")
    if len(devices) != chips:
        die(f"cell {args.workload} asks for {chips} chip(s); JAX reports "
            f"{len(devices)}")

    from harness import checks, xplane
    from harness.job import Spans, Watchdog, run_window
    from harness.peaks import peaks_for

    peaks = None if args.rehearsal else peaks_for(devices[0].device_kind)
    jax_compiles = [0]
    xla_cache = {"hits": 0, "misses": 0}
    phases = {"import_s": time.perf_counter() - _T0}

    def on_duration(event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            jax_compiles[0] += 1

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            xla_cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            xla_cache["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    def phase(name: str, since: float) -> float:
        now = time.perf_counter()
        phases[name] = now - since
        return now

    # ---- set-up: the system, the state, the programs, the warm-up --------
    overrides = traffic.get("config_overrides") or {}
    bps.init(Config(**overrides)) if overrides else bps.init()
    comm = get_comm()     # the mesh follows from Config (config_overrides)
    if comm.num_ranks != chips:
        die(f"mesh has {comm.num_ranks} ranks, the cell {chips} chips")

    family = spec.load_module("families", config["family"]).build(
        config, traffic)
    root = jax.random.PRNGKey(args.seed)
    param_key, data_key = jax.random.split(root)
    per = int(traffic["seqs_per_chip"])
    spans = Spans()
    job = types.SimpleNamespace(
        workload=args.workload, config=config, traffic=traffic,
        chips=chips, comm=comm, family=family, spans=spans,
        tx=make_tx(traffic["optimizer"]), param_key=param_key,
        batch_key=lambda i: jax.random.fold_in(data_key, i),
        seqs_per_chip=per, global_seqs=per * chips,
        tokens_per_step_per_chip=per * family.tokens_per_seq)
    dog = Watchdog()
    budget = float(traffic["step_budget_s"])
    dog.arm("set-up", 1100.0)
    t = phase("init_s", _T0 + phases["import_s"])
    runner = spec.load_module("paths", traffic["path"]).Runner(job)
    t = phase("build_s", t)
    warm_losses, first = runner.warmup()
    t = phase("warmup_s", t)
    hlo = runner.hlo_texts() if args.trace else []
    dog.disarm()
    cache_at_window = dict(xla_cache)

    # ---- the window --------------------------------------------------------
    tracer = None
    trace_dir = os.path.join(OUT_DIR, "trace", args.workload)
    if args.trace:
        tracer = Tracer(trace_dir, int(traffic["trace_steps"]))
    snap0 = bps.metrics_snapshot()
    span_mark = spans.mark()
    engine_steps = getattr(runner, "engine_steps", {})
    engine_step_mark = max(engine_steps, default=-1)
    compiles0 = jax_compiles[0]
    setup_s = time.perf_counter() - _T0
    window = run_window(runner.step, first, seconds, blocking=bool(args.trace),
                        run_ahead=int(traffic.get("run_ahead", 0)), dog=dog,
                        step_budget_s=budget, spans=spans, tracer=tracer)
    jax_compiles_in_window = jax_compiles[0] - compiles0
    snap1 = bps.metrics_snapshot()
    memory_peak = checks.peak_bytes(devices)

    # ---- correctness, outside the window and after the peak is read --------
    import numpy as np

    def host_loss(x) -> float:
        try:
            return float(x)
        except Exception:  # noqa: BLE001 — a step that died on the device
            return float("nan")

    losses = np.asarray([host_loss(x) for x in window.losses], np.float64)
    nonfinite = int(np.sum(~np.isfinite(losses)))
    verdict = {"losses_finite": nonfinite == 0 and len(losses) > 0}
    t = time.perf_counter()
    if not window.errors:
        dog.arm("post-window checks", 600.0)
        verdict.update(runner.checks())
    runner.free()
    bps.shutdown()
    t = phase("checks_s", t)
    dog.arm("reference", 900.0)
    try:
        want = checks.reference_losses(
            family, job.tx, param_key, job.batch_key, n_steps=3,
            global_seqs=job.global_seqs,
            micro=int(traffic["reference_microbatch"]), device=devices[0])
    except Exception as e:  # noqa: BLE001 — no reference, so not correct
        want = []
        window.errors.append(f"reference: {type(e).__name__}: {e}"[:400])
    dog.disarm()
    phase("reference_s", t)
    verdict["matches_reference"] = checks.losses_agree(warm_losses[:3], want)
    note(phases={k: round(v, 2) for k, v in phases.items()},
         setup_spans={k: round(sum(v), 2) for k, v in spans.seconds.items()
                      if k.startswith("bench.setup.")},
         xla_cache_in_setup=cache_at_window, xla_cache=xla_cache,
         memory_stats=devices[0].memory_stats())
    note(checks=verdict, warm_losses=warm_losses[:3], reference_losses=want,
         window_errors=window.errors, steps=window.completed,
         window_s=window.elapsed_s,
         step_samples=len(window.step_s or window.traced_step_s),
         final_loss=float(losses[-1]) if len(losses) else None,
         # when each iteration of the loop ended: drift inside the window
         step_end_s=[round(x, 3) for x in window.step_end_s])

    # ---- the line -----------------------------------------------------------
    run = types.SimpleNamespace(
        job=job, family=family, window=window, spans=spans,
        span_mark=span_mark, snap0=snap0, snap1=snap1, setup_s=setup_s,
        memory_peak_bytes=memory_peak, peaks=peaks,
        jax_compiles_in_window=jax_compiles_in_window,
        engine_steps=engine_steps, engine_step_mark=engine_step_mark,
        kernel_work=family.kernel_work(per), trace=None, reduced=None,
        mosaic={}, info={})
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    breakdown = None
    if args.rehearsal:
        device["rehearsal"] = True
    if args.trace and not args.rehearsal:
        try:
            run.trace = xplane.load(xplane.find_xplane(trace_dir))
            run.reduced = xplane.reduce(run.trace, window.traced_steps)
            for text in hlo:
                run.mosaic.update(xplane.mosaic_ops(text))
            device.update(busy_s=run.reduced["busy_s"],
                          window_s=run.reduced["window_s"])
            breakdown = {"device_ops": xplane.top_device_ops(run.trace),
                         "idle_gaps": xplane.idle_gaps(run.trace)}
        except (OSError, ValueError) as e:
            # an unreadable trace loses the trace's metrics, not the line
            run.trace = run.reduced = None
            note(trace_error=f"{type(e).__name__}: {e}"[:400])
    if not args.keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in spec.metrics_for(bench, section, args.workload):
        reader = spec.load_module(
            "layer_metrics" if args.trace else "end_to_end", entry["name"])
        if args.rehearsal and reader.SOURCE != "program_counter":
            continue            # a CPU run prints counts, nothing else
        value = reader.read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        else:
            # the driver refuses a line that lacks a metric BENCHMARK.json
            # lists for the cell: a metric of some cells names them under
            # "workloads"
            print(f"benchmark: {entry['name']} found nothing to read in "
                  f"{args.workload}", file=sys.stderr, flush=True)
    if run.info:
        note(**run.info)

    line = {"correct": bool(all(verdict.values())) and not window.errors,
            "attempted": window.attempted,
            "failed": window.failed + nonfinite,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
