"""Weak-scaling evidence toward the v5e-256 north star (round-2 VERDICT
item 3).

Two parts, both runnable without TPU hardware:

1. **Measured weak scaling** over 1/2/4 real processes x 2 CPU devices
   each (the rendezvous pattern of tests/test_multiprocess.py): every
   process contributes a fixed-size gradient per step through the engine's
   hierarchical push_pull path — per-process work constant, total work
   grows with the process count.  Reported as median step time per
   process count and the 4-process weak-scaling efficiency t1/t4.
   (CPU "DCN" here is loopback shared memory; the point is that the
   *collective structure* — dcn=n_proc hierarchical RS/psum/AG — executes
   and how its cost grows, not absolute GB/s.)

2. **Analytic projection** for BERT-large DP on a v5e-256 pod from
   published hardware numbers and the framework's own measured single-chip
   step time (BENCH_TPU_MEASURED.json).  The wire-byte formula
   (ring all-reduce moves 2*M*(N-1)/N bytes per chip) is validated
   against the compiled HLO on the CPU mesh (utils/hlo_wire.py), then
   evaluated at N=256.  Assumptions are in the output — this is a model,
   not a measurement, and is labeled as such.

Usage:  python tools/weak_scaling.py            # orchestrate + print JSON
        python tools/weak_scaling.py --worker   # (internal) worker body
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

GRAD_BYTES = 4 * 1024 * 1024   # per-process contribution per step (f32)
STEPS = 8


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ----------------------------------------------------------------- worker

def worker() -> int:
    # Pin BEFORE jax initializes so XLA's thread pool inherits the mask
    # (round-3 VERDICT task 5: unpinned workers timeslice one another and
    # the curve measures the OS scheduler, not the collective).
    spec = os.environ.get("BYTEPS_WS_PIN")
    if spec:
        os.sched_setaffinity(0, {int(c) for c in spec.split(",")})
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import byteps_tpu.core.api as api

    api.init()
    eng = api._require()
    x = np.random.RandomState(0).randn(GRAD_BYTES // 4).astype(np.float32)
    eng.push_pull_local(x, "ws.grad")          # warmup + compile
    times = []
    for _ in range(STEPS):
        t0 = time.perf_counter()
        eng.push_pull_local(x, "ws.grad")
        times.append(time.perf_counter() - t0)
    api.shutdown()
    from tools._bench_util import quantile_stats
    med, iqr = quantile_stats(times)
    print("WS_RESULT " + json.dumps({
        "pid": jax.process_index(),
        "median_ms": med,
        "iqr_ms": iqr,
    }))
    return 0


# ------------------------------------------------------------ orchestrate

def _core_slices(n_proc: int, cores_per_proc: int = 0):
    """Disjoint core sets for n_proc workers, or None when the host can't
    provide at least one dedicated core per worker.

    ``cores_per_proc`` pins EVERY group size to the same per-worker core
    budget (the max group's share): without the cap, the 1-process
    baseline would get all host cores while the 4-process group gets a
    quarter each, and the efficiency ratio would measure thread-pool
    width, not collective growth."""
    try:
        avail = sorted(os.sched_getaffinity(0))
    except AttributeError:
        return None
    if len(avail) < n_proc:
        return None
    per = cores_per_proc or max(1, len(avail) // n_proc)
    if per * n_proc > len(avail):
        return None
    return [avail[i * per:(i + 1) * per] for i in range(n_proc)]


def run_group(n_proc: int, timeout: float = 420.0, pin: bool = False,
              cores_per_proc: int = 0):
    """Spawn n_proc workers x 2 CPU devices; return median step ms.
    ``pin=True`` gives each worker a disjoint core slice of
    ``cores_per_proc`` cores."""
    slices = _core_slices(n_proc, cores_per_proc) if pin else None
    if pin and slices is None:
        raise RuntimeError("not enough cores to pin")
    port = _free_port()
    procs = []
    for pid in range(n_proc):
        env = dict(os.environ)
        if slices is not None:
            env["BYTEPS_WS_PIN"] = ",".join(map(str, slices[pid]))
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(n_proc),
            "DMLC_WORKER_ID": str(pid),
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "BYTEPS_LOG_LEVEL": "WARNING",
            "BYTEPS_TELEMETRY_ON": "0",
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker"],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    results = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(
                    f"weak-scaling worker rc={p.returncode}: {out[-800:]}")
            for line in out.splitlines():
                if line.startswith("WS_RESULT "):
                    results.append(json.loads(line.split(" ", 1)[1]))
    except BaseException as e:
        # a dead worker must take its siblings down with it: survivors
        # blocked in the DMLC rendezvous would otherwise orphan, holding
        # cores and polluting every later group's timings
        for p in procs:
            if p.poll() is None:
                p.kill()
        if isinstance(e, subprocess.TimeoutExpired):
            raise RuntimeError(
                f"weak-scaling group n={n_proc} timed out") from e
        raise
    # slowest process bounds the step; its IQR is the reported spread
    slow = max(results, key=lambda r: r["median_ms"])
    return slow["median_ms"], slow.get("iqr_ms")


def _curve(counts, pin: bool, cores_per_proc: int = 0):
    out = {}
    for n in counts:
        med, iqr = run_group(n, pin=pin, cores_per_proc=cores_per_proc)
        out[f"{n}proc_ms"] = round(med, 2)
        if iqr:
            out[f"{n}proc_iqr_ms"] = [round(q, 2) for q in iqr]
    base = out[f"{counts[0]}proc_ms"]
    last = out[f"{counts[-1]}proc_ms"]
    out[f"efficiency_{counts[-1]}proc"] = round(base / last, 3)
    return out


def measure_weak_scaling(counts=(1, 2, 4)):
    """Contended + (when the host allows) core-pinned weak-scaling curves.

    Round-3 VERDICT Weak #3: the contended curve on a shared box measures
    timeslicing, not collective structure.  With each worker pinned to a
    disjoint core slice the curve measures how the dcn=N hierarchical
    RS/psum/AG actually grows; both curves are reported side by side so
    the reader sees what the environment allowed."""
    out = {"contended": _curve(counts, pin=False)}
    ncores = len(os.sched_getaffinity(0)) if hasattr(
        os, "sched_getaffinity") else (os.cpu_count() or 1)
    per = ncores // counts[-1]
    if per >= 1 and _core_slices(counts[-1], per) is not None:
        # every group size gets the SAME cores/worker (the max group's
        # share), so the curve isolates collective growth
        out["pinned"] = _curve(counts, pin=True, cores_per_proc=per)
        out["pinned"]["cores_per_proc"] = per
    else:
        out["pinned"] = {"skipped": (
            f"host has {ncores} core(s); need >= {counts[-1]} for "
            "disjoint per-worker pinning")}
    out["note"] = (f"{GRAD_BYTES >> 20} MB/process hierarchical push_pull, "
                   "2 CPU devices/process, loopback gRPC DCN; the "
                   "contended curve shares all cores (timeslicing "
                   "dominates), the pinned curve gives each worker its own "
                   "cores and isolates the collective structure's growth")
    return out


def measure_dcn_sweep():
    """Contention-free structure scaling: ONE process, 8 CPU devices,
    hierarchical push_pull with dcn = 1/2/4 slices (fixed total bytes).
    Isolates the cost of the two-level RS -> DCN-psum -> AG structure as
    the slice count grows — the shape that rides real DCN on a pod."""
    import subprocess as sp
    code = r"""
import json, time, os
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from byteps_tpu.comm.mesh import CommContext, _build_mesh
from byteps_tpu.comm.collectives import hierarchical_all_reduce

nbytes = 4 * 1024 * 1024
# set up all three configs first, then interleave reps across them so
# load drift on a shared host hits every dcn count equally
cfgs = {}
for n_dcn in (1, 2, 4):
    comm = CommContext(mesh=_build_mesh(jax.devices()[:8], n_dcn),
                       n_dcn=n_dcn, n_ici=8 // n_dcn)
    x = jax.device_put(jnp.zeros((8, nbytes // 4), jnp.float32),
                       comm.stacked_sharding(extra_dims=1))
    hierarchical_all_reduce(comm, x).block_until_ready()  # compile
    cfgs[n_dcn] = (comm, x)
times = {n: [] for n in cfgs}
for _ in range(8):
    for n_dcn, (comm, x) in cfgs.items():
        t0 = time.perf_counter()
        hierarchical_all_reduce(comm, x).block_until_ready()
        times[n_dcn].append(time.perf_counter() - t0)
res = {f"dcn{n}_ms": round(sorted(ts)[4] * 1e3, 2)
       for n, ts in times.items()}
print("SWEEP " + json.dumps(res))
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    p = sp.run([sys.executable, "-c", code], env=env, cwd=REPO,
               capture_output=True, text=True, timeout=420)
    for line in p.stdout.splitlines():
        if line.startswith("SWEEP "):
            return json.loads(line.split(" ", 1)[1])
    raise RuntimeError(f"dcn sweep failed: {(p.stderr or '')[-400:]}")


# ---------------------------------------------------------- analytic model

# Public v5e numbers (Google Cloud TPU v5e spec; scaling-book tables):
#   - bf16 peak 197 TFLOP/s per chip
#   - interchip interconnect 1600 Gbps aggregate per chip (4x400 2D torus)
# Effective all-reduce bandwidth assumption: bidirectional ring over the
# torus uses the aggregate links; we model EFFECTIVE = 100 GB/s per chip
# (half the 200 GB/s aggregate, a deliberately conservative derate for
# protocol/latency overhead).
V5E_EFFECTIVE_ALLREDUCE_BPS = 100e9

# BERT-large (the reference's headline workload, README.md:35-41):
BERT_LARGE_PARAMS = 336_226_108  # measured from models/bert.py bert_large


def validate_wire_formula():
    """Compile the fused DP gradient reduction on the 8-device CPU mesh
    and confirm the program issues exactly ONE full-gradient-sized
    all-reduce (no duplicated collectives): the projection then converts
    that all-reduce to wire bytes with the standard ring identity
    2*M*(N-1)/N.  Returns (grad_bytes, hlo_allreduce_bytes)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np
    from byteps_tpu.utils.hlo_wire import collectives

    devs = np.array(jax.devices()[:8])
    if devs.size < 8:
        raise RuntimeError("needs 8 CPU devices (XLA_FLAGS set too late)")
    mesh = Mesh(devs.reshape(1, 8), ("dcn", "ici"))
    n = 1 << 18  # 1 MB of f32 per rank

    def body(x):
        return jax.lax.psum(x[0], ("dcn", "ici"))

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(("dcn", "ici")),
                              out_specs=P()))
    hlo = f.lower(jnp.zeros((8, n), jnp.float32)).compile().as_text()
    ar_bytes = sum(nbytes for op, nbytes, _ in collectives(hlo)
                   if op == "all-reduce")
    return n * 4, ar_bytes


def _measured_throughput():
    """(per-chip examples/s, per-chip batch) the analytic projection is
    anchored to.  526.41 ex/s at batch 32 is the one fused BERT-large
    seq-128 step ever timed on a v5e chip (2026-07-30, round-4 code; the
    record file is gone, ROADMAP.md quotes the line).  It is history,
    not a measurement of today's code: until the benchmark (ROADMAP S1)
    supplies a ledger figure the projection stays a projection."""
    return 526.41, 32


def analytic_v5e256(measured_step_ms=None, dtype_bytes=2):
    """Project BERT-large DP scaling efficiency at v5e-256.

    efficiency = compute / (compute + exposed_comm); bounds given for
    zero overlap (all comm exposed) and full overlap (comm hidden behind
    the backward pass, the reference's priority-scheduling claim)."""
    if measured_step_ms is None:
        ex_per_s, batch = _measured_throughput()
        measured_step_ms = batch / ex_per_s * 1e3
    grad_bytes = BERT_LARGE_PARAMS * dtype_bytes
    n = 256
    wire = 2 * grad_bytes * (n - 1) / n
    comm_ms = wire / V5E_EFFECTIVE_ALLREDUCE_BPS * 1e3
    eff_none = measured_step_ms / (measured_step_ms + comm_ms)
    out = {
        "model": "bert_large mixed-precision DP, one v5e-256 pod (all ICI)",
        "grad_bytes": grad_bytes,
        "assumed_allreduce_bps": V5E_EFFECTIVE_ALLREDUCE_BPS,
        "measured_step_ms_per_chip": round(measured_step_ms, 2),
        "allreduce_ms": round(comm_ms, 2),
        "efficiency_no_overlap": round(eff_none, 3),
        "efficiency_full_overlap": 1.0,
        "target": "reference: ~90% at 256 GPUs (README.md:35-41)",
        "zero1_note": ("ZeRO-1 wire bytes identical (RS+AG is the "
                       "all-reduce decomposition); HSDP adds a DCN psum "
                       "of the 1/n_ici shard only on multi-pod DCN "
                       "deployments"),
    }
    try:
        formula, hlo = validate_wire_formula()
        out["wire_formula_check"] = {
            "formula_bytes_per_rank": formula, "hlo_bytes_per_rank": hlo,
            "match": bool(abs(formula - hlo) <= 0.25 * formula)}
    except Exception as e:  # noqa: BLE001 - validation is best-effort
        out["wire_formula_check"] = {"error": str(e)[:200]}
    return out


def main() -> int:
    if "--worker" in sys.argv:
        return worker()
    result = {"weak_scaling": measure_weak_scaling(),
              "dcn_sweep": measure_dcn_sweep(),
              "analytic_v5e256": analytic_v5e256()}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
