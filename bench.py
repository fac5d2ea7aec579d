"""Benchmark: BERT-large MLM training throughput through byteps_tpu.

The reference's headline benchmark is BERT-large pretraining throughput /
scaling efficiency (reference README.md:35-41; BASELINE.md).  This harness
runs the fused data-parallel train step (forward + backward + push_pull +
adamw) on whatever devices are visible and prints ONE JSON line (the last
stdout line) with the headline metric plus secondary metrics:

    {"metric": ..., "value": N, "unit": "examples/s", "vs_baseline": N,
     "mfu": ..., "push_pull_gbps": {...}, "onebit_pallas": {...}}

Process model: a chip belongs to one process, so the outer process never
touches JAX — it runs the real bench (``--inner``) in one subprocess, on
whatever backend JAX gives it, and embeds the CPU-mesh tool sections
afterwards.  A run that finds no TPU fails (non-zero exit, no result);
the CPU path is taken only on an explicit ``JAX_PLATFORMS=cpu`` request,
runs ``bert_tiny`` on the virtual 8-device mesh and says so in its metric
name and ``"device": "cpu"``.

The inner streams each completed section as a flushed ``BENCH_SECTION``
stdout line; if it is killed at its time limit the outer assembles them
into a ``"partial": true`` result naming the section that never finished.
A section that raises is recorded as an error dict, the remaining
sections still run, and the exit code is non-zero.  On TPU the
engine-path section runs FIRST (cheapest compiles), before the
multi-minute BERT-large compile.

Baseline bookkeeping: the first green TPU run writes its per-chip
examples/s into BASELINE_MEASURED.json; later runs report vs_baseline
against it so the BENCH_r{N}.json series shows drift.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MEASURED_BASELINE_FILE = os.path.join(REPO, "BASELINE_MEASURED.json")

# Approximate peak bf16 matmul FLOP/s per chip, by device_kind substring.
# Public numbers: v5e 197T, v5p 459T, v6e (Trillium) 918T, v4 275T, v3 123T.
_PEAK_FLOPS = (
    ("v6e", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5litepod", 197e12), ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def _peak_flops(device_kind: str) -> float:
    dk = device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in dk:
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device_kind!r}: add it to "
        f"_PEAK_FLOPS with its source — a utilization against a guessed "
        f"peak is not a measurement")


# --------------------------------------------------------------------------
# Inner bench (runs in a subprocess whose backend is already decided)
# --------------------------------------------------------------------------

def _bench_train_step(devices):
    """Headline: fused DP train-step throughput on the flagship model."""
    import jax
    import numpy as np
    import optax

    from byteps_tpu.comm.mesh import CommContext, _build_mesh
    from byteps_tpu.models.bert import (BertForMLM, bert_large, bert_tiny,
                                        mlm_loss, synthetic_batch)
    from byteps_tpu.parallel import make_dp_train_step, replicate, shard_batch

    on_tpu = devices[0].platform == "tpu"
    n = len(devices)
    comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)

    cfg = bert_large() if on_tpu else bert_tiny()
    seq_len = 128 if on_tpu else 32
    per_dev_batch = 32 if on_tpu else 2
    steps = 20 if on_tpu else 3

    model = BertForMLM(cfg)
    rng = jax.random.PRNGKey(0)
    global_batch = per_dev_batch * n
    batch = synthetic_batch(rng, cfg, batch=global_batch, seq_len=seq_len)
    params = model.init(rng, batch["input_ids"], batch["attention_mask"])
    n_params = int(sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(params)))

    def loss_fn(params, b):
        # gathered MLM head: vocab projection only on masked positions
        logits = model.apply(params, b["input_ids"], b["attention_mask"],
                             masked_positions=b["masked_positions"])
        return mlm_loss(logits, b["masked_labels"])

    tx = optax.adamw(1e-4)
    opt_state = tx.init(params)
    step = make_dp_train_step(comm, loss_fn, tx)
    params = replicate(comm, params)
    opt_state = replicate(comm, opt_state)
    batch = shard_batch(comm, batch)

    def run(k):
        nonlocal params, opt_state
        t0 = time.perf_counter()
        loss = None
        for _ in range(k):
            params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready((params, opt_state))
        lv = float(loss)
        return time.perf_counter() - t0, lv

    run(3)  # warmup/compile
    dt, lv = run(steps)
    dt2, lv = run(steps)
    dt = min(dt, dt2)
    assert np.isfinite(lv), "non-finite loss"

    examples_per_sec = steps * global_batch / dt
    per_chip = examples_per_sec / n

    # Training FLOPs/example ~= 6 * N * T (fwd 2NT + bwd 4NT); the standard
    # transformer approximation used by the scaling literature.  N includes
    # embeddings (a few % overcount on BERT-large).
    flops_per_example = 6.0 * n_params * seq_len
    peak = _peak_flops(devices[0].device_kind) if on_tpu else None
    mfu = (per_chip * flops_per_example / peak) if peak else None
    return {
        "on_tpu": on_tpu,
        "per_chip": per_chip,
        "tokens_per_sec_per_chip": per_chip * seq_len,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "n_params": n_params,
        "seq_len": seq_len,
        "per_dev_batch": per_dev_batch,
        "device_kind": devices[0].device_kind,
        "n_devices": n,
    }


def _bench_push_pull(devices, on_tpu, emit=None):
    """Secondary: engine-path push_pull bandwidth (the product's own
    metric — BASELINE.json 'grad push_pull GB/s').  ``emit``, when given,
    receives the accumulated dict after every measurement (the bench's
    mid-section salvage stream).

    GB/s = logical gradient bytes / wall time, one direction.  The engine
    path includes host staging + partitioning + priority scheduling +
    per-chunk dispatch; 'fused' is the device-resident jitted reduction for
    comparison (what make_dp_train_step uses in-graph).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.comm.mesh import CommContext, _build_mesh
    from byteps_tpu.common.config import Config
    from byteps_tpu.core.engine import PushPullEngine

    n = len(devices)
    comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)

    def to_gbps(nbytes, times):
        """(median GB/s, [q25, q75] GB/s, median seconds) from per-rep
        seconds.  Per-rep MEDIAN, not total/mean: the dispatcher's
        group-merge width is timing-dependent, so a width can first
        appear mid-timing and drag a fresh XLA compile (seconds)
        into one rep; the median rejects that outlier,
        and the IQR carries the spread (the repo convention — every
        artifact shows its honesty term).  The raw median seconds feed
        the ablation window-economy guard without round-trip through the
        3-decimal GB/s rounding.  Rates divide by the UNROUNDED median
        seconds: the display rounding collapses sub-50 ns medians to 0
        and a rate computed from it would divide by zero, aborting the
        section's remaining sizes."""
        from tools._bench_util import quantile_stats_raw
        med_s, q25_s, q75_s = quantile_stats_raw(times)
        return (round(nbytes / med_s / 1e9, 3),
                [round(nbytes / q75_s / 1e9, 3),      # slow quartile ->
                 round(nbytes / q25_s / 1e9, 3)],     # low GB/s bound
                med_s)

    # The most recent engine run's auto-tuner snapshot (chunk/credit
    # choices): recorded into the section JSON so every round shows WHAT
    # the planner picked alongside how fast the pick ran.
    tuner = {}

    def _warm_to_steady_state(eng, push, nbytes, cap=24):
        """Warm until the planner locks its bucket (bounded): the timed
        reps then measure the tuned steady state — chunk size chosen,
        credits installed, every program compiled — not the exploration
        phase's dispatch patterns."""
        for _ in range(cap):
            push()
            if eng.planner.locked(nbytes):
                break
        tuner["snapshot"] = eng.planner.snapshot()

    def engine_gbps(nbytes, reps=5, **cfg_kw):
        cfg = Config(telemetry_on=False, trace_on=False, **cfg_kw)
        eng = PushPullEngine(comm, cfg)
        try:
            x = np.random.RandomState(0).randn(nbytes // 4).astype(np.float32)
            # declare-time AOT warm: the steady-state program set
            # compiles here, not inside a timed rep
            eng.declare_tensor("bench.pp", x.shape, np.float32)
            _warm_to_steady_state(
                eng, lambda: eng.push_pull_local(x, "bench.pp"), nbytes)
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                eng.push_pull_local(x, "bench.pp")
                times.append(time.perf_counter() - t0)
        finally:
            eng.shutdown(wait=False)
        return to_gbps(nbytes, times)

    def engine_device_gbps(nbytes, reps=5):
        """Engine path fed a device-resident stacked array: measures the
        engine itself (scheduler, partitioner, per-chunk dispatch,
        collective) without the host->device staging cost — the fair
        comparison against the fused path (round-1 weakness #4: the host
        round-trip must not be mistaken for engine overhead)."""
        cfg = Config(telemetry_on=False, trace_on=False)
        eng = PushPullEngine(comm, cfg)
        try:
            # (n, nbytes/4): every rank contributes nbytes, matching
            # engine_gbps's per-rank workload so the GB/s are comparable
            x = jax.device_put(
                jnp.zeros((n, nbytes // 4), jnp.float32),
                comm.stacked_sharding(extra_dims=1))
            eng.declare_tensor("bench.dev", (nbytes // 4,), np.float32,
                               local=False)
            _warm_to_steady_state(
                eng, lambda: jax.block_until_ready(
                    eng.push_pull(x, "bench.dev")), nbytes)
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                out = eng.push_pull(x, "bench.dev")
                jax.block_until_ready(out)
                times.append(time.perf_counter() - t0)
        finally:
            eng.shutdown(wait=False)
        return to_gbps(nbytes, times)

    def fused_gbps(nbytes, reps=10):
        """The exact collective the engine dispatches (push_pull_array on
        the stacked sharding), without the engine around it — so
        engine_device vs fused isolates the scheduling layer's cost on an
        identical workload."""
        from byteps_tpu.comm.collectives import push_pull_array
        x = jax.device_put(jnp.zeros((n, nbytes // 4), jnp.float32),
                           comm.stacked_sharding(extra_dims=1))
        push_pull_array(comm, x, op="sum").block_until_ready()
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            push_pull_array(comm, x, op="sum").block_until_ready()
            times.append(time.perf_counter() - t0)
        return to_gbps(nbytes, times)

    def dispatch_amortization(nchunks=64):
        """Deterministic dispatch-count datum (VERDICT r4 task 3): the
        same multi-chunk push one chunk a program and as the engine's
        dispatch units (a bucket's worth of queued columns a program:
        64 chunks -> 4), with the dispatcher paused until the queue
        holds every chunk."""
        counts = {}
        chunk_elems = 65536 // 4
        x = np.zeros(nchunks * chunk_elems, np.float32)
        for label, one_chunk in (("chunked", True), ("units", False)):
            cfg = Config(telemetry_on=False, trace_on=False,
                         partition_bytes=65536)
            eng = PushPullEngine(comm, cfg)
            eng._one_chunk_units = one_chunk
            try:
                eng.pause_dispatch()
                h = eng.push_pull_local_async(x, "bench.amort")
                eng.resume_dispatch()
                # bounded: a chip dying exactly here must cost two
                # minutes, not the whole inner budget (the sections after
                # this one are the expensive ones the window exists for)
                h.wait(timeout=120.0)
                counts[f"dispatches_{label}"] = eng.stats["dispatches"]
                counts[f"chunks_{label}"] = eng.stats["chunks"]
            finally:
                eng.shutdown(wait=False)
        return counts

    mb = 1024 * 1024
    sizes = [mb, 16 * mb, 256 * mb] if on_tpu else [mb, 8 * mb]
    out = {}

    med_s = {}

    def add(key, fn):
        # Stream each measurement as it lands: on hardware this section's
        # duration is itself an unknown, so a run cut short must not lose
        # the sizes already measured.  A measurement that RAISES annotates
        # the error, keeps what was measured, and skips the rest.
        if "error" in out:
            return
        try:
            out[key], out[key + "_iqr"], med_s[key] = fn()
        except Exception as e:  # noqa: BLE001 - keep partial measurements
            out["error"] = f"{key}: {type(e).__name__}: {e}"[:300]
        if emit is not None:
            emit(dict(out))

    # fused ceiling first: it is the denominator every engine figure is
    # judged against, and the cheapest program of the lot.
    big = sizes[-1]
    add(f"fused_{big // mb}MB", lambda: fused_gbps(big))
    add(f"engine_device_{big // mb}MB", lambda: engine_device_gbps(big))
    for nbytes in sizes:
        add(f"engine_{nbytes // mb}MB", lambda n=nbytes: engine_gbps(n))
    # Headline ratios (ISSUE 5 acceptance: engine >= 0.7x fused, from
    # 0.30x): the engine-vs-fused gap IS the metric this bench exists to
    # track, so it rides the compact summary line, not just the full
    # record.  The auto-tuner's chosen knobs land next to it — a
    # regression round can tell "the planner chose badly" apart from
    # "the path got slower".
    fused = out.get(f"fused_{big // mb}MB")
    for num, label in ((f"engine_{big // mb}MB", "engine_vs_fused_ratio"),
                       (f"engine_device_{big // mb}MB",
                        "engine_device_vs_fused_ratio")):
        if isinstance(fused, (int, float)) and fused > 0 \
                and isinstance(out.get(num), (int, float)):
            out[label] = round(out[num] / fused, 3)
    if tuner.get("snapshot") is not None:
        out["autotune"] = tuner["snapshot"]
    if emit is not None:
        emit(dict(out))
    if "error" not in out:  # same chip-gone gate as add(): once a drop
        try:                # is seen, stop touching the device
            out["dispatch_amortization"] = dispatch_amortization()
        except Exception as e:  # noqa: BLE001 - must not kill the sweep
            out["dispatch_amortization"] = {"error": str(e)[:200]}
        if emit is not None:
            emit(dict(out))
    # The three ablations are secondary to the headline engine figure; if
    # the hardware engine path is slow enough that each would eat minutes
    # of a possibly-short green window, skip them with the projection
    # recorded (each ablation costs ~8 calls: 3 warmup + 5 reps).
    headline_key = f"engine_{big // mb}MB"
    headline = out.get(headline_key)
    # measured median seconds, not the 3-decimal GB/s inverted (which
    # collapses anything under 0.0005 GB/s to a meaningless infinity)
    per_call_s = med_s.get(headline_key)
    if per_call_s is not None and per_call_s * 8 > 240.0:
        out["ablations_skipped"] = (
            f"projected {per_call_s * 8:.0f}s per ablation at "
            f"{headline} GB/s; window economy")
    else:
        add(f"engine_{big // mb}MB_no_partition",
            lambda: engine_gbps(big, partition_bytes=2**31 - 512))
        add(f"engine_{big // mb}MB_no_priority",
            lambda: engine_gbps(big, enable_priority=False))
        add(f"engine_{big // mb}MB_credit16MB",
            lambda: engine_gbps(big, scheduling_credit=16 * mb))
    return out


def _bench_resnet(devices):
    """Secondary: ResNet-50 synthetic images/s (the reference's other
    headline benchmark, docs/performance.md:3-12), via the fused DP step
    with cross-replica BatchNorm."""
    import jax
    import numpy as np
    import optax

    from byteps_tpu.comm.mesh import CommContext, _build_mesh
    from byteps_tpu.models import resnet as R
    from byteps_tpu.parallel import shard_batch

    n = len(devices)
    comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)
    model = R.resnet50(axis_name=comm.dp_axes)
    rng = jax.random.PRNGKey(0)
    per_dev = 32
    batch = R.synthetic_images(rng, per_dev * n, 224, 1000)
    step, state = R.make_vision_trainer(
        comm, model, optax.sgd(0.1, momentum=0.9), batch, rng)
    batch = shard_batch(comm, batch)
    steps = 10

    def run(k):
        nonlocal state
        t0 = time.perf_counter()
        loss = None
        for _ in range(k):
            state, loss = step(state, batch)
        jax.block_until_ready(state)
        return time.perf_counter() - t0, float(loss)

    run(2)
    dt, loss = run(steps)
    assert np.isfinite(loss)
    return {"images_per_sec_per_chip": round(steps * per_dev / dt, 1),
            "batch_per_chip": per_dev}


def _bench_dcn_compare():
    """Compressed vs plain DCN hop on a (dcn=2, ici=4) CPU mesh (round-1
    VERDICT item 5): wall time of hierarchical_push_pull with and without
    the onebit DCN compression, plus the per-rank wire bytes each compiled
    program moves over each axis (from the HLO — the wire contract a real
    2-slice pod would execute)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from byteps_tpu.ops.collective_ops import (hierarchical_push_pull,
                                               make_onebit_pair,
                                               make_powersgd_pair)
    from byteps_tpu.utils.hlo_wire import dcn_ici_bytes

    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dcn", "ici"))
    # 4 MB of f32 per rank: the wire-bytes ratio (the point of this
    # section) comes from the HLO and is size-independent; small keeps the
    # CPU-mesh run inside the smoke-test budget on a loaded host.
    n = 1 << 20

    def build(pair):
        c, d = pair() if pair else (None, None)

        def body(x):
            # compress_min_bytes=0: this section's point IS the compressed
            # wire contract, and the small benchmark shard (1 MB/device)
            # sits under the default economic gate that would otherwise
            # silently fall back to the plain path (ratio 1.0 artifact).
            return hierarchical_push_pull(x[0], op="sum", compress=c,
                                          decompress=d, compress_min_bytes=0)
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=P(("dcn", "ici")),
                                  out_specs=P(), check_vma=False))
        x = jnp.zeros((8, n), jnp.float32)
        return f, x, f.lower(x).compile().as_text()

    out = {}
    for tag, pair in (("plain", None), ("onebit_dcn", make_onebit_pair),
                      ("powersgd_dcn", make_powersgd_pair)):
        f, x, hlo = build(pair)
        f(x).block_until_ready()
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            r = f(x)
        r.block_until_ready()
        dt = time.perf_counter() - t0
        dcn_b, ici_b = dcn_ici_bytes(hlo, n_ici=4)
        out[tag] = {"ms_per_call": round(dt / reps * 1e3, 2),
                    "dcn_bytes_per_rank": dcn_b,
                    "ici_bytes_per_rank": ici_b}
    p, c = out["plain"], out["onebit_dcn"]
    out["dcn_wire_ratio"] = round(
        p["dcn_bytes_per_rank"] / max(1, c["dcn_bytes_per_rank"]), 1)
    out["dcn_wire_ratio_powersgd"] = round(
        p["dcn_bytes_per_rank"]
        / max(1, out["powersgd_dcn"]["dcn_bytes_per_rank"]), 1)
    return out


def _bench_pallas(devices):
    """On real TPU: compile the onebit Pallas kernels non-interpreted,
    bit-compare against the portable numpy refs, and time them (round-1
    weakness #5: the kernels had never run on hardware)."""
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.ops import pallas_kernels as pk
    from tests import compression_refs as refs

    try:
        numel = 32 * 128 * 1024  # 16 MiB of f32
        rng = np.random.RandomState(3)
        x = rng.randn(numel).astype(np.float32)
        L = pk.padded_lanes(numel)
        x2d = jnp.pad(jnp.asarray(x), (0, 32 * L - numel)).reshape(32, L)

        words, abs_sum = pk.onebit_pack(x2d)  # non-interpret: Mosaic
        words.block_until_ready()
        ref_words, ref_scale = refs.onebit_compress(x, scaling=True)
        bitexact = bool(np.array_equal(np.asarray(words), ref_words))

        out2d = pk.onebit_unpack(words, abs_sum / numel)
        out2d.block_until_ready()
        ref_dec = refs.onebit_decompress(ref_words, ref_scale, numel)
        got_dec = np.asarray(out2d).reshape(-1)[:numel]
        bitexact = bitexact and bool(
            np.allclose(got_dec, ref_dec, rtol=1e-6))

        def _time(fn, reps=20):
            t0 = time.perf_counter()
            r = None
            for _ in range(reps):
                r = fn()
            jnp.asarray(
                r[0] if isinstance(r, tuple) else r).block_until_ready()
            return time.perf_counter() - t0

        nbytes = numel * 4
        dt_pack = _time(lambda: pk.onebit_pack(x2d))
        dt_unpack = _time(lambda: pk.onebit_unpack(words, abs_sum / numel))
        return {
            "bitexact_vs_ref": bitexact,
            "pack_gbps": round(20 * nbytes / dt_pack / 1e9, 2),
            "unpack_gbps": round(20 * nbytes / dt_unpack / 1e9, 2),
        }
    except Exception as e:  # noqa: BLE001 - Mosaic may reject a kernel
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def _bench_flash(devices, emit=None):
    """On real TPU: flash-attention Pallas kernels vs XLA exact attention
    at long context (the regime the kernels exist for), forward and
    forward+backward, timed as scan-chained calls so the host round-trip
    amortizes away.  ``emit`` streams the accumulated dict after each
    timed chain (each carries its own compile, so a run killed at its
    time limit keeps the chains already measured)."""
    import jax
    import jax.numpy as jnp

    from byteps_tpu.ops.flash_attention import flash_attention
    from byteps_tpu.parallel import full_attention

    try:
        # TPU: the long-context regime.  CPU (smoke/test only; the bench
        # skips this section off-TPU): tiny shapes the interpreter can
        # finish, exercising the same chains and emission protocol.
        on_cpu = devices[0].platform == "cpu"
        b, t, h, d = (1, 512, 2, 64) if on_cpu else (4, 4096, 16, 128)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, t, h, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, t, h, d), jnp.bfloat16)
        reps = 2 if on_cpu else 10

        def fwd_chain(attn):
            def f(q, k, v):
                def body(c, _):
                    return attn(c, k, v), None
                out, _ = jax.lax.scan(body, q, None, length=reps)
                return jnp.sum(out.astype(jnp.float32))
            return jax.jit(f)

        def bwd_chain(attn):
            # grad w.r.t. all of (q, k, v): differentiating q alone would
            # let XLA dead-code the exact path's dK/dV branches while the
            # flash custom_vjp always computes all three — unequal work.
            def f(q, k, v):
                def body(c, _):
                    gq, gk, gv = jax.grad(
                        lambda qq, kk, vv: jnp.sum(
                            attn(qq, kk, vv).astype(jnp.float32)),
                        argnums=(0, 1, 2))(c, k, v)
                    nxt = (gq + gk + gv).astype(c.dtype)
                    return nxt, None
                out, _ = jax.lax.scan(body, q, None, length=reps)
                return jnp.sum(out.astype(jnp.float32))
            return jax.jit(f)

        def timeit(f):
            float(f(q, k, v))  # warm + forces completion through the host
            t0 = time.perf_counter()
            float(f(q, k, v))
            return (time.perf_counter() - t0) / reps * 1e3

        flash = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
        exact = lambda q, k, v: full_attention(q, k, v, causal=True)  # noqa: E731
        diff = float(jnp.max(jnp.abs(
            flash(q[:1, :512], k[:1, :512], v[:1, :512]).astype(jnp.float32)
            - exact(q[:1, :512], k[:1, :512],
                    v[:1, :512]).astype(jnp.float32))))
        out = {"shape": f"b{b} t{t} h{h} d{d} bf16 causal",
               "max_diff_vs_exact": round(diff, 4)}

        def add(key, f):
            # Same raising-drop contract as _bench_push_pull.add: keep the
            # chains already measured, annotate, skip the rest.
            if "error" in out:
                return
            try:
                out[key] = round(timeit(f), 2)
            except Exception as e:  # noqa: BLE001 - keep partial chains
                out["error"] = f"{key}: {type(e).__name__}: {e}"[:300]
            if emit is not None:
                emit(dict(out))

        add("fwd_ms", fwd_chain(flash))
        add("fwd_exact_ms", fwd_chain(exact))
        add("fwd_bwd_ms", bwd_chain(flash))
        add("fwd_bwd_exact_ms", bwd_chain(exact))
        if "fwd_ms" in out and "fwd_exact_ms" in out:
            out["fwd_speedup"] = round(
                out["fwd_exact_ms"] / out["fwd_ms"], 2)
        if "fwd_bwd_ms" in out and "fwd_bwd_exact_ms" in out:
            out["fwd_bwd_speedup"] = round(
                out["fwd_bwd_exact_ms"] / out["fwd_bwd_ms"], 2)
        return out
    except Exception as e:  # noqa: BLE001 - secondary metric only
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def _bench_tpu_overlap(devices):
    """On real TPU: does engine traffic hide behind device-busy compute?

    The single-chip projection of the cross-barrier pipelining claim
    (reference docs/best-practice.md:7, '0-15%' end-to-end): the engine's
    host-side staging + chunk dispatch runs on engine threads, so an
    async push_pull issued before a train step should cost
    max(compute, comm) wall-clock, not compute + comm.  The 1-core build
    host cannot show this (tools/overlap_bench.py records the negative
    honestly); the chip can — device programs run while the host stages.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from byteps_tpu.comm.mesh import CommContext, _build_mesh
    from byteps_tpu.common.config import Config
    from byteps_tpu.core.engine import PushPullEngine

    n = len(devices)
    on_cpu = devices[0].platform == "cpu"
    # TPU: ~10 ms of MXU work vs a 16 MB gradient.  CPU (smoke/test only,
    # the bench calls this section on TPU): scaled way down so the 1-core
    # host finishes in seconds.
    dim, depth, grad_elems, reps = ((256, 4, 1 << 18, 3) if on_cpu
                                    else (4096, 16, 4 * (1 << 20), 10))
    comm = CommContext(mesh=_build_mesh(devices, 1), n_dcn=1, n_ici=n)
    eng = PushPullEngine(comm, Config(telemetry_on=False, trace_on=False))
    try:
        w = jax.random.normal(jax.random.PRNGKey(0), (dim, dim),
                              jnp.bfloat16)

        @jax.jit
        def compute(x):
            def body(c, _):
                return jnp.tanh(c @ w), None
            out, _ = jax.lax.scan(body, x, None, length=depth)
            return out

        x = jax.random.normal(jax.random.PRNGKey(1), (dim, dim),
                              jnp.bfloat16)
        grad = np.random.RandomState(2).randn(grad_elems).astype(
            np.float32)  # host gradient, the adapter-realistic input

        def comm_only():
            eng.push_pull_local(grad, "ov.g")

        def serial():
            compute(x).block_until_ready()
            eng.push_pull_local(grad, "ov.g")

        def pipelined():
            h = eng.push_pull_local_async(grad, "ov.g")
            compute(x).block_until_ready()
            h.wait()
            eng.handles.release(h.id)

        def timeit(fn):
            # per-rep median + IQR (same rationale and convention as
            # _bench_push_pull.to_gbps): the engine modes can hit a
            # timing-dependent group-merge recompile mid-measurement; the
            # median rejects that rep and the bracket shows the spread.
            # digits=4: the CPU smoke path's sub-ms times must not
            # quantize to zero.
            from tools._bench_util import quantile_stats
            fn()  # warm (compile + engine program cache)
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn()
                times.append(time.perf_counter() - t0)
            return quantile_stats(times, digits=4)

        out = {"grad_mb": grad_elems * 4 // (1 << 20)}

        def add_t(key, fn):
            out[key + "_ms"], out[key + "_ms_iqr"] = timeit(fn)

        add_t("compute", lambda: compute(x).block_until_ready())
        add_t("comm", comm_only)
        add_t("serial", serial)
        add_t("pipelined", pipelined)
        hideable = min(out["compute_ms"], out["comm_ms"])
        out["overlap_fraction"] = (
            round((out["serial_ms"] - out["pipelined_ms"]) / hideable, 3)
            if hideable > 0 else None)
        out["note"] = ("async engine push_pull issued before a ~%d ms "
                       "device compute; overlap_fraction = recovered / "
                       "min(compute, comm)" % round(out["compute_ms"]))
        return out
    except Exception as e:  # noqa: BLE001 - secondary metric only
        return {"error": f"{type(e).__name__}: {e}"[:300]}
    finally:
        eng.shutdown(wait=False)


def _bf16_composite_body():
    """Train the bf16 (fsdp, tp) Llama composite a few steps on the
    CURRENT backend and return the loss trajectory (round-3 VERDICT
    task 7: bf16 composite loss from either backend).  Mesh sizing:
    tp=2 when possible, and fsdp clamped to a divisor of the batch (8)
    so odd device counts don't fail the batch sharding."""
    import jax
    import optax

    from byteps_tpu.models.llama import Llama, llama_tiny
    from byteps_tpu.parallel.fsdp_tp import (
        init_llama_opt_state, make_fsdp_tp_mesh, make_fsdp_tp_train_step,
        shard_llama_batch, shard_llama_params)
    from byteps_tpu.parallel.long_context import synthetic_lm_batch

    devs = jax.devices()
    n_tp = 2 if len(devs) >= 2 else 1
    fsdp = max(f for f in (1, 2, 4, 8) if f <= len(devs) // n_tp)
    mesh = make_fsdp_tp_mesh(devs[:fsdp * n_tp], n_tp=n_tp)
    cfg = llama_tiny()
    model = Llama(cfg)
    rng = jax.random.PRNGKey(0)
    batch = synthetic_lm_batch(rng, cfg, batch=8, seq_len=16)
    params = shard_llama_params(mesh,
                                model.init(rng, batch["input_ids"][:1]))
    tx = optax.adam(1e-2)
    opt = init_llama_opt_state(tx, params)
    step = make_fsdp_tp_train_step(mesh, cfg, tx)
    b = shard_llama_batch(mesh, batch)
    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt, b)
        losses.append(round(float(loss), 4))
    return {"dtype": "bfloat16", "mesh": f"fsdp={fsdp} x tp={n_tp}",
            "platform": devs[0].platform, "losses": losses,
            "decreased": losses[-1] < losses[0]}


def _bench_bf16_fsdp_tp(on_tpu: bool):
    """bf16 (fsdp, tp) composite section, backend-appropriate isolation.

    On TPU: in-process — libtpu is exclusive to this process, so a child
    could never open the chip; the GSPMD jit path has no known process-
    killing failure there (the CHECK crash is the CPU emitter's
    partial-manual shard_map path, tests/test_three_d.py canary).
    On CPU: subprocess-isolated against exactly that CHECK, on the
    virtual 8-device mesh."""
    if on_tpu:
        try:
            return _bf16_composite_body()
        except Exception as e:  # noqa: BLE001 - section must not kill bench
            return {"error": f"{type(e).__name__}: {e}"[:300]}
    import subprocess
    code = ("import os, json\n"
            "flags = os.environ.get('XLA_FLAGS', '')\n"
            "if 'host_platform_device_count' not in flags:\n"
            "    os.environ['XLA_FLAGS'] = (flags +"
            " ' --xla_force_host_platform_device_count=8').strip()\n"
            "import jax\n"
            "jax.config.update('jax_platforms', 'cpu')\n"
            "import bench\n"
            "print('BF16_FSDP_TP ' +"
            " json.dumps(bench._bf16_composite_body()))\n")
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=600,
                           cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": "bf16 composite subprocess timed out"}
    for line in p.stdout.splitlines():
        if line.startswith("BF16_FSDP_TP "):
            return json.loads(line.split(" ", 1)[1])
    return {"error": (f"rc={p.returncode}: "
                      + (p.stderr or p.stdout or "")[-300:]),
            "canary": "tests/test_three_d.py tracks the related XLA bug"}


def _bench_bf16_three_d(devices):
    """bf16 (dp, pp, tp) composite on the available devices (round-4
    VERDICT task 8).  On the CPU emitter the bf16 partial-manual psum
    CHECK-crashes the process (tests/test_three_d.py canary keeps the
    repro hot), so the 3D path pins f32 there; real Mosaic is expected to
    be unaffected — this section is the hardware evidence.  Axis sizes
    adapt to the device count: a pod runs real (dp, pp, tp); a single
    chip degenerates to (1, 1, 1), where the full 3D program (GPipe scan,
    auto-tp GSPMD annotations, the psum pattern) still compiles and
    trains in bf16 with trivial collectives — the note records which
    regime the losses came from."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from byteps_tpu.models.gpt import gpt_tiny
    from byteps_tpu.parallel import (init_3d_opt_state, make_3d_mesh,
                                     make_dp_pp_tp_train_step,
                                     shard_3d_batch, shard_3d_params,
                                     synthetic_lm_batch)
    from byteps_tpu.parallel.pipeline import init_pipeline_params

    n = len(devices)
    n_pp = 2 if n % 2 == 0 else 1       # gpt_tiny has 2 layers
    n_tp = 2 if n % (n_pp * 2) == 0 else 1
    dp = n // (n_pp * n_tp)
    cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.bfloat16)
    mesh = make_3d_mesh(devices, n_pp=n_pp, n_tp=n_tp)
    rng = jax.random.PRNGKey(0)
    batch = synthetic_lm_batch(rng, cfg, batch=4 * dp, seq_len=16)
    params = shard_3d_params(
        mesh, init_pipeline_params(cfg, rng, batch["input_ids"][:1]))
    tx = optax.sgd(0.1)
    opt = init_3d_opt_state(tx, params)
    step = make_dp_pp_tp_train_step(mesh, cfg, tx, num_microbatches=2)
    b = shard_3d_batch(mesh, batch)
    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt, b)
        losses.append(round(float(loss), 4))
    # the note must describe the axes actually exercised: 2 chips give
    # (1, 2, 1) — a single non-trivial axis, not "multi-axis" evidence
    live_axes = [f"{name}={size}" for name, size in
                 (("dp", dp), ("pp", n_pp), ("tp", n_tp)) if size > 1]
    return {
        "dtype": "bfloat16",
        "mesh": f"dp={dp} x pp={n_pp} x tp={n_tp}",
        "platform": devices[0].platform,
        "losses": losses,
        "decreased": losses[-1] < losses[0],
        "note": ("collectives trivial at (1,1,1); the multi-axis wire "
                 "pattern stays covered in f32 by dryrun_multichip"
                 if not live_axes else
                 f"bf16 collectives over {', '.join(live_axes)}"),
    }


def _emit_section(key, value):
    """Stream a completed section to stdout immediately (flushed through
    the pipe) so the outer process can still assemble it if the inner is
    killed at its time limit before the final line."""
    print("BENCH_SECTION " + json.dumps({"key": key, "value": value}),
          flush=True)


def _mark_start(key):
    """Announce a section before it runs, so a hang is attributable."""
    print("BENCH_SECTION_START " + key, flush=True)


def _emit_progress(key, value):
    """Stream a section's accumulated state mid-run.  Salvage keeps the
    last progress value unless the section completed (a full
    BENCH_SECTION line wins), and a section that died mid-stream still
    counts as the hung one."""
    print("BENCH_SECTION_PROGRESS " + json.dumps(
        {"key": key, "value": value}), flush=True)


def _load_measured_baseline():
    if os.path.exists(MEASURED_BASELINE_FILE):
        try:
            with open(MEASURED_BASELINE_FILE) as f:
                return json.load(f).get("per_chip_examples_per_sec")
        except Exception:  # noqa: BLE001
            return None
    return None


def _assemble(sections, note="", write_baseline=True):
    """Build the single result line from whatever sections completed.

    Used by the inner process for a full run and by the outer process to
    reconstruct a partial run from its streamed BENCH_SECTION lines; a
    TPU run whose headline train section never finished still reports
    every completed TPU section, with value 0.0 and the cause noted
    (and a non-zero exit code — see inner_main / _run_inner).
    ``write_baseline`` is False on the partial path: an aborted run must
    not seed BASELINE_MEASURED before a complete one can."""
    train = sections.get("train")
    train_err = None
    if isinstance(train, dict) and "per_chip" not in train:
        train_err = train.get("error", "train section incomplete")
        train = None
    dev = sections.get("device") or {}
    on_tpu = bool(dev.get("on_tpu", (train or {}).get("on_tpu")))

    baseline = _load_measured_baseline()
    if on_tpu and train and baseline is None and write_baseline:
        # First green TPU run: record the measured baseline for later rounds.
        with open(MEASURED_BASELINE_FILE, "w") as f:
            json.dump({
                "per_chip_examples_per_sec": round(train["per_chip"], 2),
                "device_kind": train["device_kind"],
                "recorded": time.strftime("%Y-%m-%d"),
                "config": {"model": "bert_large", "seq_len": train["seq_len"],
                           "per_dev_batch": train["per_dev_batch"]},
            }, f, indent=1)
        baseline = train["per_chip"]

    per_chip = train["per_chip"] if train else 0.0
    result = {
        # a CPU-mesh figure is never written under a per-chip name
        "metric": ("bert_large_mlm_train_throughput_per_chip" if on_tpu
                   else "bert_tiny_cpu_mesh_smoke_throughput"),
        "value": round(per_chip, 2),
        "unit": "examples/s",
        "vs_baseline": (round(per_chip / baseline, 3)
                        if (on_tpu and train and baseline) else 0.0),
        "mfu": train["mfu"] if train else None,
        **({"tokens_per_sec_per_chip": (
            round(train["tokens_per_sec_per_chip"], 1) if train else 0.0)}
           if on_tpu else {}),
        "device": (train or dev).get("device_kind", "unknown"),
        "n_devices": (train or dev).get("n_devices", 0),
        "push_pull_gbps": sections.get("push_pull_gbps",
                                       {"skipped": "not reached"}),
        "onebit_pallas": sections.get("onebit_pallas",
                                      {"skipped": "not reached"}),
        "flash_attention": sections.get("flash_attention",
                                        {"skipped": "not reached"}),
        "bf16_fsdp_tp": sections.get("bf16_fsdp_tp",
                                     {"skipped": "not reached"}),
    }
    for opt in ("resnet50", "dcn_compare", "tpu_overlap", "bf16_three_d"):
        if sections.get(opt) is not None:
            result[opt] = sections[opt]
    notes = [n for n in (note, train_err and f"train: {train_err}") if n]
    if notes:
        result["error"] = "; ".join(notes)
    return result


def _cpu_requested() -> bool:
    """True only on an EXPLICIT CPU request (``JAX_PLATFORMS=cpu...`` in
    the environment) — the one way this bench runs off the chip."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    return plats.split(",")[0].strip().lower() == "cpu"


def inner_main() -> int:
    """Full bench on the backend JAX gives this process: a TPU, or the
    CPU mesh when the environment asked for it by name.  Anything else
    (no chip and no request) fails without printing a result."""
    import jax

    from byteps_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if os.environ.get("_BPS_BENCH_ONLY") == "dcn":
        # standalone mode: the (dcn=2, ici=4) comparison needs 8 devices,
        # so on a single-chip TPU run the outer process re-invokes this on
        # the virtual CPU mesh (its environment pins JAX_PLATFORMS=cpu).
        print(json.dumps({"dcn_compare": _bench_dcn_compare()}))
        return 0

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not _cpu_requested():
        print(f"bench: no TPU — JAX reports platform "
              f"{devices[0].platform!r}.  There is no CPU fallback; export "
              f"JAX_PLATFORMS=cpu to run the labelled CPU-mesh smoke.",
              file=sys.stderr)
        return 2

    sections = {}
    raised = []

    def section(key, fn, *args):
        _mark_start(key)
        try:
            val = fn(*args)
        except Exception as e:  # noqa: BLE001 - one section must not kill
            val = {"error": f"{type(e).__name__}: {e}"[:300]}  # the rest
        if isinstance(val, dict) and "error" in val:
            raised.append(key)   # raised here, or caught inside the section
        sections[key] = val
        _emit_section(key, val)
        return val

    def push_pull_section(key="push_pull_gbps"):
        section(key, lambda: _bench_push_pull(
            devices, on_tpu, emit=lambda v: _emit_progress(key, v)))

    section("device", lambda: {"device_kind": devices[0].device_kind,
                               "n_devices": len(devices), "on_tpu": on_tpu})
    if on_tpu:
        # Cheapest-compile sections first: a run cut short at its time
        # limit still holds the engine-path numbers before the
        # multi-minute BERT-large compile is even attempted.
        push_pull_section()
        section("tpu_overlap", _bench_tpu_overlap, devices)
        section("onebit_pallas", _bench_pallas, devices)
        section("flash_attention", lambda: _bench_flash(
            devices, emit=lambda v: _emit_progress("flash_attention", v)))
        section("train", _bench_train_step, devices)
        section("resnet50", _bench_resnet, devices)
        section("bf16_fsdp_tp", _bench_bf16_fsdp_tp, on_tpu)
        # bf16 3D runs ONLY where the emitter survives it: real Mosaic
        # (any chip count) — on CPU the partial-manual psum would kill
        # the process at multi-device axis sizes (canary test_three_d.py)
        section("bf16_three_d", _bench_bf16_three_d, devices)
    else:
        for key in ("onebit_pallas", "flash_attention"):
            sections[key] = {"skipped": "cpu run"}
            _emit_section(key, sections[key])
        sections["bf16_three_d"] = {
            "skipped": "cpu run: bf16 partial-manual psum CHECK-crashes "
                       "the CPU emitter (tests/test_three_d.py canary); "
                       "the 3D composite runs f32 in dryrun_multichip"}
        _emit_section("bf16_three_d", sections["bf16_three_d"])
        section("train", _bench_train_step, devices)
        push_pull_section()
        section("bf16_fsdp_tp", _bench_bf16_fsdp_tp, on_tpu)
        if len(devices) >= 8:
            section("dcn_compare", _bench_dcn_compare)

    print(json.dumps(_assemble(sections)))
    if raised and on_tpu:
        # the line above still carries every section that did run; the
        # exit code says the chip run is not a clean record
        print(f"bench: section(s) raised: {', '.join(raised)}",
              file=sys.stderr)
        return 1
    return 0


# --------------------------------------------------------------------------
# Outer orchestration: run the inner once, merge the CPU-mesh tool
# sections.  Never imports jax (a parent that touched JAX would hold the
# chip the inner needs).
# --------------------------------------------------------------------------

# Full TPU bench budget for the section list (engine sweep + overlap +
# pallas + flash chains + BERT-large + resnet + bf16 composite).  Past
# it the inner is killed and the streamed sections are assembled into a
# partial line; too tight a budget cuts off the tail sections instead.
_INNER_TIMEOUT = 3000.0


def _sections_from_stdout(text):
    """Salvage completed BENCH_SECTION lines from a killed inner run.
    Returns (sections, hung_section): the section that had started but
    never completed is where the chip (or compile) hung."""
    done, progress, started = {}, {}, None
    for ln in (text or "").splitlines():
        if ln.startswith("BENCH_SECTION_START "):
            started = ln[len("BENCH_SECTION_START "):].strip()
            continue
        for prefix, store in (("BENCH_SECTION_PROGRESS ", progress),
                              ("BENCH_SECTION ", done)):
            if ln.startswith(prefix):
                try:
                    doc = json.loads(ln[len(prefix):])
                    store[doc["key"]] = doc["value"]
                except (json.JSONDecodeError, KeyError, TypeError):
                    pass
                break
    sections = {**progress, **done}  # a completed section wins
    hung = started if started not in done else None
    return sections, hung


def _echo_inner_stream(out):
    """Re-emit the inner's section stream on the OUTER's stdout (flushed).
    The outer otherwise prints nothing until its final BENCH_FULL +
    compact lines, which can be hours after the sections were measured
    (merge tools); an outer-level kill would lose every section the inner
    already streamed.  With the echo, any consumer of the outer's partial
    stdout can reassemble them (_sections_from_stdout)."""
    for ln in (out or "").splitlines():
        if ln.startswith("BENCH_SECTION"):
            print(ln, flush=True)


def _run_inner(extra_env=None, timeout=_INNER_TIMEOUT):
    """Run ``bench.py --inner``; returns ``(line, err)``.  ``line`` is the
    inner's final JSON line (or a partial assembled from its section
    stream when it was killed at the time limit), ``err`` is None only
    for a complete line from an inner that exited 0."""
    env = dict(os.environ)
    env.update(extra_env or {})
    try:
        p = subprocess.run([sys.executable, os.path.abspath(__file__),
                            "--inner"], capture_output=True, text=True,
                           timeout=timeout, cwd=REPO, env=env)
        _echo_inner_stream(p.stdout)
    except subprocess.TimeoutExpired as e:
        # subprocess.run kills the child and attaches the output read so
        # far; any sections the inner streamed before the hang survive.
        out = e.stdout if isinstance(e.stdout, str) else (
            (e.stdout or b"").decode("utf-8", "replace"))
        _echo_inner_stream(out)
        sections, hung = _sections_from_stdout(out)
        if sections:
            note = ("inner bench timed out after %ds" % timeout
                    + (f"; hung in section '{hung}'" if hung else ""))
            result = _assemble(sections, note, write_baseline=False)
            result["partial"] = True
            if hung:
                result["hung_section"] = hung
            return json.dumps(result), note
        return None, "inner bench timed out"
    for line in reversed(p.stdout.strip().splitlines()):
        if line.startswith("{"):
            return line, (None if p.returncode == 0
                          else f"inner bench exited {p.returncode}")
    tail = (p.stderr or p.stdout or "").strip().splitlines()
    return None, (" | ".join(tail[-3:]) if tail else f"rc={p.returncode}")


def _cpu8_flags() -> str:
    from tools._bench_util import cpu8_flags  # jax-free helper
    return cpu8_flags()


def _run_tool(script: str, timeout: float, env=None):
    """Run a tools/ script in its own session, returning its last JSON
    stdout line (or an {"error": ...} dict).  The session matters: these
    tools spawn their own worker subprocesses (weak_scaling's DMLC
    groups), and killing only the orchestrator on timeout would orphan
    workers stuck in rendezvous — they would keep burning CPU under the
    later bench sections.  killpg reaps the whole tree."""
    import signal
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", script)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO, env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        return {"error": f"{script} timed out after {timeout:.0f}s"}
    for out_line in reversed(out.strip().splitlines()):
        if out_line.startswith("{"):
            try:
                return json.loads(out_line)
            except json.JSONDecodeError:
                return {"error": f"{script}: unparseable JSON line"}
    return {"error": (err or out or "no output")[-300:]}


def _merge_tool_section(line: str, key: str, script: str,
                        timeout: float, env=None) -> str:
    """Embed a tools/ script's JSON output as ``result[key]``."""
    try:
        result = json.loads(line)
    except json.JSONDecodeError:
        return line
    if key in result:
        return line
    try:
        result[key] = _run_tool(script, timeout, env=env)
    except Exception as e:  # noqa: BLE001 - evidence sections must not
        result[key] = {"error": str(e)[:300]}  # kill the bench
    return json.dumps(result)


def _merge_scaling(line: str) -> str:
    """Scaling-evidence section (round-2 VERDICT item 3): measured weak
    scaling over real processes, the contention-free dcn-structure sweep,
    and the analytic v5e-256 projection (tools/weak_scaling.py).  The
    timeout covers the tool's own internal worst case — contended AND
    pinned curves (3 groups x 420s each) plus the 420s dcn sweep plus
    compile slack — so a slow box degrades to a clean error."""
    return _merge_tool_section(line, "scaling", "weak_scaling.py",
                               timeout=3600.0)


def _merge_mechanisms(line: str) -> str:
    """Mechanism-proof section (round-2 VERDICT item 4): priority and
    partitioning measured as LATENCY mechanisms under a credit window
    (tools/mechanism_bench.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _cpu8_flags()
    return _merge_tool_section(line, "mechanisms", "mechanism_bench.py",
                               timeout=900.0, env=env)


def _merge_overlap(line: str) -> str:
    """End-to-end overlap section (round-3 VERDICT task 2): full torch
    training steps through the engine in sync vs cross-barrier mode, with
    a no-communication floor — the measured answer to the reference's
    0-15% overlap claim (tools/overlap_bench.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _cpu8_flags()
    # 1800 s (2x the single-pass budget): on a multi-core host the tool
    # measures TWICE (unpinned + disjoint-pinned, round-5) — a budget
    # sized for one pass would time out mid-second-pass and lose BOTH
    return _merge_tool_section(line, "overlap", "overlap_bench.py",
                               timeout=1800.0, env=env)


def _couple_overlap_to_projection(line: str) -> str:
    """Narrow the analytic 82-100% bracket with the MEASURED overlap
    fraction (round-3 VERDICT task 2's second half): the v5e-256
    projection's exposed-comm term becomes (1 - measured_overlap) * comm
    instead of an assumed bound.  On a saturated host the measured
    fraction is ~0 and the estimate lands on the no-overlap end — that
    is the honest reading for that host, and the conditions block says
    which host it was."""
    try:
        result = json.loads(line)
    except json.JSONDecodeError:
        return line
    ov = result.get("overlap") or {}
    an = (result.get("scaling") or {}).get("analytic_v5e256") or {}
    # Prefer the disjoint-pinned measurement when the host could run it
    # (round-5): transport with its own cores is the closest host-side
    # analog of a TPU's on-chip compute / host dispatch split.
    pinned = ov.get("pinned_disjoint") or {}
    frac = pinned.get("overlap_fraction")
    if frac is None:  # pinned skipped OR measured but undefined (comm
        frac = ov.get("overlap_fraction")  # share ~0): fall back

    step = an.get("measured_step_ms_per_chip")
    comm = an.get("allreduce_ms")
    if frac is None or step is None or comm is None:
        return line
    f = min(max(frac, 0.0), 1.0)
    an["measured_overlap_fraction"] = round(f, 3)
    an["efficiency_at_measured_overlap"] = round(
        step / (step + (1.0 - f) * comm), 3)
    an["overlap_note"] = (
        "overlap fraction from the end-to-end cross-barrier bench on THIS "
        "host (overlap.conditions records cores/load); hosts with spare "
        "transport cores — and TPU pods, where compute runs on-chip — "
        "land nearer the full-overlap end")
    result["scaling"]["analytic_v5e256"] = an
    return json.dumps(result)


def _merge_async_vs_sync(line: str) -> str:
    """Async-PS convergence datum (round-4 VERDICT task 7): the same MLP
    trained sync (barriered grad average) vs async weight-delta workers
    sharing a KVStore, final-loss gap recorded (tools/async_bench.py).
    Matches the mode the reference ships as BYTEPS_ENABLE_ASYNC
    (server.cc:310-314, torch/__init__.py:186-214)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return _merge_tool_section(line, "async_vs_sync", "async_bench.py",
                               timeout=600.0, env=env)


def _merge_aot_memory(line: str) -> str:
    """8B feasibility section (round-3 VERDICT task 6): XLA memory
    analysis of the AOT-compiled (fsdp, tp) Llama-3-8B train step —
    per-device persistent/transient bytes vs v5e HBM, layer-count trend
    (tools/aot_memory.py)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = _cpu8_flags()
    return _merge_tool_section(line, "aot_memory_8b", "aot_memory.py",
                               timeout=900.0, env=env)


def _merge_dcn_compare(line: str) -> str:
    """If the main bench ran single-chip (no dcn_compare), obtain it from a
    virtual 8-device CPU mesh subprocess and merge into the JSON line."""
    try:
        result = json.loads(line)
    except json.JSONDecodeError:
        return line
    if "dcn_compare" in result:
        return line
    env = {
        "_BPS_BENCH_ONLY": "dcn",
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": _cpu8_flags(),
    }
    dcn_line, err = _run_inner(extra_env=env, timeout=600.0)
    if dcn_line is not None:
        try:
            result["dcn_compare"] = json.loads(dcn_line)["dcn_compare"]
        except (json.JSONDecodeError, KeyError):
            result["dcn_compare"] = {"error": "unparseable"}
    else:
        result["dcn_compare"] = {"error": str(err)[:200]}
    return json.dumps(result)


def _parse_line(line):
    try:
        return json.loads(line)
    except (json.JSONDecodeError, TypeError):
        return None


# The driver snapshots the last ~2000 stdout chars; staying well under
# leaves room for a stray warning line landing after ours.
_COMPACT_BUDGET = 1500


def _record_dir():
    """Where the full-record artifacts are written.  _BPS_BENCH_REPO
    overrides it so a test-suite bench run cannot clobber the committed
    BENCH_FULL record; only the WRITES move — tool paths and subprocess
    cwds stay on the real repo."""
    return os.environ.get("_BPS_BENCH_REPO") or REPO


def _round_number():
    """Best-effort current round index: one past the newest BENCH_r{N}.json
    (the driver writes those at each round end; they live in the real
    repo even when the artifact WRITES are redirected).  Never raises —
    a failed stamp must not cost the record itself."""
    import re
    try:
        ns = [int(m.group(1)) for f in os.listdir(REPO)
              for m in [re.match(r"BENCH_r(\d+)\.json$", f)] if m]
    except OSError:
        return None
    return (max(ns) + 1) if ns else None


_SCALAR_KEYS = ("metric", "value", "unit", "vs_baseline", "mfu",
                "tokens_per_sec_per_chip", "device", "n_devices")


def _section_status(v):
    """One-word health flag for the compact line's per-section map."""
    if not isinstance(v, dict):
        return "ok"
    if "error" in v:
        data = [k for k in v if k not in ("error", "skipped", "note")]
        return "error+data" if data else "error"
    if "skipped" in v:
        return "skip"
    return "ok"


def _compact_summary(doc):
    """The FINAL stdout line: ≤_COMPACT_BUDGET chars so the driver's tail
    capture always ends in one parseable JSON object.  Rounds 3 and 4
    lost their records (BENCH_r0{3,4}.json parsed: null) because the full
    ~10 kB line outgrew the 2000-char tail window — the compact line
    carries the scalars, per-section status flags and a few headline
    figures; everything else lives in the committed full record."""
    import re
    out = {k: doc[k] for k in _SCALAR_KEYS if k in doc}
    for k in ("partial", "hung_section"):
        if doc.get(k):
            out[k] = doc[k]
    skip = set(_SCALAR_KEYS) | {"partial", "hung_section", "error",
                                "recorded", "round"}
    out["sections"] = {k: _section_status(v) for k, v in doc.items()
                       if k not in skip}
    heads = {}
    pp = doc.get("push_pull_gbps")

    def _largest(prefix):
        best = None
        if isinstance(pp, dict):
            for k, v in pp.items():
                m = re.match(re.escape(prefix) + r"_(\d+)MB$", k)
                if m and isinstance(v, (int, float)):
                    if best is None or int(m.group(1)) > best[0]:
                        best = (int(m.group(1)), k, v)
        return best

    for prefix in ("fused", "engine_device", "engine"):
        b = _largest(prefix)
        if b:
            heads[b[1] + "_gbps"] = b[2]
    if isinstance(pp, dict):
        for rk in ("engine_vs_fused_ratio", "engine_device_vs_fused_ratio"):
            if isinstance(pp.get(rk), (int, float)):
                heads[rk] = pp[rk]
    for sec, label in (("tpu_overlap", "tpu_overlap_fraction"),
                       ("overlap", "host_overlap_fraction")):
        v = doc.get(sec)
        if isinstance(v, dict) and isinstance(
                v.get("overlap_fraction"), (int, float)):
            heads[label] = v["overlap_fraction"]
    if heads:
        out["headline"] = heads
    if doc.get("round") is not None:
        out["round"] = doc["round"]
    out["full_record"] = "BENCH_FULL.json"
    if doc.get("error"):
        out["error"] = str(doc["error"])[:200]
    s = json.dumps(out, separators=(",", ":"))
    for drop in ("headline", "sections"):  # belt-and-braces; the normal
        if len(s) <= _COMPACT_BUDGET:      # line is a few hundred chars
            break
        out.pop(drop, None)
        s = json.dumps(out, separators=(",", ":"))
    if len(s) > _COMPACT_BUDGET and "error" in out:
        out["error"] = out["error"][:80]
        s = json.dumps(out, separators=(",", ":"))
    return s


def _record_class(doc):
    """Displacement rank for the numbers-of-record file: a complete TPU
    record (2) outranks a complete chipless/CPU record (1) outranks a
    degraded record (0): a failed run's line must not clobber the last
    good record."""
    if not isinstance(doc, dict) or _is_degraded(doc):
        return 0
    on_tpu = str(doc.get("device", "")).lower().startswith(
        ("tpu", "v5", "v6", "v4"))
    return 2 if on_tpu else 1


def _atomic_write(doc, path):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)


def _finalize(line: str) -> str:
    """Persist the full assembled record and return the compact final line
    (round-4 VERDICT task 1).  The full record is echoed to stdout as a
    'BENCH_FULL '-prefixed line for stream consumers and written to two
    run-time files (.gitignore'd): BENCH_FULL_LATEST.json (every run,
    any quality) and BENCH_FULL.json, which a lower-class record never
    displaces (_record_class).  The returned compact summary is printed
    LAST so the driver's 2000-char tail capture always parses."""
    doc = _parse_line(line)
    if doc is None:
        return line
    doc["recorded"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    rnd = _round_number()
    if rnd is not None:
        doc["round"] = rnd
    full = json.dumps(doc)
    try:
        rec_dir = _record_dir()
        record_path = os.path.join(rec_dir, "BENCH_FULL.json")
        _atomic_write(doc, os.path.join(rec_dir,
                                        "BENCH_FULL_LATEST.json"))
        try:
            with open(record_path) as f:
                existing = json.load(f)
        except (OSError, json.JSONDecodeError):
            existing = None
        if _record_class(doc) >= _record_class(existing):
            _atomic_write(doc, record_path)
    except OSError:
        pass  # unwritable tree: stdout still carries the full line
    print("BENCH_FULL " + full, flush=True)
    return _compact_summary(doc)


def _is_degraded(doc):
    """A line that must not be trusted as the round's record: salvaged
    partial, or a 'complete' line whose train section failed (section()
    converts a raised train step into an error dict, so the inner still
    prints a line with value 0.0 — that is a failure, not a result)."""
    return bool(doc) and (bool(doc.get("partial")) or not doc.get("value"))


def main() -> int:
    if "--inner" in sys.argv:
        return inner_main()

    # The CPU path exists only on request, and then it runs the virtual
    # 8-device mesh: a bare CPU inner would get ONE device, every
    # collective would degenerate to a no-op, and the record would be
    # incomparable with every other CPU-mesh record.
    extra = {"XLA_FLAGS": _cpu8_flags()} if _cpu_requested() else None
    line, err = _run_inner(extra_env=extra)
    if line is None:
        print(f"bench: no result: {err}", file=sys.stderr)
        return 1
    print(_finalize(_couple_overlap_to_projection(_merge_aot_memory(
        _merge_async_vs_sync(_merge_overlap(_merge_mechanisms(
            _merge_scaling(_merge_dcn_compare(line)))))))))
    if err is not None:
        print(f"bench: {err}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
