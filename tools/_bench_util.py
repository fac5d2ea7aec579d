"""Shared helpers for the bench tools (mechanism_bench, overlap_bench):
one copy of the CPU-mesh setup, quantile stats, and core pinning, so the
tools can't silently drift apart in how they measure."""

from __future__ import annotations

import os


def cpu8_flags(existing=None) -> str:
    """XLA_FLAGS value forcing the virtual 8-device CPU mesh, stripping
    any stale device-count flag first.  The ONE copy of this
    strip-and-append (bench.py and every tool import it), so embedded and
    standalone runs can't drift in what mesh they measure.  jax-free:
    safe to import from processes that must not init a backend."""
    import re
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", "")
                   if existing is None else existing)
    return (flags + " --xla_force_host_platform_device_count=8").strip()


def setup_cpu8_mesh():
    """Force the virtual 8-device CPU mesh in THIS process.

    A bare ``python tools/<bench>.py`` must measure the same multi-rank
    configuration bench.py embeds, not a silent 1-device mesh — and these
    tools are CPU-mesh tools whatever the environment's default platform
    is.  Must run before the first JAX backend use."""
    os.environ["XLA_FLAGS"] = cpu8_flags()
    import jax
    jax.config.update("jax_platforms", "cpu")


def quantile_stats_raw(samples):
    """(median_s, q25_s, q75_s) unrounded, in seconds, linearly
    interpolated.  Derived rates (GB/s) must divide by THESE, not the
    display-rounded ms from quantile_stats: a sub-50 ns median rounds to
    0.0 ms at 4 digits and a rate computed from it divides by zero."""
    xs = sorted(samples)
    n = len(xs)

    def q(p):
        i = p * (n - 1)
        lo, hi = int(i), min(int(i) + 1, n - 1)
        return xs[lo] + (xs[hi] - xs[lo]) * (i - lo)

    return q(0.5), q(0.25), q(0.75)


def quantile_stats(samples, digits=1):
    """(median, [q25, q75]) in ms from samples in seconds, rounded for
    display.  The IQR is the honesty term: a shared host can't promise
    tight medians, so every artifact carries its spread."""
    med, q25, q75 = quantile_stats_raw(samples)
    return (round(med * 1e3, digits),
            [round(q25 * 1e3, digits), round(q75 * 1e3, digits)])


def pin_cores():
    """Pin this process to a stable core subset when that actually changes
    anything; return the pinned set (or None) for the conditions block.

    Pinning cannot evict other processes, but it stops scheduler migration
    from adding its own variance.  Only a *strict subset* of the available
    cores is ever reported: pinning to everything is a no-op and recording
    it would claim a stabilization that didn't happen.  Opt out with
    BYTEPS_BENCH_PIN=off; choose cores with e.g. BYTEPS_BENCH_PIN=0-3 or
    BYTEPS_BENCH_PIN=0,2,5 (a bare "1" pins core 1 — every non-empty
    value that isn't "off"/"none" is a core spec).
    """
    spec = os.environ.get("BYTEPS_BENCH_PIN", "")
    if spec.lower() in ("off", "none"):
        return None
    try:
        avail = sorted(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        return None
    if spec:
        try:
            want = set()
            for part in spec.split(","):
                lo, _, hi = part.partition("-")
                want |= set(range(int(lo), int(hi or lo) + 1))
            want &= set(avail)
        except ValueError:
            return None  # malformed spec: run unpinned rather than die
        if want == set(avail):
            # explicit spec covering every available core: setting the
            # affinity is a no-op; honoring the strict-subset invariant
            # beats honoring the spec literally
            return None
    elif len(avail) >= 4:
        # leave core 0 (interrupt-heavy) out when there's room
        want = set(avail[1:])
    else:
        # 1-3 cores: any default pin is the full set, i.e. a no-op —
        # don't report a stabilization that didn't happen
        return None
    if not want:
        return None
    try:
        os.sched_setaffinity(0, want)
    except OSError:
        return None
    return sorted(want)


def conditions_block(pinned=None, note: str = "") -> dict:
    """The measurement-environment stamp every bench JSON carries."""
    return {
        "pinned_cores": pinned,
        "host_cores": os.cpu_count(),
        "loadavg_1m": (round(os.getloadavg()[0], 2)
                       if hasattr(os, "getloadavg") else None),
        "note": note,
    }


def metrics_diag() -> dict:
    """Diagnostics counters embedded in bench artifacts (bench_smoke,
    overlap_bench): a regression record arrives with its own evidence —
    did the compile cache stop hitting, did AOT warm fail, did the wire
    start retransmitting.  ONE copy, so the benches cannot drift in
    which counters they snapshot."""
    from byteps_tpu.common.telemetry import counters
    return {
        "compile_cache_hit": counters.get("engine.compile_cache_hit"),
        "compile_cache_miss": counters.get("engine.compile_cache_miss"),
        "aot_compiled": counters.get("engine.aot_compiled"),
        "aot_compile_failed": counters.get("engine.aot_compile_failed"),
        "retransmits": counters.get("integrity.retransmit"),
        "crc_rejects": counters.get("integrity.crc_reject"),
    }
