"""From the profiler's ``.xplane.pb`` to numbers.

Read with nothing but ``jax.profiler.ProfileData``.  What a v5e trace
holds (looked at by hand, PR 22): one plane ``/device:TPU:<i>`` per chip
with the lines ``Steps``, ``XLA Modules`` (one event per executed
program, ``jit_step(<fingerprint>)``), ``XLA Ops`` (one event per
executed HLO instruction on the TensorCore, named by the instruction's
whole text, ``%fusion.24 = (f32[...]) fusion(...)``; a Mosaic call is
``%attn.72 = ... custom-call(...)``) and ``Async XLA Ops`` (copies,
slices and collectives in flight, ``%copy-start.854 = ...``, lasting from
their start to their done).  Host threads are lines of ``/host:CPU`` and
hold the benchmark's own ``jax.profiler.TraceAnnotation`` spans
(``bench.*``).  Device and host events share one clock (nanoseconds from
the start of the trace).

Everything here works on plain ``(name, start_ns, end_ns)`` tuples so the
arithmetic is testable without a trace.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from . import intervals as iv

Event = Tuple[str, float, float]          # name, start_ns, end_ns

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.traced_window"
# matched against an instruction's OPCODE: XLA names instructions after
# the JAX primitive (``%psum.3164 = f32[...] all-reduce(...)``), so the
# name alone misses collectives (two of eleven in bert_large.fused_4c)
COLLECTIVE_RE = re.compile(
    r"^(all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_NUMBER = re.compile(r"\.\d+$")


class Trace:
    """Device ops / modules per chip and the host's ``bench.*`` spans."""

    def __init__(self):
        self.ops: Dict[int, List[Event]] = defaultdict(list)
        self.async_ops: Dict[int, List[Event]] = defaultdict(list)
        self.modules: Dict[int, List[Event]] = defaultdict(list)
        self.host: List[Event] = []
        self.opcodes: Dict[str, str] = {}     # instruction name -> opcode

    @property
    def device_ids(self) -> List[int]:
        return sorted(set(self.ops) | set(self.modules))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction_name(event_name: str) -> str:
    """``%fusion.24 = (f32[...]) fusion(...)`` -> ``fusion.24``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def opcode(event_name: str) -> str:
    """``%psum.3164 = f32[8]{0:T(8)} all-reduce(f32[8] %x), ...`` ->
    ``all-reduce``: the first lower-case word that opens a parenthesis
    after a space (shapes and tilings follow ``:`` or ``,``).  A bare
    instruction name stands for itself without its number."""
    m = _OPCODE.search(event_name)
    return m.group(1) if m else _NUMBER.sub("", instruction_name(event_name))


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                dest = {OPS_LINE: trace.ops, ASYNC_LINE: trace.async_ops,
                        MODULES_LINE: trace.modules}[line.name][
                            int(m.group(1))]
                for ev in line.events:
                    name = instruction_name(ev.name)
                    trace.opcodes.setdefault(name, opcode(ev.name))
                    dest.append((name, float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns)))
            elif not m:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        trace.host.append(
                            (ev.name, float(ev.start_ns),
                             float(ev.start_ns + ev.duration_ns)))
    return trace


def window(trace: Trace) -> Tuple[float, float]:
    """The traced window: the benchmark's own ``bench.traced_window``
    span; failing that, the hull of everything recorded."""
    spans = [(s, e) for n, s, e in trace.host if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    every = [(s, e) for evs in list(trace.ops.values())
             + list(trace.modules.values()) for _, s, e in evs]
    every += [(s, e) for _, s, e in trace.host]
    if not every:
        raise ValueError("empty trace")
    return min(s for s, _ in every), max(e for _, e in every)


def _collective(name: str, opcodes: Dict[str, str]):
    return COLLECTIVE_RE.match(opcodes.get(name) or opcode(name))


def collective_intervals(ops: List[Event], async_ops: List[Event] = (),
                         opcodes: Optional[Dict[str, str]] = None
                         ) -> List[iv.Interval]:
    """Intervals in which a collective is in flight on one device.  A
    synchronous collective is its own event in ``XLA Ops``.  An
    asynchronous one is one event of ``Async XLA Ops`` lasting from its
    start to its done; where that line holds none, it is taken from the
    start of ``<op>-start.N`` to the end of the next ``<op>-done`` of the
    same kind in ``XLA Ops`` (XLA numbers the two halves independently,
    so they pair in order)."""
    opcodes = opcodes or {}
    in_flight = [(s, e) for n, s, e in async_ops if _collective(n, opcodes)]
    out: List[iv.Interval] = list(in_flight)
    open_: Dict[str, List[float]] = defaultdict(list)
    for name, s, e in sorted(ops, key=lambda ev: ev[1]):
        m = _collective(name, opcodes)
        if not m:
            continue
        kind, half = m.group(1), m.group(2)
        if half is None:
            out.append((s, e))
        elif in_flight:
            continue              # the async line already has the pair
        elif half == "-start":
            open_[kind].append(s)
        else:
            out.append((open_[kind].pop(0) if open_[kind] else s, e))
    return out


def compute_intervals(ops: List[Event],
                      opcodes: Optional[Dict[str, str]] = None
                      ) -> List[iv.Interval]:
    return [(s, e) for n, s, e in ops if not _collective(n, opcodes or {})]


def reduce(trace: Trace, steps: int) -> dict:
    """Per-step device numbers of the traced window, averaged over the
    chips used.  Times in the result are SECONDS."""
    lo, hi = window(trace)
    ids = trace.device_ids
    if not ids:
        raise ValueError("trace holds no /device:TPU:<i> plane")
    busy, module, coll, exposed = [], [], [], []

    def clipped(events):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events
                if min(e, hi) > max(s, lo)]

    for d in ids:
        ops = clipped(trace.ops[d])
        module.append(sum(e - s for _, s, e in clipped(trace.modules[d])))
        c = collective_intervals(ops, clipped(trace.async_ops[d]),
                                 trace.opcodes)
        # busy: an op runs on the TensorCore, or a collective is in flight
        busy.append(iv.total([(s, e) for _, s, e in ops] + c))
        coll.append(iv.total(c))
        exposed.append(iv.total(iv.subtract(
            c, compute_intervals(ops, trace.opcodes))))
    n = len(ids)
    steps = max(1, steps)
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "step_device_s": sum(module) / n / steps / 1e9,
        "collective_s": sum(coll) / n / steps / 1e9,
        "collective_exposed_s": sum(exposed) / n / steps / 1e9,
    }


def op_seconds(trace: Trace, names, steps: int = 1) -> float:
    """Seconds per step (averaged over chips) in device ops whose
    instruction name is in ``names``."""
    lo, hi = window(trace)
    want = set(names)
    ids = trace.device_ids
    tot = sum(min(e, hi) - max(s, lo)
              for d in ids for n, s, e in trace.ops[d]
              if n in want and min(e, hi) > max(s, lo))
    return tot / max(1, len(ids)) / max(1, steps) / 1e9


def top_device_ops(trace: Trace, k: int = 10) -> List[list]:
    """The k HLO instructions that took most device time on the first
    chip, summed over the traced steps: ``[[name, seconds], ...]``."""
    lo, hi = window(trace)
    ids = trace.device_ids
    if not ids:
        return []
    acc: Dict[str, float] = defaultdict(float)
    for n, s, e in trace.ops[ids[0]]:
        if min(e, hi) > max(s, lo):
            acc[n] += (min(e, hi) - max(s, lo)) / 1e9
    return [[n, t] for n, t in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:k]]


def _host_span_at(host: List[Event], t: float) -> Optional[str]:
    """The innermost (latest-started) ``bench.*`` span open at t, the
    window span itself aside."""
    best = None
    for n, s, e in host:
        if n != WINDOW_SPAN and s <= t < e:
            if best is None or s >= best[1]:
                best = (n, s)
    return best[0] if best else None


def idle_gaps(trace: Trace, k: int = 10) -> List[list]:
    """Idle seconds of the first chip inside the window, summed by what
    the host was doing at the middle of each gap (the benchmark's own
    span open at that moment; ``(no span)`` where none was):
    ``[[name, seconds], ...]``, largest first."""
    lo, hi = window(trace)
    ids = trace.device_ids
    if not ids:
        return []
    busy = [(s, e) for _, s, e in trace.ops[ids[0]]]
    acc: Dict[str, float] = defaultdict(float)
    for s, e in iv.gaps(busy, lo, hi):
        acc[_host_span_at(trace.host, (s + e) / 2) or "(no span)"] += \
            (e - s) / 1e9
    return [[n, t] for n, t in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:k]]


_MOSAIC_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?"
    r"custom_call_target=\"tpu_custom_call\"[^\n]*?"
    r"op_name=\"([^\"]*)\"", re.M)


def mosaic_ops(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> JAX op_name for every Mosaic (Pallas)
    custom call of a compiled program's text: the kernel names the trace
    reduction looks for.  A backward kernel's op_name holds
    ``transpose(``."""
    return {m.group(1): m.group(2)
            for m in _MOSAIC_INSTR.finditer(hlo_text)}
