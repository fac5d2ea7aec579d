"""ISSUE 23 — the engine-mode step's phases (``common/tracing.py``
``phase``): each is a ``jax.profiler.TraceAnnotation`` on the thread that
does the work, in any profiler session, AND milliseconds in the step's
``StepStats`` — from one enter/exit pair.

The traced half runs engine-mode ``DistributedOptimizer`` steps under a
real ``jax.profiler.start_trace`` on the CPU mesh, reads the
``.xplane.pb`` back with ``jax.profiler.ProfileData`` and holds the spans
against each other and against the counters; the untraced half holds
that nothing is recorded, the counters still fill, and a fused step never
touches them.

Since ISSUE 24 the tree's float leaves ride a bucket, one engine tensor
for all four: the traced half runs twice, once on a tree whose leaves
share a bucket (enqueue / submit fire once a step and carry ``leaves``)
and once on leaves at the bucket cap, which go per leaf as before.
"""

import collections
import glob
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import byteps_tpu as bps  # noqa: E402
from byteps_tpu.common import tracing  # noqa: E402
from byteps_tpu.common.config import Config, set_config  # noqa: E402
from byteps_tpu.jax import DistributedOptimizer  # noqa: E402

# the spans that feed an attribution component of the same name
PHASES = ("enqueue", "submit", "wait", "plan", "dispatch", "sync", "assemble")
PER_TENSOR = ("bps.engine.enqueue", "bps.engine.submit")
PER_UNIT = ("bps.engine.dispatch", "bps.engine.sync", "bps.engine.assemble")
TRACED_STEPS = 3          # the first is cold: its units compile
PREEMPTION_MS = 25.0      # room for one descheduled thread under -n 6
PART = 1 << 16            # pinned partition_bytes = bytes of every chunk
LEAVES = 4
# leaf elements -> what one step pushes.  The bucket cap is 16 partitions:
# four 2-chunk leaves share one bucket (half the cap: were they larger,
# two identical buckets of two would be cut), a 16-chunk leaf is at the
# cap and goes alone.
BUCKETED = dict(leaf_elems=1 << 15, tensors=1, chunks=8, buckets=1,
                bucketed_leaves=LEAVES)
PER_LEAF = dict(leaf_elems=1 << 18, tensors=LEAVES, chunks=64, buckets=0,
                bucketed_leaves=0)


def _tree(n_ranks, leaf_elems=1 << 16, leaves=LEAVES):
    params = {f"w{i}": jnp.zeros((leaf_elems,), jnp.float32)
              for i in range(leaves)}
    grads = jax.tree.map(
        lambda p: jnp.stack([p + r for r in range(n_ranks)]), params)
    return params, grads


def _engine_steps(n_steps, traced_dir=None, leaf_elems=1 << 16,
                  part_bytes=PART):
    """Engine-mode steps on a fresh engine (4 leaves, pinned chunks of
    ``part_bytes``); returns the steps' StepStats."""
    set_config(Config(telemetry_on=True, partition_bytes=part_bytes,
                      partition_pinned=True))
    bps.init()
    try:
        eng = bps.core.api._require()
        params, grads = _tree(bps.size(), leaf_elems)
        opt = DistributedOptimizer(optax.sgd(0.1))
        state = opt.init(params)
        if traced_dir is not None:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(traced_dir, profiler_options=options)
        try:
            for _ in range(n_steps):
                updates, state = opt.update(grads, state, params)
                jax.block_until_ready(updates)
                # the syncer feeds a unit's "assemble" just AFTER the
                # callback that releases the caller: let the last one
                # land before the next step's first push closes the step
                time.sleep(0.002)
        finally:
            if traced_dir is not None:
                jax.profiler.stop_trace()
        eng.step_stats.flush()
        return eng.step_stats.history()
    finally:
        bps.shutdown()


Span = collections.namedtuple("Span", "name line start end args")


@pytest.fixture(scope="module", params=[BUCKETED, PER_LEAF],
                ids=["bucketed", "per_leaf"])
def traced(request, tmp_path_factory):
    """``(spans, steps, shape)``: every ``bps.*`` event of a profiler
    session over TRACED_STEPS engine-mode steps (line = index of its host
    thread line), those steps' StepStats by step number, and what one
    step pushes (BUCKETED or PER_LEAF)."""
    from jax.profiler import ProfileData
    shape = request.param
    trace_dir = str(tmp_path_factory.mktemp("phase_trace"))
    history = _engine_steps(TRACED_STEPS, traced_dir=trace_dir,
                            leaf_elems=shape["leaf_elems"])
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans, n_line = [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            n_line += 1
            for ev in line.events:
                if ev.name.startswith("bps."):
                    spans.append(Span(
                        ev.name, n_line, ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats)))
    steps = {s.step: s for s in history}
    assert sorted(steps) == list(range(1, TRACED_STEPS + 1)), history
    return spans, steps, shape


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _inside(inner, outers):
    return any(o.line == inner.line and o.start <= inner.start
               and inner.end <= o.end for o in outers)


def test_three_threads_on_three_lines(traced):
    """(a) caller, dispatcher and syncer each annotate on their own
    thread: their spans lie on three different host lines."""
    spans, _, _ = traced
    lines = {n: {s.line for s in _named(spans, n)}
             for n in ("bps.push_pull", "bps.engine.dispatch",
                       "bps.engine.sync")}
    assert all(len(v) == 1 for v in lines.values()), lines
    assert len(set.union(*lines.values())) == 3, lines
    # plan rides the dispatcher's line, assemble the syncer's
    assert ({s.line for s in _named(spans, "bps.engine.plan")}
            == lines["bps.engine.dispatch"])
    assert ({s.line for s in _named(spans, "bps.engine.assemble")}
            == lines["bps.engine.sync"])


def test_caller_spans_nest(traced):
    """(b) every enqueue / submit / wait lies inside a ``bps.push_pull``
    and every ``bps.push_pull`` inside a ``bps.adapter.update``."""
    spans, _, shape = traced
    updates = _named(spans, "bps.adapter.update")
    pushes = _named(spans, "bps.push_pull")
    assert len(updates) == len(pushes) == TRACED_STEPS
    assert all(_inside(p, updates) for p in pushes)
    inner = [s for s in spans if s.name in PER_TENSOR + ("bps.engine.wait",)]
    # enqueue + submit once per engine tensor (a bucket is one), one wait
    assert len(inner) == TRACED_STEPS * (2 * shape["tensors"] + 1)
    assert all(_inside(s, pushes) for s in inner)


def test_dispatch_events_equal_dispatch_count(traced):
    """(c) one dispatch span per launched program: their number is the
    steps' summed ``StepStats.dispatches`` (a unit that compiled is the
    same span with ``compiled=1``), and ``chunks`` counts every task."""
    spans, steps, shape = traced
    units = _named(spans, "bps.engine.dispatch")
    assert len(units) == sum(s.dispatches for s in steps.values()) > 0
    assert all(s.chunks == shape["chunks"] for s in steps.values()), steps
    assert sum(u.args["width"] for u in units) == (
        shape["chunks"] * TRACED_STEPS)
    for n in ("bps.engine.sync", "bps.engine.assemble"):
        assert len(_named(spans, n)) == len(units)
    # making the tree's plan declared its tensors, every program the
    # dispatcher can form for them compiled on the caller's thread (a
    # bucket's inside its first "enqueue"): no unit ever compiles
    assert not any(u.args.get("compiled") for u in units)
    assert all("compile" not in s.attrib for s in steps.values())
    if shape["buckets"]:
        assert steps[1].attrib["enqueue"] > 10 * steps[3].attrib["enqueue"]


def test_a_unit_that_compiles_feeds_compile(tmp_path):
    """A tensor pushed by itself, undeclared, compiles at its first
    dispatch: that unit is ``bps.engine.dispatch`` with ``compiled=1``
    and feeds ``compile``, not ``dispatch``."""
    from jax.profiler import ProfileData
    set_config(Config(telemetry_on=True, partition_bytes=PART,
                      partition_pinned=True))
    bps.init()
    try:
        eng = bps.core.api._require()
        x = jnp.ones((bps.size(), 1 << 15), jnp.float32)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            jax.block_until_ready(bps.push_pull(x, "lone"))
            time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        stats = eng.step_stats.flush()
    finally:
        bps.shutdown()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    units = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "bps.engine.dispatch"]
    assert units and sum(u["width"] for u in units) == stats.chunks == 2
    assert any(u.get("compiled") for u in units), units
    assert stats.attrib["compile"] > 0
    assert all("leaves" not in u for u in units)


@pytest.mark.parametrize("component", PHASES + ("compile", "push_pull"))
def test_span_durations_are_the_counters(traced, component):
    """(d) per step, a phase's spans sum to its counter: both come from
    one enter/exit pair (the TraceMe opens a moment before the
    ``time.monotonic`` stamp and closes a moment after it, so the span is
    never the shorter by more than the counter's rounding).  How much
    LONGER the spans may be is judged over the traced steps together: a
    thread pre-empted between the TraceMe and the stamp (six xdist
    workers share this host) adds milliseconds to one span, once."""
    spans, steps, _ = traced
    span_total = want_total = n_spans = 0
    for n, stats in steps.items():
        if component == "push_pull":
            got = _named(spans, "bps.push_pull")
            want = stats.push_pull_ms
        elif component in ("dispatch", "compile"):
            got = [s for s in _named(spans, "bps.engine.dispatch")
                   if bool(s.args.get("compiled")) == (component == "compile")]
            want = stats.attrib.get(component, 0.0)
        else:
            got = _named(spans, f"bps.engine.{component}")
            want = stats.attrib.get(component, 0.0)
        got = [s for s in got if s.args["step"] == n]
        span_ms = sum(s.end - s.start for s in got) / 1e6
        assert bool(got) == (want > 0), (component, n, want)
        assert span_ms >= want - 0.002 * (len(got) + 1), (component, n)
        span_total += span_ms
        want_total += want
        n_spans += len(got)
    assert span_total <= (1.1 * want_total + 0.05 * n_spans + 0.01
                          + PREEMPTION_MS), (
        component, span_total, want_total, n_spans)


def test_spans_carry_step_and_tensor(traced):
    """Spans of one step share its number; per-leaf and per-unit spans
    name their tensor, dispatch its width and bytes."""
    spans, steps, _ = traced
    assert all(s.args.get("step") in steps for s in spans), [
        s for s in spans if s.args.get("step") not in steps]
    for s in spans:
        if s.name in PER_TENSOR + PER_UNIT:
            assert str(s.args["tensor"]).startswith("grad['w"), s
    for u in _named(spans, "bps.engine.dispatch"):
        assert u.args["bytes"] == u.args["width"] * PART, u


def test_bucket_counters_and_leaves_argument(traced):
    """ISSUE 24: the step counts its bucket tensors and the leaves that
    rode them (the other ``pushes - buckets`` tensors went per leaf),
    and a bucket's enqueue / dispatch spans carry ``leaves``; a leaf
    pushed alone carries none."""
    spans, steps, shape = traced
    for stats in steps.values():
        assert stats.pushes == shape["tensors"], stats
        assert (stats.buckets, stats.bucketed_leaves) == (
            shape["buckets"], shape["bucketed_leaves"]), stats
    tagged = [s for s in spans
              if s.name in ("bps.engine.enqueue", "bps.engine.dispatch")]
    assert tagged
    for s in tagged:
        if shape["buckets"]:
            assert s.args["leaves"] == LEAVES, s
            assert s.args["tensor"] == "grad['w0']+3", s
        else:
            assert "leaves" not in s.args, s


@pytest.mark.parametrize("shape", [BUCKETED, PER_LEAF],
                         ids=["bucketed", "per_leaf"])
def test_without_a_session_nothing_is_recorded(shape):
    """No profiler session: no annotation is ever built, the counters
    fill all the same, no step compiles (the plan declared every
    program), and the caller thread's three phases account for the whole
    push_pull (within 5 %)."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    fed = []
    with tracing.phase("bps.test.none", fed.append) as ph:
        ph.note(step=1)
    assert ph.ann is None and fed == [(ph.t1 - ph.t0) * 1e3]
    steps = _engine_steps(10, leaf_elems=shape["leaf_elems"])[2:]
    assert len(steps) == 8
    for s in steps:
        assert "compile" not in s.attrib, s.attrib
        assert s.dispatches > 0 and s.chunks == shape["chunks"]
        assert (s.pushes, s.buckets, s.bucketed_leaves) == (
            shape["tensors"], shape["buckets"], shape["bucketed_leaves"])
        assert all(s.attrib.get(c, 0.0) > 0 for c in PHASES), s.attrib
        assert s.push_pull_ms <= s.wall_ms
    shares = sorted((s.attrib["enqueue"] + s.attrib["submit"]
                     + s.attrib["wait"]) / s.push_pull_ms for s in steps)
    assert 0.95 <= shares[len(shares) // 2] <= 1.0005, shares


def test_phase_late_facts_and_feeds():
    """The feed can be switched before exit (dispatch -> compile) and a
    phase with no feed records nothing anywhere."""
    a, b = [], []
    with tracing.phase("bps.test.switch", a.append) as ph:
        ph.feed = b.append
    assert a == [] and b == [(ph.t1 - ph.t0) * 1e3]
    with tracing.phase("bps.test.nofeed") as ph:
        pass
    assert ph.t1 >= ph.t0 > 0.0


def test_phase_annotates_inside_a_session(tmp_path):
    """Inside a session the annotation exists, named as given, with the
    arguments ``note`` gave it."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert jax.profiler.TraceAnnotation.is_enabled()
        with tracing.phase("bps.test.session") as ph:
            ph.note(step=7)
            ph.note(late=1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    got = [dict(ev.stats) for plane in ProfileData.from_file(path).planes
           for line in plane.lines for ev in line.events
           if ev.name == "bps.test.session"]
    assert got == [{"step": 7, "late": 1}]


def test_fused_step_leaves_step_stats_untouched():
    """A fused ``make_dp_train_step`` step runs no engine code: no step,
    no counter, no dispatch."""
    from byteps_tpu.comm.mesh import get_comm
    from byteps_tpu.parallel import make_dp_train_step, replicate, shard_batch
    set_config(Config(telemetry_on=True))
    bps.init()
    try:
        comm = get_comm()
        eng = bps.core.api._require()
        params = {"w": jnp.ones((8, 4), jnp.float32)}
        tx = optax.sgd(0.1)
        step = make_dp_train_step(
            comm, lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2), tx,
            donate=False)
        p, o = replicate(comm, params), replicate(comm, tx.init(params))
        batch = shard_batch(comm, {"x": jnp.asarray(
            np.ones((4 * bps.size(), 8), np.float32))})
        for _ in range(2):
            p, o, loss = step(p, o, batch)
        jax.block_until_ready(loss)
        assert eng.step_stats.current_step == 0
        assert eng.step_stats.flush() is None
        assert eng.step_stats.history() == []
        assert eng.stats == {"dispatches": 0, "chunks": 0,
                             "whole_units": 0}
    finally:
        bps.shutdown()
