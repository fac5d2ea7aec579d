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
    # dispatcher can form for them compiled on the caller's thread: no
    # unit ever compiles.  A bucket's programs compile inside its first
    # "enqueue", which then feeds "compile" and says ``compiled=1``
    # (ISSUE 33)
    assert not any(u.args.get("compiled") for u in units)
    compiled = [s for s in _named(spans, "bps.engine.enqueue")
                if s.args.get("compiled")]
    assert [s.args["step"] for s in compiled] == [1] * shape["buckets"]
    assert all("compile" not in s.attrib
               for n, s in steps.items() if n > 1)
    if shape["buckets"]:
        # the bucket's first push fed "compile" and nothing of it
        # "enqueue" — held by the spans' own arguments and the SAME
        # step's counters, not by two steps' wall times against each
        # other (a thread descheduled in step 3 under -n 6 broke a
        # ratio, ISSUE 48): what step 1 has under "compile" is its
        # ``compiled=1`` spans, from one enter/exit pair (the span is
        # never the shorter; (d) holds how much longer it may be)
        assert "enqueue" not in steps[1].attrib   # its one tensor compiled
        span_ms = sum(s.end - s.start for s in compiled) / 1e6
        assert 0 < steps[1].attrib["compile"] <= (
            span_ms + 0.002 * (len(compiled) + 1))
    else:
        assert "compile" not in steps[1].attrib


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


@pytest.mark.parametrize("component", PHASES + ("compile", "push_pull",
                                                "tx_update", "update"))
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
        elif component == "update":
            got = _named(spans, "bps.adapter.update")
            want = stats.update_ms
        elif component == "tx_update":
            got = _named(spans, "bps.adapter.tx_update")
            want = stats.attrib.get(component, 0.0)
        elif component in ("dispatch", "enqueue", "compile"):
            # a launch, or a bucket's first enqueue, that compiled feeds
            # "compile" and carries ``compiled=1``
            names = (("bps.engine.dispatch", "bps.engine.enqueue")
                     if component == "compile"
                     else (f"bps.engine.{component}",))
            got = [s for s in spans if s.name in names
                   and bool(s.args.get("compiled")) == (component == "compile")]
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
    with tracing.phase("bps.test.none", lambda *ms: fed.append(ms)) as ph:
        ph.note(step=1)
    (wall, cpu), = fed          # no session, not a once-a-step phase
    assert ph.ann is None and wall == (ph.t1 - ph.t0) * 1e3 and cpu is None
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
    with tracing.phase("bps.test.switch", lambda *ms: a.append(ms)) as ph:
        ph.feed = lambda *ms: b.append(ms)
    assert a == [] and [wall for wall, _ in b] == [(ph.t1 - ph.t0) * 1e3]
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
    cpu_us = got[0].pop("cpu_us")        # ISSUE 33: every span's CPU time
    assert got == [{"step": 7, "late": 1}] and 0 <= cpu_us < 10_000


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


# -- ISSUE 33: every phase reads two clocks ----------------------------------

# the components a ``phase`` feeds, by the thread whose CPU they are
CPU_COMPONENTS = ("enqueue", "submit", "wait", "plan", "dispatch", "compile",
                  "sync", "assemble", "tx_update")
# the once-a-step phases read the thread clock in every step, the
# per-tensor and per-unit ones only under a profiler session
ONCE_A_STEP = ("wait", "tx_update")
THREAD_PHASES = {"caller": ("enqueue", "submit", "wait", "tx_update"),
                 "dispatcher": ("plan", "dispatch"),
                 "syncer": ("sync", "assemble")}
CPU_SLACK_MS = 0.5        # clock granularity, the two stamps' own cost


def _fed():
    got = []
    return got, lambda wall, cpu: got.append((wall, cpu))


def test_phase_feeds_wall_and_cpu():
    """The feed gets both clocks of the one enter/exit pair: the wall is
    the monotonic stamps', the CPU lies inside it."""
    got, feed = _fed()
    with tracing.phase("bps.test.two_clocks", feed, cpu=True) as ph:
        sum(range(20_000))
    (wall, cpu), = got
    assert wall == (ph.t1 - ph.t0) * 1e3
    assert 0 < cpu <= wall + 0.01


@pytest.mark.parametrize("how", ["sleeps", "spins"])
def test_cpu_is_the_running_part_of_the_wall(how):
    """A phase that sleeps reads CPU ~ 0 and one that spins reads CPU ~
    wall (the first of up to twenty tries that does: beside five busy
    xdist workers another process holds the core more than once)."""
    good = (lambda x: x < 0.05) if how == "sleeps" else (lambda x: x > 0.9)
    shares = []
    for _ in range(20):
        got, feed = _fed()
        with tracing.phase("bps.test." + how, feed, cpu=True):
            if how == "sleeps":
                time.sleep(0.02)
            else:
                end = time.thread_time() + 0.02
                while time.thread_time() < end:
                    pass
        (wall, cpu), = got
        assert wall >= 20.0 and cpu <= wall + 0.01
        shares.append(cpu / wall)
        if good(shares[-1]):
            break
    assert good(shares[-1]), shares


@pytest.fixture
def clock_calls(monkeypatch):
    """Counts every read of the thread CPU clock a ``phase`` makes."""
    calls = []

    def counted():
        calls.append(1)
        return time.thread_time()
    monkeypatch.setattr(tracing, "_thread_time", counted)
    return calls


@pytest.mark.parametrize("fed,cpu,reads", [
    (False, False, 0), (False, True, 0), (True, False, 0), (True, True, 2)],
    ids=["no_feed", "no_feed_cpu", "feed", "feed_cpu"])
def test_the_thread_clock_is_read_only_for_a_reader(clock_calls, fed, cpu,
                                                    reads):
    """Outside a session a phase reads the thread clock (a system call)
    only where it was asked to AND something will take the number: no
    feed, no call at all; a feed without ``cpu=True`` gets ``None``."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    got, feed = _fed()
    with tracing.phase("bps.test.cost", feed if fed else None, cpu=cpu) as ph:
        pass
    assert len(clock_calls) == reads
    assert len(got) == (1 if fed else 0) and ph.t1 >= ph.t0
    if fed:
        assert (got[0][1] is not None) == cpu


def test_a_session_reads_the_clock_for_every_phase(tmp_path, clock_calls):
    """Inside a session every phase reads the clock, fed or not, asked
    or not: its span carries ``cpu_us``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        got, feed = _fed()
        with tracing.phase("bps.test.in_session", feed):
            pass
        with tracing.phase("bps.test.in_session_unfed"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert len(clock_calls) == 4
    (_, cpu), = got
    assert cpu is not None and cpu >= 0


@pytest.mark.parametrize("telemetry", [False, True], ids=["off", "on"])
def test_clock_reads_a_step_without_a_session(clock_calls, monkeypatch,
                                              telemetry):
    """Whole engine-mode steps with no session.  ``BYTEPS_TELEMETRY_ON=0``:
    every phase is entered and none reads the thread clock, no step is
    recorded.  On: the three once-a-step phases read it (six calls a
    step), the ~10 per-tensor and per-unit ones do not."""
    set_config(Config(telemetry_on=telemetry, partition_bytes=PART,
                      partition_pinned=True))
    bps.init()
    try:
        eng = bps.core.api._require()
        params, grads = _tree(bps.size(), BUCKETED["leaf_elems"])
        opt = DistributedOptimizer(optax.sgd(0.1))
        state = opt.init(params)
        for _ in range(3):
            updates, state = opt.update(grads, state, params)
        jax.block_until_ready(updates)
        assert eng.stats["dispatches"] > 0
        if not telemetry:
            assert set(eng.phase_feeds.values()) == {None}
            assert eng.step_stats.history() == []
    finally:
        bps.shutdown()
    assert len(clock_calls) == (3 * 6 if telemetry else 0)


@pytest.fixture(scope="module")
def cpu_steps():
    """StepStats of eight untraced engine-mode steps on the bucketed
    tree; the first one compiles (inside the bucket's first enqueue)."""
    steps = _engine_steps(8, leaf_elems=BUCKETED["leaf_elems"])
    assert [s.step for s in steps] == list(range(1, 9))
    return steps


@pytest.mark.parametrize("component", CPU_COMPONENTS)
def test_every_fed_component_has_its_cpu(traced, cpu_steps, component):
    """On a real engine step under a session each component a phase
    feeds carries the CPU milliseconds inside its wall — present
    wherever the wall is, and never above it.  Without a session only
    the once-a-step phases read the clock."""
    _, steps, shape = traced
    have = [s for s in steps.values() if component in s.attrib_cpu]
    if component == "compile":
        # a bucket's first push, inside its enqueue
        assert [s.step for s in have] == [1] * shape["buckets"]
    else:
        assert len(have) >= TRACED_STEPS - 1, (component, steps)
    for s in have:
        assert 0 <= s.attrib_cpu[component] <= (
            s.attrib[component] + CPU_SLACK_MS), (component, s)
    for s in steps.values():
        assert set(s.attrib_cpu) <= set(CPU_COMPONENTS)
        assert (component in s.attrib_cpu) == (
            s.attrib.get(component, 0.0) > 0 or component == "sync"), s
    for s in cpu_steps:
        assert (component in s.attrib_cpu) == (component in ONCE_A_STEP), s
        if component in ONCE_A_STEP:
            assert 0 <= s.attrib_cpu[component] <= (
                s.attrib[component] + CPU_SLACK_MS), (component, s)


def test_a_blocked_phase_is_not_a_poll(traced, cpu_steps):
    """``wait`` and ``sync`` are blocked phases: over the steady steps
    their CPU is a small part of their wall (the caller parks on the
    handles' events, the syncer in ``block_until_ready``)."""
    _, steps, _ = traced
    for c, some in (("wait", cpu_steps[2:]), ("sync", list(steps.values()))):
        wall = sum(s.attrib[c] for s in some)
        cpu = sum(s.attrib_cpu[c] for s in some)
        assert cpu <= 0.5 * wall + CPU_SLACK_MS, (c, cpu, wall)


@pytest.mark.parametrize("thread", sorted(THREAD_PHASES))
def test_thread_cpu_covers_the_threads_phases(traced, thread):
    """``thread_cpu`` is the thread's WHOLE CPU over the step, read at
    its two boundaries: never less than the CPU inside that thread's
    phases (what is over is the CPU outside any span)."""
    _, steps, _ = traced
    for n, s in steps.items():
        assert sorted(s.thread_cpu) == sorted(THREAD_PHASES), s
        if n == 1:
            continue                      # compiles, on either thread
        inside = sum(s.attrib_cpu.get(c, 0.0) for c in THREAD_PHASES[thread])
        assert s.thread_cpu[thread] >= inside - CPU_SLACK_MS, (thread, s)
        assert s.thread_cpu[thread] <= s.wall_ms + CPU_SLACK_MS, (thread, s)


def test_push_pull_cpu_is_the_callers_cpu_inside_the_call(traced, cpu_steps):
    """``push_pull_cpu_ms``: of ``push_pull_ms``, what the caller ran —
    in every step, session or none; it holds the CPU of the caller's
    phases inside the call and lies inside the caller's whole CPU."""
    _, steps, _ = traced
    for s in list(steps.values()) + cpu_steps:
        assert 0 < s.push_pull_cpu_ms <= s.push_pull_ms + CPU_SLACK_MS, s
        assert s.push_pull_cpu_ms + s.attrib_cpu["tx_update"] <= (
            s.thread_cpu["caller"] + CPU_SLACK_MS), s
        assert s.push_pull_cpu_ms >= s.attrib_cpu["wait"] - CPU_SLACK_MS, s
    for n, s in steps.items():
        if n > 1:
            inside = sum(s.attrib_cpu[c] for c in ("enqueue", "submit",
                                                   "wait"))
            assert s.push_pull_cpu_ms >= inside - CPU_SLACK_MS, s


def test_update_ms_holds_push_pull_and_tx_update(cpu_steps):
    """The adapter's three program spans: ``update_ms`` (the whole
    ``update()``) holds ``push_pull_ms`` and ``attrib["tx_update"]``;
    what is left is the adapter's own (flatten, names, unflatten)."""
    for s in cpu_steps:
        own = s.update_ms - s.push_pull_ms - s.attrib["tx_update"]
        assert own >= -0.005, s
        # the step's wall starts at its first push, a moment INSIDE
        # update(): a caller descheduled before that push adds to
        # update_ms alone
        assert s.update_ms <= s.wall_ms + PREEMPTION_MS, s
        assert s.attrib["tx_update"] > 0


def test_the_first_enqueue_of_a_bucket_feeds_compile(cpu_steps):
    """A bucket's first push compiles its programs inside
    ``bps.engine.enqueue``: that enqueue feeds ``compile``, and
    ``enqueue`` is back from the second step on."""
    first, second = cpu_steps[0], cpu_steps[1]
    assert "enqueue" not in first.attrib
    # each against its own step, not one step's wall against another's
    # (a thread descheduled in the second step broke a ratio, ISSUE 48)
    assert first.attrib["compile"] > 0 and second.attrib["enqueue"] > 0
    assert "compile" not in second.attrib
    assert all("compile" not in s.attrib for s in cpu_steps[1:])


def test_tx_update_span_sits_after_push_pull_in_its_step(traced):
    """``bps.adapter.tx_update`` nests inside ``bps.adapter.update``,
    after that update's ``bps.push_pull``, on the caller's line, and
    carries the step of the push_pull that preceded it — the step whose
    ``attrib["tx_update"]`` it feeds (a step is finalized by the NEXT
    step's first push)."""
    spans, steps, _ = traced
    updates = _named(spans, "bps.adapter.update")
    pushes = {s.args["step"]: s for s in _named(spans, "bps.push_pull")}
    txs = _named(spans, "bps.adapter.tx_update")
    assert sorted(s.args["step"] for s in txs) == sorted(steps)
    for tx in txs:
        push = pushes[tx.args["step"]]
        assert tx.line == push.line and tx.start >= push.end
        outer, = [u for u in updates if _inside(tx, [u])]
        assert _inside(push, [outer]) and outer.args["step"] == tx.args["step"]
        got_ms = (tx.end - tx.start) / 1e6
        want = steps[tx.args["step"]].attrib["tx_update"]
        assert want - 0.01 <= got_ms <= 1.1 * want + PREEMPTION_MS


def test_every_span_carries_its_cpu_time(traced):
    """Inside a session every ``bps.*`` span carries ``cpu_us`` beside
    ``step``: the CPU microseconds inside the span, never above its
    duration; per step and phase they sum to the counter's CPU."""
    spans, steps, _ = traced
    assert spans
    for s in spans:
        assert isinstance(s.args.get("cpu_us"), int), s
        assert 0 <= s.args["cpu_us"] <= (s.end - s.start) / 1e3 + 50, s
    for n, stats in steps.items():
        for c in ("sync", "assemble", "plan", "wait", "submit"):
            us = sum(s.args["cpu_us"] for s in _named(spans, f"bps.engine.{c}")
                     if s.args["step"] == n)
            assert abs(us / 1e3 - stats.attrib_cpu[c]) <= 0.002 * (
                len(spans) + 1), (n, c)
        us, = [s.args["cpu_us"] for s in _named(spans, "bps.push_pull")
               if s.args["step"] == n]
        assert abs(us / 1e3 - stats.push_pull_cpu_ms) <= 0.002


def test_sharded_update_feeds_no_tx_update():
    """The sharded-update branch runs no ``tx.update`` on the caller:
    ``update_ms`` fills, ``tx_update`` is in neither dict."""
    set_config(Config(telemetry_on=True, sharded_update=True))
    bps.init()
    try:
        eng = bps.core.api._require()
        params, grads = _tree(bps.size(), 1 << 10, leaves=2)
        opt = DistributedOptimizer(optax.sgd(0.1))
        state = opt.init(params)
        for _ in range(3):
            updates, state = opt.update(grads, state, params)
            jax.block_until_ready(updates)
            time.sleep(0.002)
        eng.step_stats.flush()
        steps = eng.step_stats.history()
    finally:
        bps.shutdown()
    assert len(steps) == 3
    for s in steps:
        assert "tx_update" not in s.attrib and "tx_update" not in s.attrib_cpu
        assert s.update_ms >= s.push_pull_ms > 0


def test_update_before_init_feeds_nothing():
    """With no engine the adapter has no feeds: ``update()`` opens its
    span unfed and fails where it always did."""
    assert bps.core.api._engine is None
    opt = DistributedOptimizer(optax.sgd(0.1))
    with pytest.raises(RuntimeError, match="not initialized"):
        opt.update({"w": jnp.ones((1, 4))}, optax.EmptyState())


def _tracker():
    from byteps_tpu.common.telemetry import StepStatsTracker
    return StepStatsTracker(recorder=type(
        "R", (), {"record": lambda self, *a, **k: None})())


def test_a_feed_without_a_clock_reading_leaves_no_cpu_key():
    """``cpu_ms=None`` (a phase that did not read the clock) adds wall
    only: the component has no ``attrib_cpu`` key, never a 0."""
    tr = _tracker()
    tr.on_push("a", 8)
    tr.feed("dispatch")(2.0, None)
    tr.feed("dispatch")(3.0, 1.25)
    tr.feed("assemble")(4.0, None)
    tr.feed("sync")(1.0, None)
    tr.feed("push_pull")(9.0, 2.5)
    tr.feed("update")(12.0, None)
    done = tr.flush()
    assert done.attrib["dispatch"] == 5.0 and done.attrib["assemble"] == 4.0
    assert done.attrib_cpu == {"dispatch": 1.25}
    assert (done.push_pull_ms, done.push_pull_cpu_ms, done.update_ms) == (
        9.0, 2.5, 12.0)
    assert done.sync_stall_ms == done.attrib["sync"] == 1.0


def test_one_tracker_call_retires_a_unit():
    """``retire_unit``: the lagging tensor, the ``queue`` component and
    the unit's latency in one call; the latencies reach the
    ``engine.unit_sync_ms`` histogram at the step's boundary."""
    from byteps_tpu.common.telemetry import histograms
    tr = _tracker()
    n0 = histograms.count("engine.unit_sync_ms")
    tr.on_push("a", 8)
    tr.retire_unit("a", 1.5, 3.0)
    tr.retire_unit("b", None, 5.0)
    tr.retire_unit("c", 2.0, None)
    assert histograms.count("engine.unit_sync_ms") == n0
    done = tr.flush()
    assert done.lagging_tensor == "c" and done.attrib["queue"] == 3.5
    assert "queue" not in done.attrib_cpu
    assert histograms.count("engine.unit_sync_ms") == n0 + 2
    tr.on_push("a", 8)
    assert tr.flush().lagging_tensor is None
    assert histograms.count("engine.unit_sync_ms") == n0 + 2


def test_thread_cpu_leaves_out_what_it_cannot_read():
    """No engine thread registered: ``thread_cpu`` has the caller only.
    A step that another thread finalizes has no ``caller`` (two clocks
    cannot be subtracted): a key is left out, never 0."""
    import threading
    tr = _tracker()
    tr.open_call()
    tr.on_push("a", 8)
    sum(range(50_000))
    tr.open_call()
    tr.on_push("a", 8)                       # finalizes step 1, here
    first, = tr.history()
    assert list(first.thread_cpu) == ["caller"] and first.thread_cpu[
        "caller"] > 0
    t = threading.Thread(target=lambda: tr.on_push("a", 8))
    t.start()
    t.join()                                 # step 2 finalized over there
    assert tr.history()[1].thread_cpu == {}
    tr.register_thread("syncer")             # this thread, as the syncer
    tr.on_push("a", 8)                       # step 3 ends: registered late
    assert "syncer" not in tr.history()[2].thread_cpu
    sum(range(50_000))
    tr.on_push("a", 8)
    fourth = tr.history()[3]
    assert fourth.thread_cpu["syncer"] > 0
    assert fourth.thread_cpu["syncer"] == pytest.approx(
        fourth.thread_cpu["caller"], abs=0.5)


def test_cpu_gauges_describe_the_last_step():
    """The step's CPU readings are published like its walls: one gauge
    a component / thread, named by the literal tables."""
    from byteps_tpu.common import telemetry
    last = _engine_steps(3, leaf_elems=BUCKETED["leaf_elems"])[-1]
    g = bps.metrics_snapshot()["gauges"]
    assert sorted(last.attrib_cpu) == sorted(ONCE_A_STEP)
    for comp, ms in last.attrib_cpu.items():
        assert g[telemetry.ATTRIB_CPU_GAUGE_NAMES[comp]] == ms
    for role, ms in last.thread_cpu.items():
        assert g[telemetry.THREAD_CPU_GAUGE_NAMES[role]] == ms
    assert g["step.update_ms"] == last.update_ms
    assert g["step.push_pull_cpu_ms"] == last.push_pull_cpu_ms
    assert g["step.attrib_tx_update_ms"] == last.attrib["tx_update"]


def _new_gauge_names():
    from byteps_tpu.common import telemetry
    return (sorted(telemetry.ATTRIB_CPU_GAUGE_NAMES.values())
            + sorted(telemetry.THREAD_CPU_GAUGE_NAMES.values())
            + ["step.update_ms", "step.push_pull_cpu_ms",
               "step.attrib_tx_update_ms"])


@pytest.mark.parametrize("name", _new_gauge_names())
def test_every_new_gauge_is_in_the_established_names_table(name):
    """bpslint's metric-name rule reads docs/observability.md's table:
    each gauge ISSUE 33 publishes has its row there."""
    from tools.bpslint.rules_metrics import doc_names
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        assert name in doc_names(f.read().splitlines())


# ---- engine-mode ``DistributedOptimizer.update`` consumes its state ----
# (ISSUE 34) The optax update runs as ONE program of the adapter's own in
# which the engine's reduced gradients and the caller's state are donated,
# so every output lands in a buffer that dies in the call.  This JAX
# honours donation on the CPU, so the contract is guarded here: what is
# deleted, what is not, that the arithmetic is the plain jitted update's
# to the bit, and that the program is compiled once.  They live in THIS
# file because tier-1 runs `--dist loadfile`, which hands out files in the
# order of their test counts: as a file of their own, or in
# test_jax_adapter.py, they moved every later file's slot, and one of the
# multi-process chaos tests then met a heavier neighbour and failed on a
# heartbeat (three whole runs of three).  This file starts first either
# way.
import byteps_tpu.jax as bps_jax  # noqa: E402


@pytest.fixture
def session():
    bps.init()
    yield
    bps.shutdown()


GAUGE = "adapter.tx_update_donated_share"
TXS = {
    "sgd": lambda: optax.sgd(0.1),
    "adam": lambda: optax.adam(1e-2),
    "adamw_clip": lambda: optax.chain(optax.clip_by_global_norm(1.0),
                                      optax.adamw(1e-2)),
}


@pytest.fixture
def reduced_seen(monkeypatch):
    """What ``push_pull`` handed the adapter, call by call: the engine's
    own trees, and a copy of each made before the update could consume
    it."""
    seen = []
    real = bps_jax.push_pull

    def spy(tree, *a, **k):
        out = real(tree, *a, **k)
        seen.append((out, jax.tree.map(jnp.copy, out)))
        return out

    monkeypatch.setattr(bps_jax, "push_pull", spy)
    return seen


def _params():
    rng = np.random.RandomState(0)
    return {"w": jnp.asarray(rng.randn(24, 33).astype(np.float32)),
            "b": jnp.asarray(rng.randn(33).astype(np.float32)),
            "e": jnp.asarray(rng.randn(7, 5, 3).astype(np.float32))}


def _grads(params, step, ranks=8):
    rng = np.random.RandomState(100 + step)
    return jax.tree.map(lambda p: jnp.asarray(
        rng.randn(ranks, *p.shape).astype(np.float32)), params)


def _on_the_mesh(tree):
    """Placed as a training script places parameters and state, and as
    the update's own outputs are: the first call's signature is then
    every later call's."""
    from byteps_tpu.comm.mesh import get_comm
    return jax.device_put(tree, get_comm().replicated_sharding())


def _arrays(tree):
    return [x for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)]


def _deleted(tree):
    return [x.is_deleted() for x in _arrays(tree)]


def _same(a, b):
    got, want = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(got) == len(want)
    for x, y in zip(got, want):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_update_consumes_state_and_reduced_not_grads_nor_params(
        session, reduced_seen):
    params = _params()
    opt = bps_jax.DistributedOptimizer(optax.adam(1e-2))
    state = opt.init(params)
    grads = _grads(params, 0)
    updates, new_state = opt.update(grads, state, params)
    (reduced, _), = reduced_seen
    assert len(_arrays(state)) == 7 and all(_deleted(state))
    assert len(_arrays(reduced)) == 3 and all(_deleted(reduced))
    assert not any(_deleted(grads)) and not any(_deleted(params))
    assert not any(_deleted((updates, new_state)))
    # what a loop that kept the old state gets: JAX's own error
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(jax.tree.leaves(state)[0])


@pytest.mark.parametrize("jitted", [False, True], ids=["plain", "jitted"])
@pytest.mark.parametrize("name", sorted(TXS))
def test_outputs_are_the_plain_jitted_updates_bit_for_bit(
        session, reduced_seen, name, jitted):
    """Same body, same precision, same order: donation moves buffers, not
    bits.  (An update run op by op may differ in the last bit from ANY
    compiled one; the plain side is jitted for that reason.)"""
    tx = TXS[name]()
    plain = jax.jit(tx.update)
    opt = bps_jax.DistributedOptimizer(optax.GradientTransformation(
        tx.init, jax.jit(tx.update) if jitted else tx.update))
    params = _params()
    state = opt.init(params)
    for step in range(3):
        before = jax.tree.map(jnp.copy, state)
        updates, state = opt.update(_grads(params, step), state, params)
        _same((updates, state), plain(reduced_seen[-1][1], before, params))
        params = optax.apply_updates(params, updates)


def test_a_state_leaf_that_is_a_parameter_leaf_stays_alive(session):
    """An optimizer that starts an average AT the parameters hands back
    the same arrays in its state: XLA refuses a buffer that is donated
    and passed again in one call, so such a leaf is left undonated."""
    def init(params):
        return {"avg": params, "n": jnp.zeros((), jnp.int32)}

    def update(g, s, p):
        avg = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, s["avg"], p)
        return jax.tree.map(jnp.negative, g), {"avg": avg, "n": s["n"] + 1}

    params = _params()
    opt = bps_jax.DistributedOptimizer(
        optax.GradientTransformation(init, update))
    state = opt.init(params)
    assert state["avg"]["w"] is params["w"]
    _, new_state = opt.update(_grads(params, 0), state, params)
    assert not any(_deleted(params))
    assert state["n"].is_deleted()
    # the second step's average is the adapter's own: consumed whole
    _, newer = opt.update(_grads(params, 1), new_state, params)
    assert all(_deleted(new_state)) and int(newer["n"]) == 2
    np.testing.assert_allclose(np.asarray(newer["avg"]["b"]),
                               np.asarray(params["b"]), rtol=1e-6)


def test_one_array_twice_in_the_state_is_not_donated(session):
    """``f(donate(a), a)`` is refused whichever mention is donated."""
    def update(g, s, p):
        return g, {"a": s["a"] + 1.0, "b": s["b"] * 2.0}

    params = _params()
    twice = jnp.ones((4,))
    opt = bps_jax.DistributedOptimizer(optax.GradientTransformation(
        lambda p: {"a": twice, "b": twice}, update))
    _, new = opt.update(_grads(params, 0), opt.init(params), params)
    assert not twice.is_deleted()
    np.testing.assert_array_equal(np.asarray(new["a"]), 2.0)
    np.testing.assert_array_equal(np.asarray(new["b"]), 2.0)


def test_leaves_that_are_not_arrays_pass_through(session):
    """numpy and Python leaves of the state are arguments like any
    other, not donated and not touched."""
    def update(g, s, p):
        scaled = jax.tree.map(lambda x: -s["lr"] * s["scale"] * x, g)
        return scaled, {**s, "n": s["n"] + 1}

    params = _params()
    host = np.full((), 0.5, np.float32)
    opt = bps_jax.DistributedOptimizer(optax.GradientTransformation(
        lambda p: {"lr": 0.25, "scale": host, "n": jnp.zeros((), jnp.int32)},
        update))
    grads = _grads(params, 0)
    updates, new = opt.update(grads, opt.init(params), params)
    np.testing.assert_allclose(
        np.asarray(updates["b"]),
        -0.125 * np.asarray(grads["b"]).mean(axis=0), rtol=1e-5)
    assert host == 0.5 and int(new["n"]) == 1


def test_params_none_works_as_before(session):
    params = _params()
    opt = bps_jax.DistributedOptimizer(optax.sgd(0.5))
    grads = _grads(params, 0)
    updates, _ = opt.update(grads, opt.init(params))
    np.testing.assert_allclose(
        np.asarray(updates["w"]),
        -0.5 * np.asarray(grads["w"]).mean(axis=0), rtol=1e-5, atol=1e-7)


def test_micro_steps_leave_the_state_alive_and_the_boundary_consumes_it(
        session, reduced_seen):
    params = _params()
    opt = bps_jax.DistributedOptimizer(optax.adam(1e-2),
                                       backward_passes_per_step=3)
    state = opt.init(params)
    micro = [_grads(params, k) for k in range(3)]
    for g in micro[:2]:
        updates, same = opt.update(g, state, params)
        assert same is state and not any(_deleted(state))
        assert not reduced_seen           # nothing communicated yet
        assert all(not np.any(np.asarray(u)) for u in jax.tree.leaves(updates))
    updates, new_state = opt.update(micro[2], state, params)
    assert all(_deleted(state)) and all(_deleted(reduced_seen[0][0]))
    # the caller's micro-batches are the caller's, the first one too
    # (the accumulator starts AT it)
    assert not any(_deleted(micro)) and not any(_deleted(params))
    mean = sum(np.asarray(g["b"]).mean(axis=0) for g in micro) / 3
    want, _ = optax.adam(1e-2).update({"b": jnp.asarray(mean)},
                                      optax.adam(1e-2).init(
                                          {"b": params["b"]}))
    np.testing.assert_allclose(np.asarray(updates["b"]),
                               np.asarray(want["b"]), rtol=1e-4)
    assert int(new_state[0].count) == 1


@pytest.mark.parametrize("jitted", [False, True], ids=["plain", "jitted"])
def test_the_update_compiles_once(session, jitted):
    """Five steps, one entry in the program's cache: neither the rebuilt
    trees nor an inner jit retrace it."""
    tx = optax.adamw(1e-2)
    opt = bps_jax.DistributedOptimizer(optax.GradientTransformation(
        tx.init, jax.jit(tx.update) if jitted else tx.update))
    params = _on_the_mesh(_params())
    state = _on_the_mesh(opt.init(params))
    for step in range(5):
        updates, state = opt.update(_grads(params, step), state, params)
        params = optax.apply_updates(params, updates)
    assert opt._tx_program._cache_size() == 1
    if jitted:
        assert opt._tx.update._cache_size() == 0     # inlined, never run


def test_the_gauge_reads_one_for_adam_and_is_published_once(
        session, monkeypatch):
    sets = []
    real = bps_jax._gauges.set

    def spy(name, value, **labels):
        if name == GAUGE:
            sets.append(value)
        return real(name, value, **labels)

    monkeypatch.setattr(bps_jax._gauges, "set", spy)
    params = _on_the_mesh(_params())
    opt = bps_jax.DistributedOptimizer(optax.adam(1e-2))
    state = _on_the_mesh(opt.init(params))
    for step in range(5):
        _, state = opt.update(_grads(params, step), state, params)
    assert sets == [1.0]
    assert bps.metrics_snapshot()["gauges"][GAUGE] == 1.0


def test_the_gauge_reads_zero_where_nothing_can_be_aliased(session):
    """Outputs of another dtype than every donated input: the program
    has no use for the buffers, JAX leaves them alive, the share says
    so."""
    def update(g, s, p):
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16), g), s

    params = _params()
    opt = bps_jax.DistributedOptimizer(
        optax.GradientTransformation(lambda p: (), update))
    updates, _ = opt.update(_grads(params, 0), (), params)
    assert updates["w"].dtype == jnp.bfloat16
    assert bps.metrics_snapshot()["gauges"][GAUGE] == 0.0


@pytest.mark.parametrize("ranks", [1, 2])
def test_no_reduced_leaf_is_the_callers_buffer_on_a_small_mesh(ranks):
    """On one rank a reduction has nothing to add: were any path to hand
    the input back as its result, donating the result would delete the
    caller's gradients.  Leaves alone (an int, one over the bucket cap's
    eighth) and in buckets, the last already in its result's shape but
    for the rank axis."""
    bps.init(devices=jax.devices()[:ranks])
    try:
        params = {"w": jnp.ones((300, 1000)), "b": jnp.ones((33,)),
                  "s": jnp.ones(()), "n": jnp.ones((5,), jnp.int32)}
        grads = jax.tree.map(
            lambda p: jnp.stack([p * (r + 1) for r in range(ranks)]), params)
        opt = bps_jax.DistributedOptimizer(optax.GradientTransformation(
            lambda p: (), lambda g, s, p: (g, s)))
        updates, _ = opt.update(grads, (), params)
        assert not any(_deleted(grads)) and not any(_deleted(params))
        mean = (ranks + 1) / 2
        np.testing.assert_array_equal(np.asarray(updates["b"]), mean)
        np.testing.assert_array_equal(np.asarray(grads["b"][0]), 1.0)
        assert np.asarray(updates["n"]).tolist() == [int(mean)] * 5
    finally:
        bps.shutdown()
