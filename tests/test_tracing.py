"""Direct unit tests for common/tracing.py (ISSUE 12 satellite).

The tracer was previously only incidentally covered through engine
tests; these pin its own contracts: step-window gating, flush's
idempotent-rewrite semantics, record_span's window independence,
numeric-tid metadata emission, the jax-profiler state machine (driven
without a real profiler), the new sampled capture stream, the bounded
event buffer (spill + dropped counter), and the clock/anchor metadata
the merge tool depends on.
"""

import json
import os
import sys
import threading
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from byteps_tpu.common import tracing
from byteps_tpu.common.config import Config, set_config
from byteps_tpu.common.tracing import TraceContext, Tracer


def _read(path):
    with open(path) as f:
        return json.load(f)


# -- step-window gating ------------------------------------------------------


def test_record_gated_on_step_window(tmp_path):
    tr = Tracer(enabled=True, start_step=2, end_step=3, out_dir=str(tmp_path))
    for step in (1, 2, 3, 4):
        tr.record("g", 7, "push_pull", 1.0, 2.0, step, nbytes=64)
    # the step-4 record auto-flushed (window closed); an explicit path
    # forces a rewrite so the assertion reads the full file
    doc = _read(tr.flush(path=str(tmp_path / "win.json")))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert sorted(e["args"]["step"] for e in spans) == [2, 3]


def test_on_push_counts_per_tensor_and_flushes_past_window(tmp_path):
    tr = Tracer(enabled=True, start_step=1, end_step=2, out_dir=str(tmp_path))
    assert tr.on_push("a") == 1
    assert tr.on_push("b") == 1
    assert tr.on_push("a") == 2
    tr.record("a", 0, "push_pull", 0.0, 1.0, 2)
    # stepping past the window triggers the idempotent flush
    assert tr.on_push("a") == 3
    out = os.path.join(str(tmp_path),
                       f"bps_trace_rank0_{os.getpid()}.json")
    assert os.path.exists(out)


def test_disabled_tracer_records_nothing(tmp_path):
    tr = Tracer(enabled=False, out_dir=str(tmp_path))
    assert not tr.active
    tr.record("g", 0, "push_pull", 0.0, 1.0, 15)
    tr.record_span("fault", 0.0, 1.0)
    assert tr.flush() is None


# -- flush semantics ---------------------------------------------------------


def test_flush_idempotent_rewrite(tmp_path):
    tr = Tracer(enabled=True, start_step=1, end_step=99,
                out_dir=str(tmp_path))
    tr.record("g", 0, "queued", 0.0, 1.0, 1)
    p1 = tr.flush()
    assert p1 is not None
    assert tr.flush() is None            # nothing new -> no rewrite
    tr.record("g", 0, "queued", 1.0, 2.0, 2)
    p2 = tr.flush()                      # new event -> full rewrite
    assert p2 == p1
    spans = [e for e in _read(p2)["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 2


def test_record_span_outside_window(tmp_path):
    tr = Tracer(enabled=True, start_step=10, end_step=20,
                out_dir=str(tmp_path))
    # no windowed event ever recorded; the fault span must still land
    tr.record_span("recovery", 5.0, 6.0, epoch=3)
    doc = _read(tr.flush())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert [e["name"] for e in spans] == ["recovery"]
    assert spans[0]["cat"] == "fault"
    assert spans[0]["args"]["epoch"] == 3


def test_numeric_tid_metadata_emission(tmp_path):
    tr = Tracer(enabled=True, start_step=1, end_step=9,
                out_dir=str(tmp_path))
    tr.record("tensor.a", 0, "queued", 0.0, 1.0, 1)
    tr.record("tensor.b", 1, "queued", 0.0, 1.0, 1)
    doc = _read(tr.flush())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    # chrome requires numeric tids; names ride thread_name metadata
    assert all(isinstance(e["tid"], int) for e in spans)
    names = {m["args"]["name"]: m["tid"] for m in metas}
    assert set(names) == {"tensor.a", "tensor.b"}
    by_name = {e["args"]["key"]: e["tid"] for e in spans}
    assert by_name[0] == names["tensor.a"]
    assert by_name[1] == names["tensor.b"]


def test_flush_carries_merge_metadata(tmp_path):
    tr = Tracer(enabled=True, start_step=1, end_step=9,
                out_dir=str(tmp_path))
    tracing.set_clock_offset(0.012, 0.001, source="bus test")
    tr.record("g", 0, "queued", 0.0, 1.0, 1)
    doc = _read(tr.flush())
    assert doc["rank"] == 0 and doc["pid"] == os.getpid()
    anchor = doc["monoAnchor"]
    assert anchor["mono"] <= 1e9 < anchor["wall"]  # mono vs wall clocks
    assert doc["clockSync"]["offset_s"] == pytest.approx(0.012)
    assert doc["clockSync"]["err_s"] == pytest.approx(0.001)


# -- jax-profiler state machine (no real profiler) ---------------------------


class _FakeProfiler:
    def __init__(self):
        import jax
        self.calls = []
        self.ProfileOptions = jax.profiler.ProfileOptions

    def start_trace(self, path, profiler_options=None):
        self.calls.append(("start", path, profiler_options))

    def stop_trace(self):
        self.calls.append(("stop",))


def test_jax_profiler_state_machine(tmp_path, monkeypatch):
    import jax
    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    set_config(Config(trace_on=True, trace_jax=True, trace_start_step=2,
                      trace_end_step=3, trace_dir=str(tmp_path)))
    tr = Tracer()
    assert tr._jax_state == "idle"
    tr.on_push("g")                      # step 1: before the window
    assert fake.calls == [] and tr._jax_state == "idle"
    tr.on_push("g")                      # step 2: window opens
    assert tr._jax_state == "running"
    tr.on_push("g")                      # step 3: still inside
    assert [c[0] for c in fake.calls] == ["start"]
    # device ops and bps.* spans, not Python frames (ISSUE 23)
    assert fake.calls[0][2].python_tracer_level == 0
    tr.on_push("g")                      # step 4: window closed
    assert tr._jax_state == "done"
    assert [c[0] for c in fake.calls] == ["start", "stop"]
    tr._jax_start()                      # done is terminal
    assert tr._jax_state == "done"
    assert [c[0] for c in fake.calls] == ["start", "stop"]


def test_jax_profiler_start_failure_is_terminal(tmp_path, monkeypatch):
    import jax

    class _Broken:
        ProfileOptions = jax.profiler.ProfileOptions

        def start_trace(self, path, profiler_options=None):
            raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax, "profiler", _Broken())
    set_config(Config(trace_on=True, trace_jax=True, trace_start_step=1,
                      trace_end_step=9, trace_dir=str(tmp_path)))
    tr = Tracer()
    tr.on_push("g")
    assert tr._jax_state == "done"       # failed start never retries


# -- sampling (BYTEPS_TRACE_SAMPLE) ------------------------------------------


def test_trace_sample_parsing_and_validation():
    assert Config(trace_sample="1/8").trace_sample_n == 8
    assert Config(trace_sample="8").trace_sample_n == 8
    assert Config(trace_sample="0").trace_sample_n == 0
    assert Config(trace_sample="").trace_sample_n == 0
    with pytest.raises(ValueError, match="BYTEPS_TRACE_SAMPLE"):
        Config(trace_sample="every-other")


def test_sampled_capture_every_nth_push(tmp_path):
    tr = Tracer(enabled=False, sample_n=3, out_dir=str(tmp_path))
    assert tr.active and not tr.enabled
    caught = [tr.start_push("g")[1] for _ in range(9)]
    assert sum(c is not None for c in caught) == 3
    ids = {c.trace_id for c in caught if c is not None}
    assert len(ids) == 3                 # distinct per captured push
    # window-gated record() still records nothing in sampled-only mode
    tr.record("g", 0, "push_pull", 0.0, 1.0, 1)
    tr.record_traced(caught[2].trace_id, "push_pull", "g", 0.0, 1.0)
    doc = _read(tr.flush())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 1
    assert spans[0]["args"]["trace_id"] == caught[2].trace_id


def test_maybe_sample_per_site_counters(tmp_path):
    tr = Tracer(enabled=False, sample_n=2, out_dir=str(tmp_path))
    a = [tr.maybe_sample("serve") for _ in range(4)]
    b = [tr.maybe_sample("kv") for _ in range(4)]
    assert sum(c is not None for c in a) == 2
    assert sum(c is not None for c in b) == 2
    # windowed-only tracing captures non-push site calls ONLY while the
    # step window is open (a closed window must stop the stream — the
    # capture bound the window exists for)
    tw = Tracer(enabled=True, start_step=2, end_step=3, sample_n=0,
                out_dir=str(tmp_path))
    assert tw.maybe_sample("serve") is None       # step 0: before window
    tw.start_push("g")                            # step 1
    assert tw.maybe_sample("serve") is None
    tw.start_push("g")                            # step 2: window open
    assert tw.maybe_sample("serve") is not None
    tw.start_push("g")                            # step 3
    tw.start_push("g")                            # step 4: window closed
    assert tw.maybe_sample("serve") is None


def test_flow_event_shape_and_pairing(tmp_path):
    tr = Tracer(enabled=False, sample_n=1, out_dir=str(tmp_path))
    _, ctx = tr.start_push("g")
    tr.record_traced(ctx.trace_id, "queued", "g", 1.0, 2.0)
    tr.flow(ctx.trace_id, "s", "g", 1.0)
    tr.flow(ctx.trace_id, "t", "wire/server_push", 2.5)
    tr.flow(ctx.trace_id, "f", "g", 3.0)
    doc = _read(tr.flush())
    flows = [e for e in doc["traceEvents"] if e.get("ph") in "stf"]
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert all(e["id"] == ctx.trace_id for e in flows)
    assert all(e["name"] == tracing.FLOW_NAME
               and e["cat"] == tracing.FLOW_CAT for e in flows)
    assert flows[2]["bp"] == "e"         # finish binds enclosing slice


def test_flow_ids_unique_across_ranks():
    a = tracing._new_flow_id(0)
    b = tracing._new_flow_id(1)
    c = tracing._new_flow_id(0)
    assert len({a, b, c}) == 3
    assert (b >> 48) & 0xFFFF == 1


# -- bounded buffer (capacity, spill, dropped) -------------------------------


def test_capacity_spills_to_disk_and_flush_folds_back(tmp_path):
    tr = Tracer(enabled=True, start_step=1, end_step=10 ** 9,
                out_dir=str(tmp_path), capacity=256)
    for i in range(1000):
        tr.record("g", 0, "queued", float(i), float(i) + 0.5, 1)
    assert len(tr._events) < 256         # memory stayed bounded
    assert tr._spill_count >= 1000 - 256
    assert tr.dropped == 0
    doc = _read(tr.flush())
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(spans) == 1000            # spill folded back in order
    assert spans[0]["ts"] == 0.0


def test_spill_failure_drops_and_counts(tmp_path, monkeypatch):
    from byteps_tpu.common.telemetry import counters
    tr = Tracer(enabled=True, start_step=1, end_step=10 ** 9,
                out_dir=os.path.join(str(tmp_path), "nope"), capacity=256)
    monkeypatch.setattr(os, "makedirs",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("ro")))
    before = counters.get("trace.events_dropped")
    for i in range(600):
        tr.record("g", 0, "queued", float(i), float(i) + 0.5, 1)
    assert tr.dropped >= 256
    assert counters.get("trace.events_dropped") - before == tr.dropped
    assert len(tr._events) < 256


def test_step_map_bounded(tmp_path):
    tr = Tracer(enabled=False, sample_n=1, out_dir=str(tmp_path))
    tr._MAX_TENSORS = 4                  # class default is 8192
    for i in range(8):
        tr.start_push(f"t{i}")
    assert len(tr._step) == 4
    step, ctx = tr.start_push("t7")      # overflow name: uncaptured
    assert step == 0 and ctx is None
    assert tr.dropped >= 4


# -- process singleton / context propagation ---------------------------------


def test_process_tracer_singleton_and_reset(tmp_path):
    set_config(Config(trace_on=False, trace_sample="1/4",
                      trace_dir=str(tmp_path)))
    tracing._reset_for_tests()
    t1 = tracing.tracer()
    assert t1 is tracing.tracer()
    assert t1.sample_n == 4
    tracing._reset_for_tests()
    assert tracing.tracer() is not t1


def test_use_and_current_propagate_within_thread():
    ctx = TraceContext(trace_id=42)
    assert tracing.current() is None
    with tracing.use(ctx):
        assert tracing.current() is ctx
        seen = []
        t = threading.Thread(target=lambda: seen.append(tracing.current()))
        t.start()
        t.join()
        assert seen == [None]            # contextvars don't cross spawn
    assert tracing.current() is None


def test_begin_sample_joins_existing_context(tmp_path):
    tracing.set_tracer(Tracer(enabled=False, sample_n=1,
                              out_dir=str(tmp_path)))
    outer = TraceContext(trace_id=7)
    with tracing.use(outer):
        ctx, t0 = tracing.begin_sample("kv.push")
        assert ctx is outer and t0 > 0
    ctx, _ = tracing.begin_sample("kv.push")
    assert ctx is not None and ctx.trace_id != 7


def test_last_stamp_tracks_captured_pushes(tmp_path):
    tracing._reset_for_tests()
    tr = Tracer(enabled=False, sample_n=2, out_dir=str(tmp_path))
    tr.start_push("g")                   # 1st: not sampled
    step, ctx = tr.start_push("g")       # 2nd: sampled
    assert ctx is not None
    assert tracing.last_stamp() == (2, ctx.trace_id)
    tracing.note_step(9)
    assert tracing.last_stamp() == (9, ctx.trace_id)
