"""Observability plane (ISSUE 6): the unified metrics registry +
Prometheus exposition, the per-rank HTTP endpoint, cross-rank
aggregation over the membership bus, the flight recorder, and per-step
StepStats.

The acceptance pin lives at the end: a REAL 3-process chaos run where
every rank serves ``/metrics``/``/healthz``, ``cluster_metrics()``
answers over the bus, and the chaos-killed worker leaves a
flight-recorder dump whose tail holds the events leading into the kill.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import byteps_tpu.core.api as api
from byteps_tpu.common import flight_recorder as flight
from byteps_tpu.common import metrics as metrics_mod
from byteps_tpu.common import obs_server
from byteps_tpu.common.config import Config, set_config
from byteps_tpu.common.metrics import MetricsRegistry, pow2_bucket
from byteps_tpu.common.telemetry import (SpeedMonitor, StepStatsTracker,
                                         counters, gauges, histograms)
from byteps_tpu.fault import membership as mm
from byteps_tpu.fault.membership import (ElasticMembership, MembershipView,
                                         _BusServer, _recv_obj, _send_obj)

from .conftest import free_port as _free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")


@pytest.fixture(autouse=True)
def _fresh_epoch():
    mm._reset_epoch_for_tests()
    yield
    if api.initialized():
        api.shutdown()
    api._declared_order = []
    mm._reset_epoch_for_tests()


def _req(port, msg, timeout=20.0):
    s = socket.create_connection(("127.0.0.1", port), timeout=5)
    s.settimeout(timeout)
    _send_obj(s, msg)
    reply = _recv_obj(s)
    s.close()
    return reply


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


# -- the registry -----------------------------------------------------------


def test_registry_labels_and_consistent_snapshot():
    reg = MetricsRegistry()
    reg.inc("integrity.crc_reject")
    reg.inc("wire_bytes", 100)
    reg.inc("wire_bytes", 40, {"key": "grad.0"})
    reg.set("engine.sched_pending", 7)
    reg.observe("engine.unit_sync_ms", 5)
    snap = reg.snapshot()
    # unlabeled series keep their bare established names; the labeled
    # breakdown exists BESIDE them, never instead of them
    assert snap["counters"]["integrity.crc_reject"] == 1
    assert snap["counters"]["wire_bytes"] == 100
    assert snap["counters"]['wire_bytes{key="grad.0"}'] == 40
    assert snap["gauges"]["engine.sched_pending"] == 7.0
    assert snap["histograms"]["engine.unit_sync_ms"] == {8: 1}
    assert reg.get_counter("wire_bytes") == 100
    assert reg.get_counter("wire_bytes", {"key": "grad.0"}) == 40
    # per-kind reset (the legacy facade contract)
    reg.reset("counters")
    assert reg.snapshot()["counters"] == {}
    assert reg.snapshot()["gauges"] != {}


def test_legacy_singletons_share_one_registry():
    counters.inc("membership.shrink")
    gauges.set("engine.bytes_in_flight", 3.0)
    histograms.observe("engine.dispatch_unit_width", 4)
    snap = metrics_mod.registry.snapshot()
    assert snap["counters"]["membership.shrink"] == 1
    assert snap["gauges"]["engine.bytes_in_flight"] == 3.0
    assert snap["histograms"]["engine.dispatch_unit_width"] == {4: 1}
    # facade reads go through the same store
    assert counters.get("membership.shrink") == 1
    assert histograms.count("engine.dispatch_unit_width") == 1


def test_histogram_pow2_bucket_edges():
    # the satellite pins: 0, negatives, exact powers of two
    assert pow2_bucket(0) == 0
    assert pow2_bucket(-3) == 0
    assert pow2_bucket(0.5) == 1
    assert pow2_bucket(1) == 1
    assert pow2_bucket(8) == 8          # exact power lands in its own bucket
    assert pow2_bucket(8.0001) == 16
    assert pow2_bucket(9) == 16
    # non-finite values must neither hang the doubling loop (+inf) nor
    # silently land in bucket 1 (NaN)
    assert pow2_bucket(float("inf")) == 1 << 62
    assert pow2_bucket(float("nan")) == 0
    assert pow2_bucket(float("-inf")) == 0
    h = metrics_mod.Histograms()
    for v in (0, -1, 1, 2, 8, 9):
        h.observe("x", v)
    assert h.snapshot()["x"] == {0: 2, 1: 1, 2: 1, 8: 1, 16: 1}


def test_prometheus_rendering_and_escaping():
    reg = MetricsRegistry()
    reg.inc("integrity.crc_reject", 3)
    reg.inc("wire_bytes", 7, {"key": 'a"b\\c\nd'})
    reg.set("engine.running", 1)
    reg.observe("engine.unit_sync_ms", 3)
    reg.observe("engine.unit_sync_ms", 5)
    out = reg.render_prometheus()
    assert "# TYPE byteps_integrity_crc_reject_total counter" in out
    assert "byteps_integrity_crc_reject_total 3" in out
    # label-value escaping: backslash, double quote, newline
    assert 'key="a\\"b\\\\c\\nd"' in out
    # histogram: cumulative le buckets + _sum/_count
    assert 'byteps_engine_unit_sync_ms_bucket{le="4"} 1' in out
    assert 'byteps_engine_unit_sync_ms_bucket{le="8"} 2' in out
    assert 'byteps_engine_unit_sync_ms_bucket{le="+Inf"} 2' in out
    assert "byteps_engine_unit_sync_ms_sum 8" in out
    assert "byteps_engine_unit_sync_ms_count 2" in out
    # every sample line is "<name>[{labels}] <value>"
    for line in out.strip().splitlines():
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name and not name.startswith(" "), line
        float(value)  # parses


# -- SpeedMonitor (satellite 6) ---------------------------------------------


def test_speedmonitor_rollover_and_just_rolled_guard():
    t = [0.0]
    sm = SpeedMonitor(window_sec=10.0, clock=lambda: t[0])
    sm.record(10 * 2**20)
    t[0] = 5.0
    # matured partial window: live rate (10 MB over 5 s)
    assert sm.speed()[1] == pytest.approx(2.0)
    t[0] = 10.0
    sm.record(0)                         # rolls: 10 MB / 10 s
    assert sm.total_windows() == 1
    t[0] = 10.5
    # the satellite's pin: a JUST-rolled window (0.5 s of partial data)
    # must report the closed window's 1 MB/s, not a near-zero partial
    assert sm.speed()[1] == pytest.approx(1.0)


def test_speedmonitor_rolls_on_read_when_record_pauses():
    t = [0.0]
    sm = SpeedMonitor(window_sec=10.0, clock=lambda: t[0])
    sm.record(10 * 2**20)
    t[0] = 10.0
    sm.record(0)                         # window 1: 1 MB/s
    t[0] = 40.0
    # record() went quiet for 30 s: speed() must not freeze on the old
    # 1 MB/s figure — the stale partial rolls on read and reports idle
    assert sm.speed()[1] == pytest.approx(0.0)
    assert sm.total_windows() == 2


# -- StepStats --------------------------------------------------------------


def test_step_stats_tracker_boundaries_and_surfaces():
    rec = flight.FlightRecorder(capacity=64)
    tr = StepStatsTracker(recorder=rec)
    tr.on_push("a", 100)
    tr.on_push("b", 50)                  # same step (b's count == step)
    tr.add_stall(5.0)
    assert tr.current_step == 1
    tr.on_push("a", 100)                 # a advances -> step 1 finalizes
    last = tr.last()
    assert last.step == 1
    assert last.bytes_pushed == 150
    assert last.pushes == 2
    assert last.sync_stall_ms == pytest.approx(5.0)
    assert 0.0 <= last.overlap_fraction <= 1.0
    assert last.retransmits == 0
    # surfaced through the gauges (the /metrics route) ...
    assert gauges.get("step.bytes_pushed") == 150
    assert counters.get("step.completed") == 1
    # ... and the flight recorder
    kinds = [e["kind"] for e in rec.snapshot()]
    assert "step_stats" in kinds
    # flush() finalizes the in-progress tail step
    tr.add_stall(1.0)
    done = tr.flush()
    assert done is not None and done.step == 2 and done.bytes_pushed == 100
    assert tr.summary()["steps"] == 2


def test_step_wall_starts_where_the_call_that_opens_the_step_began():
    """A tree-level push_pull marks its start (``open_call``) before its
    span opens; the step its first push starts takes that as the wall's
    start, however late the push itself lands, so the span the call
    feeds (``push_pull_ms``) lies inside ``wall_ms``.  A push with no
    open call, or one whose mark an earlier push already took, starts
    the wall at the push."""
    tr = StepStatsTracker(recorder=flight.FlightRecorder(capacity=16))
    for lead in (0.03, 0.0, 0.03):       # the miss was a lead that SHRANK
        tr.open_call()
        t0 = time.monotonic()
        time.sleep(lead)                 # plan lookup, packing: the lead
        tr.on_push("a", 8)
        tr.on_push("b", 8)               # same step: the mark is spent
        tr.add_push_pull((time.monotonic() - t0) * 1e3)
    tr.on_push("a", 8)                   # no call open: starts at the push
    steps = tr.history()
    assert [s.step for s in steps] == [1, 2, 3]
    for s in steps:
        assert s.push_pull_ms <= s.wall_ms, s
    assert steps[0].wall_ms >= 30.0 and steps[2].wall_ms >= 30.0


# -- flight recorder --------------------------------------------------------


def test_flight_recorder_ring_bound_and_dump(tmp_path):
    rec = flight.FlightRecorder(capacity=32)
    for i in range(100):
        rec.record("ev", i=i)
    assert len(rec) == 32
    path = rec.dump("unit_test", path=str(tmp_path / "dump.json"))
    with open(path) as f:
        doc = json.load(f)
    assert doc["reason"] == "unit_test"
    assert len(doc["events"]) == 32
    # oldest -> newest; the TAIL is the most recent event
    assert doc["events"][0]["i"] == 68
    assert doc["events"][-1]["i"] == 99
    assert doc["events"][-1]["kind"] == "ev"
    # disabled recorder records and dumps nothing
    rec.configure(enabled=False)
    rec.record("ev", i=200)
    assert len(rec) == 32
    assert rec.dump("nope") is None


def test_flight_exit_dump_fires_once_and_only_when_asked(tmp_path):
    set_config(Config(flight_dir=str(tmp_path)))
    flight.record("something")
    assert flight.maybe_exit_dump() is None          # default: off
    set_config(Config(flight_dir=str(tmp_path), flight_dump_on_exit=True))
    assert flight.maybe_exit_dump() is not None
    assert flight.maybe_exit_dump() is None          # once per process
    assert len(list(tmp_path.glob("bps_flight_*_exit_*.json"))) == 1


def test_flight_dump_on_quarantine(tmp_path):
    from byteps_tpu.server.engine import ServerEngine
    set_config(Config(nonfinite_policy="skip", flight_dir=str(tmp_path)))
    srv = ServerEngine(num_threads=1)
    try:
        srv.push("k", np.array([np.nan, 1.0], np.float32), 0, 2)
    finally:
        srv.shutdown()
    dumps = list(tmp_path.glob("bps_flight_*_quarantine_*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    kinds = [e["kind"] for e in doc["events"]]
    assert "quarantine" in kinds
    assert "integrity.nonfinite" in kinds


def test_flight_dump_on_chaos_kill_inproc(tmp_path, monkeypatch):
    from byteps_tpu.fault import injector
    set_config(Config(flight_dir=str(tmp_path)))
    exits = []
    monkeypatch.setattr(injector, "_exit", lambda code: exits.append(code))
    flight.record("engine.init", ranks=8)
    injector.arm("kill:step=2", seed=0, rank=0)
    try:
        injector.on_step()
        injector.on_step()
    finally:
        injector.disarm()
    assert exits, "kill rule never fired"
    dumps = list(tmp_path.glob("bps_flight_*_chaos_kill_*.json"))
    assert len(dumps) == 1
    events = json.loads(dumps[0].read_text())["events"]
    # the tail holds the events leading into the kill, kill last
    assert events[-1]["kind"] == "fault.kill"
    assert events[-1]["step"] == 2
    assert "engine.init" in [e["kind"] for e in events]


# -- the HTTP endpoint ------------------------------------------------------


def test_obs_endpoints_serve_metrics_healthz_debug_state(tmp_path):
    from byteps_tpu.server.engine import ServerEngine
    from byteps_tpu.server.kv_store import KVStore
    api.init(Config(obs_port=0))
    srv = obs_server.get_server()
    assert srv is not None and srv.port > 0
    eng = api._require()
    x = np.ones(2048, np.float32)
    for _ in range(3):
        eng.push_pull_local(x, "obs.g")
    # satellite: integrity.* / membership.* / wire_bytes reach /metrics
    counters.inc("integrity.crc_reject")
    counters.inc("membership.stale_pushes_dropped")
    kv = KVStore()
    kv.init_key("w", np.zeros(4, np.float32))
    kv.push_delta("w", np.ones(4, np.float32), worker_id=1, seq=3)
    # one real compressed wire push: _account_wire moves the process-wide
    # wire_bytes counter the /metrics route must surface
    import jax.numpy as jnp

    from byteps_tpu.compression import registry as creg
    kv.register_compression("w", {"compressor": "onebit"}, 4)
    comp = creg.create({"compressor": "onebit"}, 4, np.float32)
    payload, _ = comp.compress(jnp.ones(4), comp.init_state())
    wire = comp.wire_encode(payload)
    kv.push_delta_wire("w", wire, worker_id=1, seq=4)
    se = ServerEngine(num_threads=1)
    try:
        base = f"http://127.0.0.1:{srv.port}"

        status, ctype, body = _get(base + "/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        assert "byteps_integrity_crc_reject_total 1" in body
        assert "byteps_membership_stale_pushes_dropped_total 1" in body
        assert f"byteps_wire_bytes_total {len(wire)}" in body
        assert "byteps_engine_running 1" in body
        assert "byteps_step_bytes_pushed" in body
        for line in body.strip().splitlines():     # valid exposition
            if not line.startswith("#"):
                float(line.rpartition(" ")[2])

        status, ctype, body = _get(base + "/healthz")
        doc = json.loads(body)
        assert doc["ok"] is True
        assert doc["membership_epoch"] == mm.current_epoch() == 0
        assert doc["engine_running"] is True
        assert doc["last_heartbeat_age_s"] is None   # no monitor armed
        assert "pushpull_mbps" in doc and doc["step"] == 3

        status, ctype, body = _get(base + "/debug/state")
        doc = json.loads(body)
        assert doc["engine"]["running"] is True
        assert doc["engine"]["sched_pending"] == 0
        assert doc["engine"]["bytes_in_flight"] == 0
        assert "planner" in doc["engine"]
        assert doc["engine"]["step"]["bytes_pushed"] == 8192
        kv_states = [c for c in doc["kv_stores"]]
        # dedup_floors is CLAMPED (ISSUE 9 satellite): worst-N entries
        # plus the true count, so the shape carries both fields
        assert any(c["dedup_floors"] == {"w:1": 4}
                   and c["dedup_floor_count"] == 1 for c in kv_states)
        assert "serving_planes" in doc
        assert any(c["kind"] == "server_engine"
                   for c in doc["server_engines"])

        with pytest.raises(urllib.error.HTTPError):
            _get(base + "/nope")
    finally:
        se.shutdown()
    # /healthz keeps answering after the engine is gone (the endpoint
    # outlives suspend/resume) and reports the engine stopped
    api.shutdown()
    _, _, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
    assert json.loads(body)["engine_running"] is False


# -- cross-rank aggregation -------------------------------------------------


def test_bus_metrics_verbs_and_cluster_metrics():
    port = _free_port()
    bus = _BusServer(("127.0.0.1", port), MembershipView(0, (0, 1)),
                     5.0, 5.0)
    try:
        r = _req(port, {"op": "metrics_put", "rank": 0,
                        "metrics": {"x": 1}})
        assert r["ok"] and r["world"] == [0, 1]
        _req(port, {"op": "metrics_put", "rank": 1, "metrics": {"x": 2}})
        out = api.cluster_metrics(bus=f"127.0.0.1:{port}")
        assert out["epoch"] == 0 and out["world"] == [0, 1]
        assert set(out["ranks"]) == {0, 1}
        assert out["ranks"][0]["metrics"] == {"x": 1}
        assert out["ranks"][1]["age_s"] >= 0.0
    finally:
        bus.close()


def test_sync_piggyback_feeds_metrics_cache():
    port = _free_port()
    bus = _BusServer(("127.0.0.1", port), MembershipView(0, (0,)), 5.0, 5.0)
    try:
        r = _req(port, {"op": "sync", "rank": 0, "epoch": 0, "step": 1,
                        "payload": None, "metrics": {"speed_mbps": 9.5}})
        assert r["ok"]
        out = api.cluster_metrics(bus=f"127.0.0.1:{port}")
        assert out["ranks"][0]["metrics"]["speed_mbps"] == 9.5
    finally:
        bus.close()


def test_membership_step_sync_attaches_real_snapshot():
    port = _free_port()
    counters.inc("integrity.retransmit", 2)
    m = ElasticMembership(0, [0], f"127.0.0.1:{port}").start()
    try:
        m.step_sync(1)
        out = api.cluster_metrics(bus=f"127.0.0.1:{port}")
        snap = out["ranks"][0]["metrics"]
        assert snap["counters"]["integrity.retransmit"] == 2
        assert snap["epoch"] == 0
        assert m.publish_metrics() is True
    finally:
        m.stop()


def test_cluster_metrics_local_fallback_without_bus():
    out = api.cluster_metrics(bus=f"127.0.0.1:{_free_port()}")
    assert out["local_only"] is True
    assert out["world"] == [0]
    assert out["ranks"][0]["metrics"]["pid"] == os.getpid()


def test_bps_top_render_and_once_json(capsys):
    from tools import bps_top
    cluster = {"epoch": 1, "world": [0, 2], "ranks": {
        0: {"age_s": 0.4, "metrics": {
            "epoch": 1, "speed_mbps": 2048.0, "sched_pending": 3,
            "bytes_in_flight": 64,
            "counters": {"integrity.retransmit": 5},
            "step": {"step": 12, "wall_ms": 100.0,
                     "sync_stall_ms": 25.0}}}}}
    text = bps_top.render(cluster)
    assert "epoch 1" in text and "RANK" in text
    assert "2.147" in text    # 2048 MiB/s -> 2.147 decimal GB/s (bench unit)
    assert "25" in text                   # stall %
    assert "rank(s) [2]" in text          # missing-rank note
    # --once --json against a live bus
    port = _free_port()
    bus = _BusServer(("127.0.0.1", port), MembershipView(0, (0,)), 5.0, 5.0)
    try:
        _req(port, {"op": "metrics_put", "rank": 0, "metrics": {"x": 1}})
        rc = bps_top.main(["--bus", f"127.0.0.1:{port}", "--once", "--json"])
        assert rc == 0
        doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert doc["world"] == [0]
    finally:
        bus.close()


# -- the 3-process acceptance run -------------------------------------------


class _Reader(threading.Thread):
    def __init__(self, proc):
        super().__init__(daemon=True)
        self.proc = proc
        self.lines = []
        self.start()

    def run(self):
        for line in self.proc.stdout:
            self.lines.append(line.rstrip("\n"))

    def wait_for(self, prefix, timeout):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for line in list(self.lines):
                if line.startswith(prefix):
                    return line
            if self.proc.poll() is not None and not any(
                    ln.startswith(prefix) for ln in self.lines):
                break
            time.sleep(0.1)
        pytest.fail(f"no {prefix!r} line within {timeout}s; output:\n"
                    + "\n".join(self.lines[-50:]))


def _spawn_obs_worker(rank, bus_port, hb_port, steps, flight_dir,
                      extra=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["DMLC_NUM_WORKER"] = "1"
    env["DMLC_WORKER_ID"] = str(rank)
    env["BYTEPS_ELASTIC_RANK"] = str(rank)
    env["BYTEPS_ELASTIC_WORLD"] = "0,1,2"
    env["BYTEPS_ELASTIC_BUS"] = f"127.0.0.1:{bus_port}"
    env["BYTEPS_ELASTIC_HB_PORT"] = str(hb_port)
    env["BYTEPS_ELASTIC_STEPS"] = str(steps)
    env["BYTEPS_ELASTIC_STEP_SLEEP"] = "0.2"
    env["BYTEPS_MEMBERSHIP_RENDEZVOUS_TIMEOUT"] = "3"
    env["BYTEPS_MEMBERSHIP_SYNC_TIMEOUT"] = "15"
    env["BYTEPS_LOG_LEVEL"] = "ERROR"
    env["BYTEPS_OBS_PORT"] = "0"              # every rank serves HTTP
    env["BYTEPS_FLIGHT_DIR"] = str(flight_dir)
    env.pop("BYTEPS_FAULT_SPEC", None)
    env.pop("BYTEPS_ELASTIC_REJOIN", None)
    env.update(extra or {})
    return subprocess.Popen([sys.executable, WORKER], env=env, cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


@pytest.mark.chaos
def test_obs_cluster_3proc_chaos_kill_flight_recorder(tmp_path):
    """The ISSUE 6 acceptance pin, all three clauses on one real run:
    with BYTEPS_OBS_PORT set, every rank of a 3-process run serves
    /metrics in valid Prometheus text and /healthz reflects the live
    membership epoch; cluster_metrics() returns the live ranks'
    snapshots over the membership bus (before AND after the shrink);
    and the chaos-killed worker leaves a flight-recorder dump whose
    tail contains the events leading into the kill."""
    steps, kill_at = 25, 6
    bus_port, hb_port = _free_port(), _free_port()
    procs = {
        r: _spawn_obs_worker(r, bus_port, hb_port, steps, tmp_path, extra=(
            {"BYTEPS_FAULT_SPEC": f"kill:rank=1:step={kill_at}",
             "BYTEPS_FAULT_SEED": "7"} if r == 1 else None))
        for r in (0, 1, 2)}
    readers = {r: _Reader(p) for r, p in procs.items()}
    try:
        # every rank announces its obs endpoint
        ports = {}
        for r in (0, 1, 2):
            line = readers[r].wait_for("OBS ", timeout=120)
            ports[r] = int(line.split()[2])

        # clause 1: every rank serves valid Prometheus text + healthz
        scraped = set()
        for r in (0, 1, 2):
            try:
                _, ctype, body = _get(
                    f"http://127.0.0.1:{ports[r]}/metrics", timeout=10)
                _, _, hz = _get(f"http://127.0.0.1:{ports[r]}/healthz",
                                timeout=10)
            except OSError:
                if r == 1:
                    continue      # the victim can die under our scrape
                raise
            assert ctype.startswith("text/plain"), (r, ctype)
            assert "# TYPE byteps_" in body, (r, body[:200])
            for line in body.strip().splitlines():
                if not line.startswith("#"):
                    float(line.rpartition(" ")[2])
            assert json.loads(hz)["membership_epoch"] in (0, 1)
            scraped.add(r)
        assert {0, 2} <= scraped     # both survivors really served

        # the shrink happens (victim killed, survivors agree on epoch 1)
        for r in (0, 2):
            readers[r].wait_for("WORLD 1 0,2", timeout=120)

        # clause 1 (cont.): /healthz reflects the LIVE epoch after the
        # shrink — the endpoint survived the suspend/resume transition
        deadline = time.monotonic() + 60
        epochs = {}
        while time.monotonic() < deadline and set(epochs) != {0, 2}:
            for r in (0, 2):
                try:
                    _, _, hz = _get(
                        f"http://127.0.0.1:{ports[r]}/healthz", timeout=5)
                    if json.loads(hz)["membership_epoch"] == 1:
                        epochs[r] = 1
                except OSError:
                    pass
            time.sleep(0.3)
        assert set(epochs) == {0, 2}, epochs

        # clause 2: one bus round-trip returns every live rank's snapshot
        deadline = time.monotonic() + 60
        cluster = None
        while time.monotonic() < deadline:
            try:
                out = api.cluster_metrics(bus=f"127.0.0.1:{bus_port}",
                                          timeout=5)
            except (ConnectionError, TimeoutError):
                break             # survivors finished; bus gone
            # the bus caches each rank's LAST sync frame, so right after
            # the shrink a survivor's cached snapshot can still be the
            # epoch-0 one — poll until the snapshots themselves have
            # caught up, not just the bus epoch
            if (not out.get("local_only") and out["epoch"] == 1
                    and {0, 2} <= set(out["ranks"])
                    and all(out["ranks"][r]["metrics"].get("epoch") == 1
                            for r in (0, 2))):
                cluster = out
                break
            time.sleep(0.3)
        assert cluster is not None, "never saw both survivors' snapshots"
        assert cluster["world"] == [0, 2]
        for r in (0, 2):
            snap = cluster["ranks"][r]["metrics"]
            assert snap["rank"] == r
            assert snap["epoch"] == 1
            assert "counters" in snap and "gauges" in snap

        outs = {}
        for r, p in procs.items():
            p.communicate(timeout=180)
            outs[r] = "\n".join(readers[r].lines)
        assert procs[1].returncode == 1, outs[1][-2000:]
        for r in (0, 2):
            assert procs[r].returncode == 0, outs[r][-2000:]
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()

    # clause 3: the chaos-killed worker left a flight-recorder dump
    # whose tail holds the events leading into the kill
    dumps = list(tmp_path.glob("bps_flight_*rank1_*_chaos_kill_*.json"))
    assert len(dumps) == 1, list(tmp_path.iterdir())
    doc = json.loads(dumps[0].read_text())
    assert doc["rank"] == 1 and doc["reason"] == "chaos_kill"
    events = doc["events"]
    assert events[-1]["kind"] == "fault.kill"
    assert events[-1]["step"] == kill_at
    kinds = {e["kind"] for e in events}
    assert "engine.init" in kinds          # the run's history, not just
    assert "step_stats" in kinds           # the final instant
