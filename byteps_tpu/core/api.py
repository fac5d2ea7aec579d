"""Top-level BytePS-style API: init/shutdown/rank/size/push_pull/....

Mirrors the reference's BytePSBasics ctypes surface
(reference byteps/common/__init__.py:52-139) plus suspend/resume
(operations.cc:96-119).  Rank semantics on TPU: JAX is a single-controller
model, so within one process every local device is a "rank"; ``rank()``
returns the first global rank owned by this process and ``size()`` the total
device count across hosts — matching how the reference numbers GPUs across
machines.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import jax

from ..comm import mesh as mesh_mod
from ..common import telemetry, tracing
from ..common.config import Config, get_config, set_config
from ..common.handles import Handle
from ..common.logging import get_logger
from .engine import PushPullEngine

_engine: Optional[PushPullEngine] = None
_heartbeat = None  # auto-armed HeartbeatMonitor (BYTEPS_HEARTBEAT_ON)
_lock = threading.Lock()
# Tensors declared before/with init, re-declared in order on resume
# (reference global.cc:431-436 re-declares in original order on re-init).
_declared_order: List[str] = []
# Sharded-update slot snapshots captured by suspend() (ISSUE 20): the
# optimizer state lives engine-side under sharded update, so an elastic
# transition must carry it across the shutdown.  Consumed (popped) by
# the next declare_update() for the same name, which re-pads the flat
# shards to the NEW mesh geometry — that re-import IS the elastic
# re-shard.
_suspended_update_state: Dict[str, dict] = {}


def init(config: Optional[Config] = None,
         devices: Optional[list] = None) -> None:
    """Initialize byteps_tpu: mesh bootstrap + engine start.

    Reference: byteps_init() (operations.cc:36-88) — spawns the background
    stage loops; here it builds the (dcn, ici) mesh and starts the
    dispatcher/syncer pair.

    The call is one ``tracing.phase`` (``bps.init``) of three
    (``bps.init.mesh`` — ``mesh_mod.bootstrap``, which starts the
    backend when the caller has not; ``bps.init.engine`` — threads,
    planner, scheduler; ``bps.init.services`` — heartbeat, flight
    recorder, obs server, health, durable store): spans in any profiler
    session open over start-up, and the first call's stamps and
    milliseconds in ``metrics_snapshot()["startup"]``.
    """
    global _engine
    with _lock:
        if _engine is not None:
            return
        if config is not None:
            set_config(config)
        cfg = get_config()
        telemetry.listen_to_compiles()
        parts_ms: Dict[str, float] = {}

        def part(name: str) -> tracing.phase:
            def feed(wall_ms: float, _cpu_ms: Optional[float]) -> None:
                parts_ms[name] = wall_ms
            return tracing.phase("bps.init." + name, feed)

        with tracing.phase("bps.init") as whole:
            from ..fault import injector as fault_injector
            if cfg.fault_spec:
                # Eager validation: a chaos-spec typo must fail init() with
                # the valid kind/site lists, not silently inject nothing.
                # Armed before bootstrap so rendezvous-time sites are live.
                fault_injector.arm(cfg.fault_spec, seed=cfg.fault_seed,
                                   rank=cfg.host_id)
            else:
                # engine-scoped only: a persist-armed injector (e.g. a
                # partition blackhole) outlives the resume it provoked
                fault_injector.disarm(engine_scoped_only=True)
            with part("mesh"):
                comm = mesh_mod.bootstrap(cfg, devices=devices)
            with part("engine"):
                engine = PushPullEngine(comm, cfg)
            with part("services"):
                _start_services(cfg, engine)
            _engine = engine
            for name in _declared_order:
                _engine.registry.declare(name)
        if cfg.telemetry_on:
            telemetry.record_init(whole.t0, whole.t1, parts_ms)
        get_logger().info("byteps_tpu initialized: %d ranks", comm.num_ranks)


def _start_services(cfg: Config, engine: PushPullEngine) -> None:
    """What ``init`` starts beside the engine: the heartbeat, the
    observability plane, retention + judgment, the durable store.  A
    heartbeat or an endpoint that cannot bind takes the engine and the
    mesh down again before it raises."""
    global _heartbeat
    if cfg.heartbeat_on and jax.process_count() > 1:
        # auto-armed liveness: one beat per process; a dead host makes
        # every survivor exit (restartable) instead of wedging in the
        # next DCN collective (utils/failure_detector.py).  Armed
        # BEFORE _engine is published: if the UDP bind fails (port in
        # use), init() raises cleanly and a retry re-runs everything
        # — never a running engine that silently believes liveness
        # is on.
        from ..common.retry import RetryPolicy
        from ..utils.failure_detector import HeartbeatMonitor

        def _arm_heartbeat():
            # fresh monitor per attempt: a failed bind leaves the old
            # instance's socket state unusable
            return HeartbeatMonitor(
                rank=jax.process_index(),
                num_ranks=jax.process_count(),
                interval=cfg.heartbeat_interval_s,
                timeout=cfg.heartbeat_timeout_s).start()

        try:
            # the UDP bind races the previous incarnation's socket
            # teardown after an elastic restart (TIME_WAIT, port still
            # held) — exactly the transient the backoff layer is for
            _heartbeat = RetryPolicy.from_config(
                cfg, retry_on=(OSError,)).call(
                    _arm_heartbeat, describe="heartbeat UDP bind")
        except Exception:
            engine.shutdown(wait=False)
            mesh_mod.shutdown_comm()
            raise
    # Observability plane: flight-recorder knobs + crash/SIGTERM/
    # atexit dump hooks, and (when BYTEPS_OBS_PORT is set) the
    # per-process HTTP endpoint.  The endpoint outlives the engine —
    # an elastic suspend/resume keeps it (ensure_started is a
    # process-lifetime idempotent singleton), so /healthz can report
    # the transition instead of going dark.
    from ..common import flight_recorder as flight_recorder_mod
    from ..common import obs_server as obs_server_mod
    flight_recorder_mod.configure_from_config(cfg)
    flight_recorder_mod.install_hooks()
    try:
        obs_server_mod.ensure_started(cfg)
    except Exception:
        # the operator explicitly asked for the endpoint: a bind
        # failure fails init() loudly, never a silently-dark plane
        if _heartbeat is not None:
            _heartbeat.stop()
            _heartbeat = None
        engine.shutdown(wait=False)
        mesh_mod.shutdown_comm()
        raise
    # Retention + judgment (ISSUE 16): the time-series sampler and
    # SLO engine, process-lifetime like the obs server — an elastic
    # suspend/resume keeps the ring and the alert state, and the
    # registry underneath stays monotonic, so a transition never
    # reads as a phantom counter reset.
    from ..common import health as health_mod
    from ..common import timeseries as timeseries_mod
    health_mod.configure(cfg)
    timeseries_mod.ensure_started(cfg)
    # Durable state plane (server/wal.py, ISSUE 19): with
    # BYTEPS_DURABLE_DIR set, open the process-lifetime durable
    # trainer-side KV store — on a cold start this replays the
    # journal and restores the last snapshot cut BEFORE any push
    # lands, so a full-world crash resumes from disk instead of
    # from zero.  Process-lifetime like the obs server: an elastic
    # suspend/resume must not close and re-replay the journal.
    if cfg.durable_dir:
        from ..server import wal as wal_mod
        wal_mod.ensure_process_store(cfg)


def initialized() -> bool:
    return _engine is not None


def durable_kv_store():
    """The process-lifetime durable trainer-side KVStore opened by
    :func:`init` when ``BYTEPS_DURABLE_DIR`` is set (server/wal.py) —
    journaled mutations, atomic snapshot cuts, cold-start recovery.
    None when the durable plane is off."""
    import sys
    wal_mod = sys.modules.get("byteps_tpu.server.wal")
    return None if wal_mod is None else wal_mod.process_store()


def shutdown(wait: bool = True) -> None:
    """Tear down engine + mesh (reference byteps_shutdown)."""
    global _engine, _heartbeat
    with _lock:
        if _engine is None:
            return
        if _heartbeat is not None:
            _heartbeat.stop()
            _heartbeat = None
        _engine.shutdown(wait=wait)
        _engine = None
        mesh_mod.shutdown_comm()
        # chaos disarms with the engine; a subsequent init()/resume()
        # re-arms from config (fresh step counter, same seeded schedule).
        # persist-armed chaos (partition blackholes) stays: the network
        # does not heal because the engine suspended
        from ..fault import injector as fault_injector
        fault_injector.disarm(engine_scoped_only=True)


def membership_epoch() -> int:
    """The current elastic-membership epoch (fault/membership.py): 0 for
    the static world every non-elastic run lives in; advanced by each
    shrink/rejoin.  Work stamped with a dead epoch is dropped, not
    delivered."""
    from ..fault import membership as _membership
    return _membership.current_epoch()


def suspend(wait: bool = True) -> None:
    """Elastic-training pause: drain and stop (reference byteps_suspend,
    operations.cc:96-105).  Declared tensor order is retained so resume()
    reproduces identical key assignment.  Under elastic membership this
    is the drain half of a shrink/rejoin transition
    (fault/membership.py).  ``wait=False`` skips the handle drain — for
    transitions driven by a WEDGED data path, where the drain would
    block on the very unit that is stuck (the epoch guard already
    protects correctness: the wedged unit's late result is dropped as
    stale)."""
    global _declared_order
    eng = _require()
    _declared_order = eng.registry.names_in_declaration_order()
    # sharded-update slots hold the ONLY copy of master/optimizer state:
    # snapshot them at logical length so resume + declare_update re-pads
    # onto whatever mesh comes back (fewer ranks after a shrink)
    _suspended_update_state.update(eng.export_update_slots())
    shutdown(wait=wait)


def resume(config: Optional[Config] = None,
           devices: Optional[list] = None,
           num_workers: Optional[int] = None,
           num_servers: Optional[int] = None,
           global_rank: Optional[int] = None) -> None:
    """Elastic-training resume: re-init with possibly different topology
    (reference byteps_resume, operations.cc:107-119); tensors are re-declared
    in their original order.

    ``num_workers`` / ``num_servers`` / ``global_rank`` mirror the
    reference's ``BytePSBasics.resume`` signature
    (common/__init__.py:75-81): they update the DMLC env the same way
    (num_servers is accepted and ignored — no server processes on TPU)
    before re-initializing."""
    import os
    if initialized():
        raise RuntimeError(
            "resume() while the engine is running: call suspend() first "
            "(reference byteps_resume likewise requires a suspended core)")
    if num_workers is not None:
        os.environ["DMLC_NUM_WORKER"] = str(num_workers)
    if num_servers is not None:
        os.environ["DMLC_NUM_SERVER"] = str(num_servers)
    if global_rank is not None:
        # bpslint: ignore[env-knob] reason=reference-parity marker WRITTEN for BytePSBasics.resume compatibility, never read by this stack; recorded in the env.md disposition table
        os.environ["BYTEPS_GLOBAL_RANK"] = str(global_rank)
        os.environ["DMLC_WORKER_ID"] = str(global_rank)
    if config is None and (num_workers is not None
                           or global_rank is not None):
        config = Config.from_env()
    init(config=config, devices=devices)


def _require() -> PushPullEngine:
    if _engine is None:
        raise RuntimeError("byteps_tpu not initialized — call bps.init()")
    return _engine


def size() -> int:
    return _require().comm.num_ranks


def rank() -> int:
    return jax.process_index() * local_size()


def local_size() -> int:
    c = _require().comm
    return c.num_ranks // jax.process_count()


def local_rank() -> int:
    return 0  # one controller process per host owns all local chips


def declare(name: str, shape=None, dtype=None, op: str = "average",
            compression: Optional[Dict[str, str]] = None,
            local: Optional[bool] = None,
            replicate_out: bool = False) -> int:
    """Pre-declare a tensor; returns its declared key.  Usable before init
    (reference declare_tensor can run before byteps_lazy_init completes).

    With ``shape`` (and optionally ``dtype``, default float32) on a
    running engine, additionally AOT-compiles the tensor's steady-state
    program set so its first push_pull dispatches with zero compile
    stalls (PushPullEngine.declare_tensor)."""
    if _engine is not None:
        if shape is not None:
            return _engine.declare_tensor(
                name, shape, dtype if dtype is not None else "float32",
                op=op, local=local, compression=compression,
                replicate_out=replicate_out).declared_key
        return _engine.registry.declare(name).declared_key
    if name not in _declared_order:
        _declared_order.append(name)
    return _declared_order.index(name)


def declare_update(name: str, shape, dtype="float32", *, tx,
                   init_value=None) -> int:
    """Declare a tensor whose pull leg is the sharded weight update
    (ISSUE 20, ``BYTEPS_SHARDED_UPDATE``): the reduce-scatter shard
    stays on its owner, a per-shard optax ``tx`` update runs against
    engine-resident flat-shard master/optimizer state, and push_pull
    returns the UPDATES tensor instead of the merged gradient.  If a
    prior :func:`suspend` stashed this name's slot, the snapshot is
    re-imported here — re-padded to the current mesh, which is how an
    elastic shrink re-shards optimizer state.  Requires a running
    engine (the slot is device state); returns the declared key."""
    eng = _require()
    restore = _suspended_update_state.pop(name, None)
    return eng.declare_update(name, shape, dtype, tx=tx,
                              init_value=init_value,
                              restore=restore).declared_key


def push_pull_update(x, name: str, **kwargs) -> Any:
    """Synchronous sharded-update step for one declared tensor: push
    this process's gradient, receive the owner-computed optax updates
    (``optax.apply_updates(params, ...)`` applies them)."""
    return _require().push_pull_update(x, name, **kwargs)


def push_pull_update_async(x, name: str, **kwargs) -> Handle:
    return _require().push_pull_update_async(x, name, **kwargs)


def push_pull(stacked, name: str, op: str = "average",
              priority: Optional[int] = None,
              compression: Optional[Dict[str, str]] = None) -> Any:
    """Synchronous sum/average of rank-stacked tensors (Horovod allreduce)."""
    return _require().push_pull(stacked, name, op=op, priority=priority,
                                compression=compression)


def push_pull_async(stacked, name: str, op: str = "average",
                    priority: Optional[int] = None,
                    compression: Optional[Dict[str, str]] = None) -> Handle:
    return _require().push_pull_async(stacked, name, op=op, priority=priority,
                                      compression=compression)


def poll(handle: Handle) -> bool:
    return handle.poll()


def synchronize(handle: Handle, timeout: Optional[float] = None) -> Any:
    out = handle.wait(timeout=timeout)
    _require().handles.release(handle.id)
    return out


def get_pushpull_speed() -> tuple:
    """(timestamp, MB/s) telemetry (reference byteps_get_pushpull_speed)."""
    return _require().speed.speed()


def metrics_snapshot(light: bool = False) -> Dict[str, Any]:
    """This process's observability snapshot: counters + gauges (one
    consistent registry view), membership epoch, push_pull speed, and
    the last completed :class:`~byteps_tpu.common.telemetry.StepStats`.
    The full form also holds ``"startup"``: the process's start-up
    record (``common/telemetry.py`` ``startup_record``), which with the
    ``compile.*`` counters says where the time before the first step
    went.  ``light=True`` drops it and the histogram buckets — the
    compact form the membership bus piggybacks on every ``step_sync`` so
    the coordinator always holds a fresh per-rank view."""
    import os
    import time

    from ..common import metrics as _metrics
    from ..fault import membership as _membership
    reg = _metrics.registry.snapshot()
    snap: Dict[str, Any] = {
        "ts": time.time(),
        "pid": os.getpid(),
        "rank": get_config().host_id,
        "epoch": _membership.current_epoch(),
        "counters": reg["counters"],
        "gauges": reg["gauges"],
    }
    if not light:
        snap["histograms"] = reg["histograms"]
        snap["startup"] = telemetry.startup_record()
        from ..utils import slowness as _slowness
        snap["slowness"] = _slowness.tracker().snapshot()
    eng = _engine
    if eng is not None:
        snap["speed_mbps"] = round(eng.speed.speed()[1], 3)
        snap["scheduler"] = type(eng.scheduler).__name__
        snap["sched_pending"] = eng.scheduler.pending
        snap["bytes_in_flight"] = eng.scheduler.bytes_in_flight
        last = eng.step_stats.last()
        snap["step"] = last.as_dict() if last is not None else None
        if not light:
            snap["planner"] = eng.planner.snapshot()
    return snap


def start_serving(store, **kwargs):
    """Stand up the parameter-serving plane over ``store`` (a
    :class:`~byteps_tpu.server.kv_store.KVStore`): versioned snapshots,
    delta pulls, hot-key replicas (``server/serving.py``).  Keyword
    arguments forward to :class:`~byteps_tpu.server.serving.ServingPlane`
    (``replicas``, ``retention``, ``hot_keys``, ``cut_interval_s``);
    defaults come from the ``BYTEPS_SERVE_*`` knobs — including
    ``cut_interval_s`` from ``BYTEPS_SERVE_CUT_INTERVAL``, so a plane
    started through this entry point is write-driven out of the box
    (pass ``cut_interval_s=None`` explicitly for manual-``cut()``
    publication, the :class:`ServingPlane` constructor's default).
    Returns the plane; build consumers with
    :class:`~byteps_tpu.server.serve_client.PullClient`.  Works with or
    without a running engine — serving is a read plane, not a training
    mode."""
    from ..server.serving import ServingPlane
    kwargs.setdefault("cut_interval_s", get_config().serve_cut_interval_s)
    return ServingPlane(store, **kwargs)


def start_serving_tier(store, **kwargs):
    """Stand up the DISTRIBUTED serving tier over ``store``
    (``server/serving_tier.py``): out-of-process serving hosts behind
    the TCP transport, snapshot deltas shipped per the consistent-hash
    ring, admission-controlled pulls.  Keyword arguments forward to
    :class:`~byteps_tpu.server.serving_tier.ServingTier` (``bus``,
    ``static_hosts``, ``replicas``, ``retention``, ``cut_interval_s``,
    ...); like :func:`start_serving`, ``cut_interval_s`` defaults from
    ``BYTEPS_SERVE_CUT_INTERVAL`` so the tier is write-driven out of the
    box (pass ``cut_interval_s=None`` explicitly for manual ``cut()``
    publication).  Hosts come from the membership bus's serving-host
    directory (start them with ``python -m
    byteps_tpu.server.serve_host``); build consumers with
    ``tier.client()``.  Works with or without a running engine."""
    from ..server.serving_tier import ServingTier
    kwargs.setdefault("cut_interval_s", get_config().serve_cut_interval_s)
    return ServingTier(store, **kwargs)


def cluster_metrics(bus: Optional[str] = None,
                    timeout: float = 10.0) -> Dict[str, Any]:
    """Every live rank's metrics snapshot in ONE round-trip to the
    membership bus (the ``metrics`` verb, fault/membership.py): returns
    ``{"epoch", "world", "ranks": {rank: {"age_s", "metrics"}}}`` where
    each rank's entry is the snapshot it last attached to a
    ``step_sync`` (or pushed with ``metrics_put``), stamped with its
    age.  ``bus`` is ``host:port`` of the membership bus; default is the
    same resolution :class:`~byteps_tpu.fault.membership.ElasticMembership`
    uses (DMLC root + BYTEPS_MEMBERSHIP_PORT).

    The bus address is re-resolved from the ACTIVE membership view
    (``fault.membership.active_membership()``) so a coordinator change
    re-points the query at the successor instead of the static
    env-derived address.  While an elastic world's bus is not answering
    (a failover in progress), the answer degrades gracefully to a
    local-only view flagged ``failover_in_progress`` instead of
    raising; a run with no bus at all (single process, non-elastic)
    falls back to the plain local-only view — so ``tools/bps_top.py``
    works against anything."""
    from ..fault import membership as _membership
    m = _membership.active_membership()
    view = m.view() if (bus is None and m is not None) else None
    if view is not None and getattr(m, "gossip", None) is not None:
        # gossip-local answer (ISSUE 17): the SWIM table already holds
        # every rank's piggybacked metrics/history payloads, so the
        # query needs NO bus round-trip — and keeps working on either
        # side of a partition, where the bus may be unreachable
        table = m.gossip
        now = time.time()
        out = {"epoch": _membership.current_epoch(),
               "world": list(view.world), "gossip": True,
               "states": table.snapshot(), "ranks": {}, "history": {}}
        for kind, dest in (("metrics", out["ranks"]),
                           ("history", out["history"])):
            for r, v in table.payloads_of_kind(kind).items():
                if not isinstance(v, dict) or "t" not in v:
                    continue
                age = max(0.0, now - float(v["t"]))
                dest[int(r)] = (
                    {"age_s": round(age, 3), "metrics": v.get("v")}
                    if kind == "metrics"
                    else {"age_s": round(age, 3), "summary": v.get("v")})
        sd = table.payloads_of_kind("serve_dir")
        if sd:
            newest = max(sd.values(),
                         key=lambda p: p.get("t", 0)
                         if isinstance(p, dict) else 0)
            if isinstance(newest, dict):
                d = newest.get("v") or {}
                out["serve_hosts"] = {int(h): v for h, v in
                                      (d.get("hosts") or {}).items()}
                out["serve_gen"] = d.get("gen", 0)
        return out
    if view is not None:
        # the live membership already tracks the bus through failovers
        # (including explicitly-constructed addresses no env resolution
        # could re-derive)
        addr = m.bus_addr
    else:
        addr = _membership.resolve_bus_addr(bus, view)
    try:
        reply = _membership.bus_request(
            addr, {"op": "metrics"}, timeout=timeout)
    except ConnectionError:
        snap = metrics_snapshot()
        out: Dict[str, Any] = {
            "epoch": _membership.current_epoch(),
            "world": (list(view.world) if view is not None
                      else [snap["rank"]]),
            "ranks": {snap["rank"]: {"age_s": 0.0, "metrics": snap}},
            "local_only": True}
        from ..common import timeseries as _ts
        store = _ts.get_store()
        out["history"] = (
            {snap["rank"]: {"age_s": 0.0, "summary": store.summary()}}
            if store is not None and store.points() else {})
        if view is not None and view.num_workers > 1:
            # an elastic world exists but its bus is not answering: the
            # standby is (or should be) rebinding right now
            out["failover_in_progress"] = True
            out["coordinator"] = view.coordinator
            out["standby"] = m.standby_rank
        return out
    if not reply.get("ok"):
        raise RuntimeError(f"cluster_metrics failed: {reply!r}")
    # serving hosts publish at SERVE_RANK_BASE + host_id (one metrics
    # cache, two id spaces): split them into their own section so
    # bps_top renders trainer ranks and tier rows as what they are
    base = _membership.SERVE_RANK_BASE
    all_ranks = {int(r): v for r, v in reply["ranks"].items()}
    out = {"epoch": reply["epoch"], "world": reply["world"],
           "ranks": {r: v for r, v in all_ranks.items() if r < base},
           "serve_ranks": {r - base: v for r, v in all_ranks.items()
                           if r >= base},
           "serve_hosts": {int(h): v for h, v in
                           (reply.get("serve_hosts") or {}).items()},
           "serve_gen": reply.get("serve_gen", 0),
           # fleet reconciliation view (ISSUE 18): the autoscaler's
           # target and the DRAINING set — bps_top's fleet banner
           # (target=N actual=M) and per-host DRAINING state read these
           "serve_target": reply.get("serve_target"),
           "serve_draining": [int(h) for h in
                              (reply.get("serve_draining") or ())]}
    for k in ("coordinator", "standby", "bus_rank"):
        if reply.get(k) is not None:
            out[k] = reply[k]
    # gray-failure columns (ISSUE 10): per-rank step-barrier slowness
    # scores and the probation list — bps_top renders SLOW/STATE from
    # these, and empty is meaningful ("nobody is slow")
    out["slow"] = {int(r): v for r, v in (reply.get("slow") or {}).items()}
    out["probation"] = [int(r) for r in (reply.get("probation") or ())]
    # the history view (ISSUE 16): each rank's piggybacked time-series
    # window summary — bps_top's TREND column and bps_doctor's live
    # diagnosis read these, again with no extra round-trip
    out["history"] = {int(r): v
                      for r, v in (reply.get("history") or {}).items()}
    return out
