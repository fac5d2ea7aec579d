"""The data-path sync deadline (ISSUE 8 tentpole part 3,
``BYTEPS_SYNC_DEADLINE_S``): a unit the engine's syncer stays blocked on
past the deadline — the wedged-collective TPU failure mode — becomes
failure evidence routed to the INSTALLED failure action
(``failure_detector.data_path_stalled``), with ``os._exit`` demoted to
the escalation of last resort.  Under ``ElasticMembership`` the evidence
(an empty stale set) becomes a *reconcile* rendezvous.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

import byteps_tpu.core.api as api
from byteps_tpu.common.config import Config, reset_config
from byteps_tpu.common.telemetry import counters
from byteps_tpu.fault import membership as mm
from byteps_tpu.utils import failure_detector as fd

from .conftest import free_port as _free_port


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    """Fresh epoch + no leaked installed action + exit trapped (a real
    os._exit would take pytest with it — and the whole point here is
    proving it is NOT called)."""
    mm._reset_epoch_for_tests()
    exits = []
    monkeypatch.setattr(fd, "_exit", lambda code: exits.append(code))
    # the membership escalation path exits through its OWN alias — trap
    # it too so a failed transition shows up as a failed assert on
    # `exits`, not a dead pytest process
    monkeypatch.setattr(mm, "_exit", lambda code: exits.append(code))
    yield exits
    fd.install_failure_action(None)
    if api.initialized():
        api.shutdown()
    api._declared_order = []
    mm._reset_epoch_for_tests()


def _wedge_next_unit(eng, seconds):
    """Make the NEXT unit the syncer retires block ``seconds`` (one-shot;
    restores the real block hook before sleeping so only one unit is
    wedged)."""
    orig = eng._block

    def _wedge_once(x):
        eng._block = orig
        time.sleep(seconds)
        return orig(x)
    eng._block = _wedge_once


def _wait_for(pred, timeout=15.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"{what} not reached within {timeout}s")


def test_sync_deadline_off_by_default():
    assert Config().sync_deadline_s == 0.0
    api.init(Config())
    assert api._require()._deadline_thread is None


def test_sync_deadline_config_validation(monkeypatch):
    with pytest.raises(ValueError, match="sync_deadline_s"):
        Config(sync_deadline_s=-1.0)
    monkeypatch.setenv("BYTEPS_SYNC_DEADLINE_S", "2.5")
    reset_config()
    from byteps_tpu.common.config import get_config
    assert get_config().sync_deadline_s == 2.5


@pytest.mark.chaos
def test_sync_deadline_fires_installed_action_not_exit(_clean_slate):
    """A wedged unit trips the deadline: the installed action receives
    the empty-stale-set evidence, counters/flight record it, and the
    process does NOT exit.  The unit itself still completes once the
    wedge resolves (no world change here — the action only observes)."""
    exits = _clean_slate
    calls = []
    fd.install_failure_action(lambda stale: calls.append(set(stale)))
    api.init(Config(sync_deadline_s=0.3))
    eng = api._require()
    assert eng._deadline_thread is not None
    _wedge_next_unit(eng, 1.2)
    h = eng.push_pull_local_async(np.ones(8, np.float32), "g", op="sum")
    _wait_for(lambda: calls, what="installed failure action call")
    assert calls[0] == set()          # wedge evidence names no suspect
    assert counters.get("engine.sync_deadline_trips") >= 1
    assert exits == []                # os._exit stayed the last resort
    out = np.asarray(h.wait(timeout=30))
    np.testing.assert_allclose(out, 1.0)


@pytest.mark.chaos
def test_sync_deadline_routes_through_reconcile_not_exit(_clean_slate):
    """End-to-end single-rank loop: deadline trip → installed
    ElasticMembership action → reconcile rendezvous (epoch +1, same
    world) → engine suspended/resumed — and the wedged unit's late
    result is dropped as stale, never delivered."""
    exits = _clean_slate
    port = _free_port()
    api.init(Config(sync_deadline_s=0.3,
                    membership_rendezvous_timeout_s=3.0,
                    membership_sync_timeout_s=10.0))
    m = mm.ElasticMembership(0, [0], f"127.0.0.1:{port}").start()
    try:
        fd.install_failure_action(m.on_failure)
        eng = api._require()
        _wedge_next_unit(eng, 1.5)
        h = eng.push_pull_local_async(np.ones(8, np.float32), "g", op="sum")
        _wait_for(lambda: mm.current_epoch() >= 1, what="reconcile epoch")
        assert counters.get("membership.reconcile_started") >= 1
        # the wedged unit was issued under epoch 0 and must be dropped
        with pytest.raises(RuntimeError, match="stale membership epoch"):
            h.wait(timeout=30)
        # the world re-agreed unchanged and the engine is back up
        _wait_for(lambda: api.initialized() and api._require()._running,
                  what="resumed engine")
        # the engine can resume a beat before THIS instance applies the
        # agreed view — wait on the view itself, don't assert the race
        _wait_for(lambda: m.view() == mm.MembershipView(1, (0,)),
                  what="reconciled view applied")
        out = api._require().push_pull_local(np.ones(8, np.float32), "g2",
                                             op="sum")
        np.testing.assert_allclose(np.asarray(out), 1.0)
        assert exits == []
    finally:
        m.stop()


@pytest.mark.chaos
def test_concurrent_stall_reports_fire_the_action_once(_clean_slate):
    """ISSUE 10 satellite: the sync-deadline watchdog and the step
    watchdog are separate threads observing the same wedge — a second
    ``data_path_stalled`` arriving while the first is still being acted
    on must be suppressed, not double-run the failure action (or,
    uninstalled, double-fire ``os._exit``)."""
    import threading
    exits = _clean_slate
    calls = []
    entered = threading.Event()

    def slow_action(stale):
        calls.append(set(stale))
        entered.set()
        time.sleep(0.5)         # the first report is still in flight...

    fd.install_failure_action(slow_action)
    t = threading.Thread(target=fd.data_path_stalled, args=(1.0, "first"))
    t.start()
    assert entered.wait(5.0)
    fd.data_path_stalled(1.0, "second")     # ...when the second lands
    t.join(timeout=5)
    assert calls == [set()]                 # the action ran ONCE
    assert counters.get("failure_detector.stall_suppressed") == 1
    assert exits == []
    # sequential reports (a later, distinct stall) still escalate
    fd.data_path_stalled(2.0, "third")
    assert len(calls) == 2


@pytest.mark.chaos
def test_stall_during_inflight_shrink_does_not_double_exit(_clean_slate,
                                                           monkeypatch):
    """Regression guard: a watchdog stall landing DURING an in-flight
    elastic transition (epoch already advanced by the shrink) resolves
    through the membership's already-moving-world path — never a second
    ``os._exit`` racing the transition."""
    import threading
    exits = _clean_slate
    port = _free_port()
    m = mm.ElasticMembership(0, [0], f"127.0.0.1:{port}",
                             rendezvous_timeout_s=2.0,
                             sync_timeout_s=5.0).start()
    # "DURING" held by events, not by who wins a race: the applier stops
    # inside the transition (epoch up, view not yet moved) until the
    # stall report is following it.  Left to timing, an applier that
    # finishes first turns the report into an ordinary reconcile to
    # epoch 2 — correct behaviour, and a failed assertion under load.
    in_transition, following = threading.Event(), threading.Event()
    real_resume, real_wait_ready = mm._resume_for_world, m.wait_ready

    def held_resume(view, devices):
        in_transition.set()
        assert following.wait(10.0), "the stall report never followed"
        return real_resume(view, devices)

    def wait_ready(*args, **kwargs):
        following.set()
        return real_wait_ready(*args, **kwargs)

    monkeypatch.setattr(mm, "_resume_for_world", held_resume)
    monkeypatch.setattr(m, "wait_ready", wait_ready)
    try:
        fd.install_failure_action(m.on_failure)
        # an in-flight transition: another thread is applying epoch 1
        applier = threading.Thread(
            target=lambda: m._maybe_apply(mm.MembershipView(1, (0,))))
        mm.set_epoch(1)          # the shrink's guard is already up
        applier.start()
        assert in_transition.wait(10.0)
        # the stall report arrives mid-transition: reconcile sees the
        # epoch already moving and FOLLOWS it (wait_ready), no exit
        fd.data_path_stalled(3.0, "watchdog during shrink")
        applier.join(timeout=30)
        assert not applier.is_alive() and following.is_set()
        assert m.view().epoch == 1
        assert exits == [], exits
    finally:
        fd.install_failure_action(None)
        m.stop()


@pytest.mark.chaos
def test_step_watchdog_default_prefers_installed_action(_clean_slate):
    """StepWatchdog's default stall action is demoted: with an installed
    failure action the evidence goes there (empty stale set); os._exit
    only when nothing is installed."""
    exits = _clean_slate
    calls = []
    fd.install_failure_action(lambda stale: calls.append(set(stale)))
    wd = fd.StepWatchdog(timeout=0.2).start()
    try:
        _wait_for(lambda: calls, timeout=5.0, what="watchdog stall action")
        assert calls[0] == set()
        assert exits == []
    finally:
        wd.stop()
    # without an installed action the last resort still exits restartable
    fd.install_failure_action(None)
    wd2 = fd.StepWatchdog(timeout=0.2).start()
    try:
        _wait_for(lambda: exits, timeout=5.0, what="last-resort exit")
        assert exits[0] == 17
    finally:
        wd2.stop()
