"""graftsan runtime sanitizer (testing/sanitizer.py).

Every armed scenario is deterministic: the ABBA schedule is event-gated so
the reverse acquisition always happens AFTER the forward edge is recorded
(and raises instead of deadlocking), the leak fence gets a thread parked on
an event the test controls, and every assertion on ``violations()`` runs
INSIDE the ``armed(...)`` context — exiting it resets the sanitizer's state
for test isolation.
"""

import json
import threading

import pytest

from autodist_tpu.testing import sanitizer as san
from autodist_tpu.testing.sanitizer import (SanViolation, san_condition,
                                            san_event, san_lock, san_rlock)


# ------------------------------------------------------------ disarmed = bare

def test_disarmed_factories_return_bare_primitives():
    with san.armed(""):
        assert type(san_lock()) is type(threading.Lock())          # noqa: E721
        assert type(san_rlock()) is type(threading.RLock())        # noqa: E721
        assert isinstance(san_condition(), threading.Condition)
        assert isinstance(san_event(), threading.Event)


def test_disarmed_condition_unwraps_sanitized_lock():
    with san.armed("locks"):
        wrapped = san_lock("outer")
    with san.armed(""):
        cond = san_condition(wrapped)
        assert isinstance(cond, threading.Condition)
        with cond:   # usable: the REAL lock was extracted from the wrapper
            cond.notify_all()


# ----------------------------------------------------------------- lock order

def test_dynamic_abba_aborts_with_both_stacks():
    with san.armed("locks"):
        a, b = san_lock("lockA"), san_lock("lockB")
        forward_done = threading.Event()
        caught = []

        def forward():
            with a:
                with b:        # records the a -> b edge
                    pass
            forward_done.set()

        def reverse():
            forward_done.wait(5.0)
            try:
                with b:
                    with a:    # b -> a closes the cycle: must raise, not hang
                        pass
            except SanViolation as e:
                caught.append(str(e))

        t1 = threading.Thread(target=forward, name="abba-forward")
        t2 = threading.Thread(target=reverse, name="abba-reverse")
        t1.start(), t2.start()
        t1.join(5.0), t2.join(5.0)
        assert not t1.is_alive() and not t2.is_alive()

        assert caught, "reverse acquisition was not aborted"
        msg = caught[0]
        assert "lock-order cycle" in msg
        assert "lockA" in msg and "lockB" in msg
        # BOTH sides of the inversion carry full stacks: the aborting
        # thread's held+acquiring frames AND the recorded forward thread's.
        assert "this thread" in msg and "other thread" in msg
        assert "abba-forward" in msg          # the recorded edge names its thread
        assert msg.count('File "') >= 4       # 2 stacks per side
        vs = san.violations()
        assert [v["kind"] for v in vs] == ["locks"]


def test_recursive_plain_lock_acquire_is_a_self_deadlock():
    with san.armed("locks"):
        lk = san_lock("plain")
        lk.acquire()
        try:
            with pytest.raises(SanViolation, match="self-deadlock"):
                lk.acquire()
            # try-acquire cannot deadlock: reported as a plain failure,
            # and the optimistic hold count is undone (release still works)
            assert lk.acquire(blocking=False) is False
        finally:
            lk.release()
        assert not lk.locked()


def test_rlock_reentrancy_is_not_a_violation():
    with san.armed("locks"):
        rl = san_rlock("re")
        with rl:
            with rl:
                assert rl.locked()
        assert san.violations() == []


def test_same_site_siblings_do_not_self_edge():
    # Lock arrays share one creation-site key; acquiring two SIBLINGS nested
    # must not record a self-edge (which would be an instant "cycle").
    with san.armed("locks"):
        shards = [san_lock("shard") for _ in range(2)]
        with shards[0]:
            with shards[1]:
                pass
        assert san.observed_edges() == []
        assert san.violations() == []


# ---------------------------------------------------------------------- waits

def test_untimed_condition_wait_flagged():
    with san.armed("locks,waits"):
        cond = san_condition(name="cv")
        with cond:
            with pytest.raises(SanViolation, match="without a timeout"):
                cond.wait()
        vs = san.violations()
        assert vs and vs[0]["kind"] == "waits"


def test_timed_wait_while_holding_another_lock_flagged():
    with san.armed("locks,waits"):
        lk = san_lock("held")
        ev = san_event("gate")
        with lk:
            with pytest.raises(SanViolation, match="while holding"):
                ev.wait(0.01)


def test_clean_timed_wait_passes():
    with san.armed("locks,waits"):
        cond = san_condition(name="ok")
        with cond:
            cond.wait(0.01)      # timed, no other lock held: clean
        ev = san_event("ok_ev")
        ev.set()
        assert ev.wait(0.01) is True
        assert san.violations() == []


# --------------------------------------------------------------- thread fence

def test_thread_fence_fires_on_leaked_nondaemon_thread():
    release = threading.Event()
    leaker = threading.Thread(target=lambda: release.wait(10.0),
                              name="fence-leaker")
    try:
        with san.armed("threads"):
            with pytest.raises(SanViolation) as exc:
                with san.thread_fence(grace_s=0.1):
                    leaker.start()
            assert "fence-leaker" in str(exc.value)
            assert "leaked 1 non-daemon thread" in str(exc.value)
    finally:
        release.set()
        leaker.join(5.0)


def test_thread_fence_passes_when_threads_join():
    with san.armed("threads"):
        with san.thread_fence(grace_s=1.0):
            t = threading.Thread(target=lambda: None)
            t.start()
            t.join(5.0)


# --------------------------------------------------------------------- export

def test_observed_edges_export_and_dump(tmp_path):
    with san.armed("locks"):
        a, b = san_lock("expA"), san_lock("expB")
        with a:
            with b:
                pass
        edges = san.observed_edges()
        assert any(e["outer"]["name"] == "expA"
                   and e["inner"]["name"] == "expB"
                   and e["count"] == 1 for e in edges)
        assert all(e["outer"]["path"] for e in edges)

        out = san.dump_observed(str(tmp_path / "obs.jsonl"))
        lines = [json.loads(line) for line in open(out, encoding="utf-8")]
        # meta header first (artifact is non-empty even edge-free), then edges
        assert "meta" in lines[0]
        assert lines[0]["meta"]["edges"] == len(edges)
        assert any("outer" in rec for rec in lines[1:])


def test_dump_observed_writes_meta_for_edge_free_run(tmp_path):
    with san.armed("locks"):
        out = san.dump_observed(str(tmp_path / "empty.jsonl"))
        lines = [json.loads(line) for line in open(out, encoding="utf-8")]
        assert lines and "meta" in lines[0]
        assert lines[0]["meta"]["edges"] == 0


# ------------------------------------------------- every test's own clock

def test_a_test_past_its_limit_fails_by_name_and_leaves_no_timer(request,
                                                                 monkeypatch):
    """``conftest.clock`` (the autouse fixture around every test): past
    ``LIMIT`` the test fails with its own node id, and neither the timer nor
    the handler outlives it."""
    import signal
    import time

    import conftest

    monkeypatch.setattr(conftest, "LIMIT", 0.2)
    before = signal.getsignal(signal.SIGALRM)
    started = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as caught:
        with conftest.clock(request.node.nodeid):
            time.sleep(30)
    assert time.monotonic() - started < 5
    assert str(caught.value) == f"{request.node.nodeid} exceeded 0.2 s"
    assert "test_a_test_past_its_limit" in str(caught.value)
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is before
    # a test inside its limit is left alone, and disarms on its way out too
    with conftest.clock(request.node.nodeid):
        assert signal.getitimer(signal.ITIMER_REAL)[0] > 0
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
