"""The runtime lock-order sanitizer: a deliberately inverted
acquisition order across two threads must be witnessed and fatal,
reentrant re-entry and consistent orders must stay clean, and the
env-flag gate must keep production locks plain stdlib objects."""
import threading
import time

import pytest

from repro.analysis.lockwitness import (LockWitness, WitnessedLock,
                                        make_lock)


def _locks(w, *names, reentrant=False):
    return [make_lock(n, reentrant=reentrant, witness=w) for n in names]


def test_inverted_order_across_threads_detected():
    w = LockWitness()
    a, b = _locks(w, "Sink._lock", "Tier._lock")
    # rendezvous so both threads really interleave rather than one
    # finishing before the other starts
    t1_has_a = threading.Event()
    t2_has_b = threading.Event()

    def t1():
        with a:
            t1_has_a.set()
            t2_has_b.wait(5)
            # don't nest for real (that could deadlock) — release and
            # take B afterwards holding nothing; the A->B edge below
            # comes from t3
        with b:
            pass

    def t3():
        with a:
            with b:                      # A -> B
                pass

    def t2():
        t1_has_a.wait(5)
        with b:
            t2_has_b.set()
            with a:                      # B -> A: the inversion
                pass

    th3 = threading.Thread(target=t3)
    th3.start(); th3.join()
    th1 = threading.Thread(target=t1)
    th2 = threading.Thread(target=t2)
    th1.start(); th2.start()
    th1.join(10); th2.join(10)

    inv = w.inversions()
    assert ("Sink._lock", "Tier._lock") in inv
    with pytest.raises(AssertionError, match="inversion"):
        w.assert_clean()


def test_consistent_order_is_clean():
    w = LockWitness()
    a, b = _locks(w, "Sink._lock", "Tier._lock")

    def worker():
        for _ in range(20):
            with a:
                with b:
                    pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert w.inversions() == []
    w.assert_clean()                     # no raise
    rep = w.report()
    assert {"from": "Sink._lock", "to": "Tier._lock", "count": 80} \
        in rep["edges"]
    assert rep["holds"]["Sink._lock"]["count"] == 80


def test_reentrant_reentry_records_one_hold_no_self_edge():
    w = LockWitness()
    (lk,) = _locks(w, "HostTier._lock", reentrant=True)
    with lk:
        with lk:                         # re-entry, same instance
            pass
    assert w.inversions() == []
    assert w.holds["HostTier._lock"][0] == 1
    assert w.edges == {}


def test_two_instances_same_name_is_self_edge():
    w = LockWitness()
    a, b = _locks(w, "HostTier._lock", "HostTier._lock")
    with a:
        with b:                          # distinct instances, one name
            pass
    assert ("HostTier._lock", "HostTier._lock") in w.inversions()


def test_hold_time_outlier_recorded_not_fatal():
    w = LockWitness()
    w.hold_threshold_s = 0.01
    (lk,) = _locks(w, "MetricsRegistry._lock")
    with lk:
        time.sleep(0.03)
    rep = w.report()
    assert len(rep["hold_outliers"]) == 1
    out = rep["hold_outliers"][0]
    assert out["lock"] == "MetricsRegistry._lock" and out["held_s"] > 0.01
    w.assert_clean()                     # outliers are not fatal


def test_reset_clears_state():
    w = LockWitness()
    a, b = _locks(w, "A._lock", "B._lock")
    with a:
        with b:
            pass
    assert w.edges and w.holds
    w.reset()
    assert not w.edges and not w.holds and not w.hold_outliers


def test_make_lock_plain_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_LOCK_WITNESS", raising=False)
    lk = make_lock("X._lock")
    assert not isinstance(lk, WitnessedLock)
    rk = make_lock("X._lock", reentrant=True)
    with rk:
        with rk:                         # really reentrant
            pass


def test_make_lock_witnessed_under_env_flag(monkeypatch):
    monkeypatch.setenv("REPRO_LOCK_WITNESS", "1")
    lk = make_lock("X._lock")
    assert isinstance(lk, WitnessedLock)


def test_nonblocking_acquire_failure_records_nothing():
    w = LockWitness()
    (lk,) = _locks(w, "A._lock")
    lk.acquire()
    try:
        got = []
        t = threading.Thread(
            target=lambda: got.append(lk.acquire(blocking=False)))
        t.start(); t.join()
        assert got == [False]
    finally:
        lk.release()
    assert w.holds["A._lock"][0] == 1    # only the successful one
