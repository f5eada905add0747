"""Master->survivor death push (VERDICT r4 Weak #3 / Next #4).

A survivor blocked in a collective on a dead peer used to wait out the
jax.distributed coordination heartbeat (default 30 s) before restarting.
``Worker.death_watch_tick`` — run from the liveness-heartbeat thread —
polls the master's membership and forces the RESTART exit within the grace
window of the master's eviction.  These tests drive the decision function
directly with a fake master; the real-process path is driven by
tests/test_multihost.py (slow) and, on the chip, by
benchmark/sizing/kill_drive.py.
"""

from __future__ import annotations

import pytest

from elasticdl_tpu.common.config import JobConfig


class _FakeMaster:
    def __init__(self):
        self.membership = {
            "version": 0,
            "world_size": 2,
            "ranks": {"w-a": 0, "w-b": 1},
            "addresses": {"w-a": "h1:1", "w-b": "h2:1"},
        }

    def call(self, method, req):
        assert method == "GetMembership"
        return dict(self.membership)


def _mk_worker(master, **cfg):
    from elasticdl_tpu.worker.worker import Worker

    config = JobConfig(
        model_def="mnist.model_spec", training_data="x", multihost=True, **cfg
    )
    w = Worker.__new__(Worker)  # no trainer/devices needed for the tick
    w.config = config
    w.master = master
    w.worker_id = "w-a"
    w._membership_version = 0
    w._ranks = {"w-a": 0, "w-b": 1}
    w._addresses = {"w-a": "h1:1", "w-b": "h2:1"}
    w._group_mode = True
    return w


def test_departure_forces_restart_after_grace():
    master = _FakeMaster()
    w = _mk_worker(master)
    state = {"pending_since": None}
    # Peer dies: master evicts it, version bumps.
    master.membership = {
        "version": 1, "world_size": 1,
        "ranks": {"w-a": 0}, "addresses": {"w-a": "h1:1"},
    }
    assert w.death_watch_tick(state, now=100.0) is False  # arms the window
    assert state["pending_since"] == 100.0
    assert w.death_watch_tick(state, now=101.0) is False  # inside grace
    assert w.death_watch_tick(state, now=102.5) is True   # grace expired


def test_main_thread_winning_race_disarms():
    master = _FakeMaster()
    w = _mk_worker(master)
    state = {"pending_since": None}
    master.membership = {
        "version": 1, "world_size": 1,
        "ranks": {"w-a": 0}, "addresses": {"w-a": "h1:1"},
    }
    assert w.death_watch_tick(state, now=100.0) is False
    # Main thread applied the change (it was between steps, not blocked).
    w._membership_version = 1
    w._ranks = {"w-a": 0}
    w._addresses = {"w-a": "h1:1"}
    assert w.death_watch_tick(state, now=105.0) is False
    assert state["pending_since"] is None


def test_pure_join_never_forces():
    master = _FakeMaster()
    w = _mk_worker(master)
    state = {"pending_since": None}
    master.membership = {
        "version": 1, "world_size": 3,
        "ranks": {"w-a": 0, "w-b": 1, "w-c": 2},
        "addresses": {"w-a": "h1:1", "w-b": "h2:1", "w-c": "h3:1"},
    }
    for now in (100.0, 105.0, 200.0):
        assert w.death_watch_tick(state, now=now) is False
    assert state["pending_since"] is None  # never even armed


def test_identical_topology_churn_never_forces():
    master = _FakeMaster()
    w = _mk_worker(master)
    state = {"pending_since": None}
    master.membership["version"] = 2  # same ranks+addresses, new version
    for now in (100.0, 200.0):
        assert w.death_watch_tick(state, now=now) is False
    assert state["pending_since"] is None


def test_disabled_by_grace_flag_and_non_group_mode():
    master = _FakeMaster()
    master.membership = {
        "version": 1, "world_size": 1,
        "ranks": {"w-a": 0}, "addresses": {"w-a": "h1:1"},
    }
    w = _mk_worker(master, death_push_grace_s=0.0)
    state = {"pending_since": None}
    for now in (100.0, 200.0):
        assert w.death_watch_tick(state, now=now) is False

    w2 = _mk_worker(master)
    w2._group_mode = False  # lone worker: no collective to be stuck in
    for now in (100.0, 200.0):
        assert w2.death_watch_tick(state, now=now) is False


def test_master_unreachable_keeps_window():
    master = _FakeMaster()
    w = _mk_worker(master)
    state = {"pending_since": None}
    master.membership = {
        "version": 1, "world_size": 1,
        "ranks": {"w-a": 0}, "addresses": {"w-a": "h1:1"},
    }
    assert w.death_watch_tick(state, now=100.0) is False

    def boom(method, req):
        raise ConnectionError("master briefly down")

    w.master = type("M", (), {"call": staticmethod(boom)})()
    assert w.death_watch_tick(state, now=105.0) is False
    assert state["pending_since"] == 100.0  # window survives the blip
    w.master = master
    assert w.death_watch_tick(state, now=105.0) is True
