"""Gang-formation settle protocol (worker.main.settle_membership).

On the CPU harness a 2-pod recovery churned through restart after restart
when staggered relaunches formed worlds one member at a
time or with stale incarnations; the settle gates (desired size + per-
member version confirmation) fixed it.  These tests drive the extracted
loop against the REAL RendezvousServer with scripted peer actions and a
virtual clock.
"""

from __future__ import annotations

import pytest

from elasticdl_tpu.master.rendezvous import RendezvousServer
from elasticdl_tpu.worker.main import settle_membership


class _DirectMaster:
    """Master adapter: the two RPCs the settle loop uses, in-process."""

    def __init__(self, rdzv: RendezvousServer, fail: set | None = None):
        self.r = rdzv
        self.fail = fail or set()  # step numbers whose RPCs raise
        self.step = 0

    def call(self, method, req):
        if self.step in self.fail:
            raise ConnectionError("master briefly unreachable")
        if method == "Heartbeat":
            return {
                "version": self.r.heartbeat(
                    req["worker_id"], req.get("version")
                )
            }
        if method == "GetMembership":
            return self.r.membership()
        raise AssertionError(method)


def _drive(rdzv, worker_id, actions, fail=None, max_s=50.0, expected_ok=True):
    """Run settle_membership with a virtual clock; ``actions`` maps a sleep
    step number to a callable performing peer activity."""
    master = _DirectMaster(rdzv, fail=fail)
    t = [0.0]
    steps = [0]

    def clock():
        return t[0]

    def sleep(dt):
        steps[0] += 1
        master.step = steps[0]
        t[0] += max(dt, 0.05)
        fn = actions.get(steps[0])
        if fn:
            fn()

    view = settle_membership(
        master,
        worker_id,
        rdzv.membership(),
        poll_s=0.05,
        stable_s=1.0,
        max_s=max_s,
        clock=clock,
        sleep=sleep,
    )
    return view, t[0], steps[0]


def test_waits_for_full_confirmed_gang():
    r = RendezvousServer()
    r.set_expected(2)
    r.register("A", "hostA:1")
    # B joins only at sleep step 3; B's registration confirms the new
    # version for B, and A's own versioned heartbeat confirms it for A.
    view, elapsed, steps = _drive(
        r, "A", {3: lambda: r.register("B", "hostB:1")}
    )
    assert view["world_size"] == 2
    assert sorted(view["workers"]) == ["A", "B"]
    assert all(
        view["confirmed"][w] == view["version"] for w in view["workers"]
    )
    assert steps >= 3  # did NOT form a world of 1 while alone
    assert elapsed < 10  # and did not ride to the deadline


def test_stale_incarnation_blocks_formation_until_replaced():
    r = RendezvousServer()
    r.set_expected(2)
    r.register("stale", "h1:1")   # confirmed v1
    r.register("A", "h2:1")       # confirmed v2; stale never re-confirms
    view, elapsed, _ = _drive(
        r, "A",
        {
            4: lambda: r.remove("stale"),          # its restart exits
            6: lambda: r.register("B", "h1:2"),    # fresh incarnation
        },
    )
    assert sorted(view["workers"]) == ["A", "B"]
    assert "stale" not in view["workers"]
    assert all(
        view["confirmed"][w] == view["version"] for w in view["workers"]
    )
    assert elapsed < 10


def test_deadline_degrades_instead_of_wedging():
    r = RendezvousServer()
    r.set_expected(3)  # third member never arrives (crash loop)
    r.register("A", "h1:1")
    r.register("B", "h2:1")
    view, elapsed, _ = _drive(r, "A", {}, max_s=5.0)
    assert view["world_size"] == 2  # proceeds with who is present
    assert elapsed >= 5.0


def test_no_expected_falls_back_to_version_stability():
    r = RendezvousServer()  # expected stays 0: hand-spawned workers
    r.register("A", "h1:1")
    view, elapsed, _ = _drive(r, "A", {})
    assert view["world_size"] == 1
    assert 1.0 <= elapsed < 5.0  # stable_s wait, not the full deadline


def test_master_blips_are_retried():
    r = RendezvousServer()
    r.set_expected(2)
    r.register("A", "h1:1")
    view, elapsed, _ = _drive(
        r, "A",
        {2: lambda: r.register("B", "h2:1")},
        fail={1, 3, 4},  # RPCs raise on these polls
    )
    assert view["world_size"] == 2
    assert all(
        view["confirmed"][w] == view["version"] for w in view["workers"]
    )


def test_scale_down_waits_for_doomed_members_to_drain():
    """Scale-down window: desired size drops to 2 while the 2 doomed
    members are still registered (terminate grace).  Forming the 4-member
    world would guarantee an immediate re-collapse as they exit — the gate
    requires EXACT size, so formation waits for the drain."""
    r = RendezvousServer()
    r.set_expected(4)
    for w, h in (("A", "h1:1"), ("B", "h2:1"), ("C", "h3:1"), ("D", "h4:1")):
        r.register(w, h)
    r.set_expected(2)  # scale-down begins; C and D are being torn down
    # Everyone still heartbeats the current version during the grace.
    for w in "BCD":
        r.heartbeat(w, r.membership()["version"])
    view, elapsed, steps = _drive(
        r, "A",
        {
            3: lambda: r.remove("C"),
            5: lambda: (
                r.remove("D"),
                r.heartbeat("B", r.membership()["version"]),
            ),
        },
    )
    assert sorted(view["workers"]) == ["A", "B"]
    assert view["world_size"] == 2
    assert steps >= 5  # did NOT form the oversized 4-member world
    assert elapsed < 10
