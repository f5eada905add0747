"""Deadline-bounded gang boundary (r13): dispatcher skip accounting and
the servicer's straggler-skip protocol, driven with a fake clock so the
deadline mechanics are deterministic."""

import pytest

from elasticdl_tpu.common import trace
from elasticdl_tpu.data.reader import Shard
from elasticdl_tpu.master.rendezvous import RendezvousServer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher


def _shards(n, size=10):
    return [Shard("f", i * size, (i + 1) * size) for i in range(n)]


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


# ---------------------------------------------------------------------------
# dispatcher: bounded skip accounting
# ---------------------------------------------------------------------------

class TestSkipAccounting:
    def test_skip_requeues_without_charging_retry_budget(self):
        d = TaskDispatcher(_shards(2), task_skip_budget=2)
        t = d.get_task("gang")
        lost = d.skip_tasks("gang")
        assert [x.task_id for x in lost] == [t.task_id]
        c = d.counts()
        assert c["skipped"] == 1 and c["skip_counts"] == {t.task_id: 1}
        # Requeued at the FRONT, retry budget untouched: the same shard
        # hands out again and can still fail max_retries times.
        t2 = d.get_task("w0")
        assert t2.shard == t.shard
        assert d._failed_counts == {}

    def test_skips_beyond_budget_charge_like_failures(self):
        d = TaskDispatcher(_shards(1), task_skip_budget=1, max_task_retries=1)
        t = d.get_task("gang")
        d.skip_tasks("gang")                    # skip 1: free
        d.get_task("gang")
        d.skip_tasks("gang")                    # skip 2: charged (fail 1/1)
        assert d._failed_counts == {t.task_id: 1}
        d.get_task("gang")
        d.skip_tasks("gang")                    # skip 3: fail 2 > budget
        c = d.counts()
        assert c["abandoned"] == 1 and c["skipped"] == 3
        assert d.finished()  # the poison shard cannot wedge the job

    def test_skip_after_stop_drops(self):
        d = TaskDispatcher(_shards(1), task_skip_budget=2)
        d.get_task("gang")
        d.stop()
        d.skip_tasks("gang")
        assert d.counts()["todo"] == 0 and d.finished()

    def test_skipped_task_still_trains_exactly_once(self):
        d = TaskDispatcher(_shards(1), task_skip_budget=2)
        t = d.get_task("gang")
        d.skip_tasks("gang")
        t2 = d.get_task("w1")
        assert t2.task_id == t.task_id
        assert d.report(t2.task_id, True)
        c = d.counts()
        assert c["done"] == 1 and c["duplicate_done"] == 0 and d.finished()

    def test_duplicate_done_counter(self):
        d = TaskDispatcher(_shards(1))
        t = d.get_task("w0")
        assert d.report(t.task_id, True)
        assert not d.report(t.task_id, True)  # late duplicate: rejected
        assert not d.report(t.task_id, False)  # late failure: benign
        assert d.counts()["duplicate_done"] == 1


# ---------------------------------------------------------------------------
# servicer: the deadline protocol over GetGroupTask/Heartbeat
# ---------------------------------------------------------------------------

def _gang(n_shards=6, deadline_ms=200.0, budget=2):
    clock = FakeClock()
    dispatcher = TaskDispatcher(
        _shards(n_shards), task_skip_budget=budget, clock=clock
    )
    rendezvous = RendezvousServer(heartbeat_timeout_s=1e9, clock=clock)
    servicer = MasterServicer(
        dispatcher, rendezvous=rendezvous,
        gang_deadline_ms=deadline_ms, clock=clock,
    )
    return servicer, clock


def _join(servicer, *workers):
    for w in workers:
        servicer.RegisterWorker({"worker_id": w})
    version = servicer.rendezvous.version()
    for w in workers:
        servicer.Heartbeat({"worker_id": w, "version": version})
    return version


def _pull(servicer, worker, seq, version):
    return servicer.GetGroupTask(
        {"worker_id": worker, "seq": seq, "version": version}
    )


def test_gang_deadline_skips_straggler_and_preserves_exactly_once():
    trace.configure(enabled=True)
    trace.default().clear()
    try:
        servicer, clock = _gang()
        d = servicer.dispatcher
        v = _join(servicer, "w0", "w1")

        # Both ranks cross boundary 0 together; the gang trains task 0.
        e0 = _pull(servicer, "w0", 0, v)
        assert _pull(servicer, "w1", 0, v) == e0 and e0["task"] is not None
        servicer.ReportTaskResult({
            "worker_id": "w1", "task_id": e0["task"]["task_id"],
            "task_type": "training", "success": True,
        })

        # w1 begins dispatching entry 1 (arrival counter 2) and blocks in
        # the collective; w0 stalls before arriving (counter frozen at
        # 1).  The beats carry the divergence.  Within the deadline
        # nothing happens; past it the heartbeat-driven check skips w0.
        e1 = _pull(servicer, "w1", 1, v)
        assert e1["task"] is not None
        in_flight = e1["task"]["task_id"]
        servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 1})
        servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 2})
        clock.advance(0.1)
        assert servicer.Heartbeat(
            {"worker_id": "w1", "version": v, "gang_seq": 2}
        )["version"] == v
        clock.advance(0.15)  # now 0.25s past the front's arrival at 2
        resp = servicer.Heartbeat(
            {"worker_id": "w1", "version": v, "gang_seq": 2}
        )
        assert resp["version"] != v  # membership bumped: w0 was skipped

        status = servicer.JobStatus({})
        assert status["skipped_ranks"] == {"w0": 1}
        assert status["skip_counts"] == {in_flight: 1}
        assert status["skipped"] == 1
        names = [e["name"] for e in trace.default().export()]
        assert "gang:skip" in names and "lease:skip" in names

        # The straggler's poll of the dead world reads stale -> restart.
        assert _pull(servicer, "w0", 1, v)["stale"]

        # Both restart and re-register; the reformed gang drains the log
        # from seq 0 — the skipped task requeued exactly once, so done
        # lands exactly on the shard count with zero duplicates.
        v2 = _join(servicer, "w0", "w1")
        seq = 0
        while True:
            ea = _pull(servicer, "w0", seq, v2)
            eb = _pull(servicer, "w1", seq, v2)
            assert ea == eb
            if ea["finished"]:
                break
            if ea["task"] is None:
                pytest.fail("gang starved: no entry and not finished")
            servicer.ReportTaskResult({
                "worker_id": "w0", "task_id": ea["task"]["task_id"],
                "task_type": "training", "success": True,
            })
            seq += 1
        final = d.counts()
        assert final["done"] == 6 and final["duplicate_done"] == 0
        assert final["abandoned"] == 0 and final["skipped"] == 1
    finally:
        trace.configure(enabled=False)
        trace.default().clear()


def test_gang_deadline_disabled_never_skips():
    servicer, clock = _gang(deadline_ms=0.0)
    v = _join(servicer, "w0", "w1")
    _pull(servicer, "w0", 0, v)
    _pull(servicer, "w1", 0, v)
    servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 1})
    servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 2})
    clock.advance(3600.0)
    resp = servicer.Heartbeat(
        {"worker_id": "w1", "version": v, "gang_seq": 2}
    )
    assert resp["version"] == v  # nobody evicted, however long the lag
    assert servicer.JobStatus({})["skipped_ranks"] == {}


def test_gang_deadline_waits_inside_window():
    servicer, clock = _gang(deadline_ms=500.0)
    v = _join(servicer, "w0", "w1")
    _pull(servicer, "w0", 0, v)
    _pull(servicer, "w1", 0, v)
    servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 1})
    servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 2})
    clock.advance(0.4)  # inside the window: a slow-but-alive rank is fine
    assert servicer.Heartbeat(
        {"worker_id": "w1", "version": v, "gang_seq": 2}
    )["version"] == v


def test_gang_deadline_heartbeat_progress_sees_wedged_batch():
    """Lease batching leaves every rank's LAST boundary ask at the same
    seq — from asks alone a mid-batch straggler is invisible (its healthy
    peers are wedged in the collective ON it and never reach the next
    boundary either; consumption freezes at the same value gang-wide).
    The heartbeat's ``gang_seq`` ARRIVAL counter is the signal that
    diverges: a healthy rank counts an entry when it BEGINS dispatching
    it — it arrived at the collective, then blocked inside — while the
    straggler that never reached the boundary never counts it.  The skip
    must fire on that signal alone."""
    servicer, clock = _gang()
    v = _join(servicer, "w0", "w1")
    servicer.GetGroupTask(
        {"worker_id": "w0", "seq": 0, "version": v, "lease": 4}
    )
    servicer.GetGroupTask(
        {"worker_id": "w1", "seq": 0, "version": v, "lease": 4}
    )
    servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 3})
    servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 2})
    clock.advance(0.25)
    resp = servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 3})
    assert resp["version"] != v  # w0 skipped on heartbeat progress alone
    assert servicer.JobStatus({})["skipped_ranks"] == {"w0": 1}


def test_gang_progress_is_version_gated_and_monotonic():
    """A beat from a stale world must not seed the current world's
    deadline clock, and a late lower-seq signal must not regress a rank's
    recorded progress (which would fabricate a straggler)."""
    servicer, clock = _gang()
    v = _join(servicer, "w0", "w1")
    _pull(servicer, "w0", 0, v)
    _pull(servicer, "w1", 0, v)
    # Stale-version beat: ignored — the head must not advance.
    servicer.Heartbeat({"worker_id": "w1", "version": v - 1, "gang_seq": 5})
    clock.advance(0.25)
    assert servicer.maybe_skip_straggler() is None
    # Monotonic: a late gang_seq=0 beat cannot drag w1 behind w0.
    servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 2})
    servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 0})
    servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 2})
    clock.advance(0.25)
    assert servicer.maybe_skip_straggler() is None  # nobody actually lags


def test_deadline_evicted_rank_beats_cannot_revive_membership():
    """The straggler's process is often still ALIVE after the skip (a
    stall, not a crash) — its background liveness beat keeps arriving,
    and the rendezvous heartbeat's unknown-worker path would re-register
    it unconfirmed, undoing the eviction and wedging the reform on a
    rank that cannot confirm the new version.  The servicer must refuse
    the revival (and the rank's stale gang progress) until the rank
    deliberately re-registers — its restart path."""
    servicer, clock = _gang()
    rv = servicer.rendezvous
    v = _join(servicer, "w0", "w1")
    _pull(servicer, "w0", 0, v)
    _pull(servicer, "w1", 0, v)
    servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 1})
    servicer.Heartbeat({"worker_id": "w1", "version": v, "gang_seq": 2})
    clock.advance(0.25)
    # The straggler's OWN beat trips the deadline: the skip fires inside
    # this very Heartbeat call, and the response must already refuse the
    # revival (the eviction re-check runs after the skip).
    resp = servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 1})
    assert servicer.JobStatus({})["skipped_ranks"] == {"w0": 1}
    v_evicted = rv.version()
    assert resp["version"] == v_evicted and resp["version"] != v
    assert "w0" not in rv.membership()["workers"]
    # The wedged rank's beat thread keeps beating: no revival, no version
    # churn — the response's version mismatch is what drives its restart.
    for _ in range(3):
        resp = servicer.Heartbeat(
            {"worker_id": "w0", "version": v, "gang_seq": 1}
        )
        assert resp["version"] == v_evicted and resp["version"] != v
    assert "w0" not in rv.membership()["workers"]
    assert rv.version() == v_evicted
    # Its stale gang_seq stayed out of the deadline accounting: only w1
    # remains at the boundary, and nobody lags anyone.
    clock.advance(0.25)
    assert servicer.maybe_skip_straggler() is None
    # A stale arrival re-seeded by a beat that lost the check-then-act
    # race against the eviction (interleaving: first evicted-check passes,
    # the skip lands, note_gang_progress re-inserts) is dropped by the
    # next refused beat — left behind, it would fake a SECOND eviction of
    # the same stall one deadline later, double-charging the skip budget.
    with servicer._group_lock:
        servicer._gang_arrivals["w0"] = (1, clock())
    servicer.Heartbeat({"worker_id": "w0", "version": v, "gang_seq": 1})
    with servicer._group_lock:
        assert "w0" not in servicer._gang_arrivals
    clock.advance(0.25)
    assert servicer.maybe_skip_straggler() is None
    assert servicer.JobStatus({})["skipped_ranks"] == {"w0": 1}
    # Deliberate re-registration (the restart path) lifts the block.
    v2 = _join(servicer, "w0", "w1")
    assert "w0" in rv.membership()["workers"]
    assert servicer.Heartbeat(
        {"worker_id": "w0", "version": v2, "gang_seq": 0}
    )["version"] == v2


def test_gang_deadline_skips_one_rank_per_window():
    """Three ranks, two stragglers: one eviction per deadline window —
    skips stay attributable one rank at a time, and the second laggard
    gets a fresh deadline against the re-formed gang."""
    servicer, clock = _gang()
    v = _join(servicer, "w0", "w1", "w2")
    for w in ("w0", "w1", "w2"):
        _pull(servicer, w, 0, v)  # establishes the lockstep world
    for w in ("w0", "w1"):
        servicer.Heartbeat({"worker_id": w, "version": v, "gang_seq": 1})
    servicer.Heartbeat({"worker_id": "w2", "version": v, "gang_seq": 2})
    clock.advance(0.25)
    assert servicer.maybe_skip_straggler() in ("w0", "w1")
    assert servicer.maybe_skip_straggler() is None  # clock restarted
    assert sum(servicer.JobStatus({})["skipped_ranks"].values()) == 1
