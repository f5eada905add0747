"""Metrics writer — the master's structured observability sink.

Reference parity (SURVEY.md §5 "Metrics/logging/observability" [U — mount
empty at survey time]): the reference surfaces eval metrics via gRPC to the
master and optionally TensorBoard through Keras callbacks.  Here the master
appends every training/eval metric report to a JSONL stream (one
machine-parseable record per event, crash-safe append) and mirrors scalars
into a TensorBoard events file it frames itself (``_EventFile``: no
TensorBoard, tensorboardX, torch or TensorFlow import in the master).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import socket
import struct
import threading
import time
from typing import Dict, Optional

from elasticdl_tpu.common import locksan, trace

#: The master's JSONL scalar stream under its metrics directory.  Durable
#: in the WAL-reader sense (torn-tail-tolerant reads via durable.read_wal)
#: but written ADVISORY: records are flushed, never fsync'd — losing the
#: page-cache tail of a metrics stream costs observability, not
#: correctness, and an fsync per scalar report would serialize the
#: master's report handlers on the disk.
METRICS_FILENAME = "metrics.jsonl"  # durable-file

#: Metric keys with this prefix carry HISTOGRAM vectors, not scalars.  They
#: flow through every aggregation layer (device psum, worker minibatch sums,
#: master cross-worker weighted means) unchanged in meaning — histograms are
#: linear, and the scalars derived from them (AUC) are scale-invariant, so
#: weighted MEANS aggregate as exactly as sums would.  ``finalize_metrics``
#: converts them to their scalar at the last step of each pipeline.
HIST_PREFIX = "__hist__"

#: The one histogram-derived metric so far: ROC AUC from score histograms
#: (the reference evaluates Criteo/DeepFM on AUC via TF's bucketed streaming
#: AUC — same construction).
AUC_POS = HIST_PREFIX + "auc_pos"
AUC_NEG = HIST_PREFIX + "auc_neg"


def auc_from_histograms(pos, neg) -> float:
    """ROC AUC from per-score-bucket positive/negative counts.

    Rank-statistic identity: AUC = P(score_pos > score_neg) + 0.5 *
    P(tie).  Bucketed: each positive in bucket b beats every negative in
    buckets < b and half-ties the negatives in bucket b.  Exact for scores
    quantized to the bucket grid; O(1/n_bins) bias otherwise — identical to
    TF's thresholded streaming AUC.  Degenerate sets (no positives or no
    negatives) return 0.5.
    """
    import numpy as np

    pos = np.asarray(pos, np.float64)
    neg = np.asarray(neg, np.float64)
    p, n = pos.sum(), neg.sum()
    if p <= 0 or n <= 0:
        return 0.5
    neg_below = np.concatenate([[0.0], np.cumsum(neg)[:-1]])
    wins = float(np.sum(pos * (neg_below + 0.5 * neg)))
    # Plain python float: np.float64 leaks would crash json.dumps on the
    # gRPC JobStatus / metrics-report paths.
    return float(wins / (p * n))


def finalize_metrics(metrics: Dict) -> Dict[str, float]:
    """Scalar-ize a metrics dict: plain entries -> float, histogram pairs ->
    their derived scalar ("auc"), raw histogram vectors dropped."""
    out: Dict[str, float] = {}
    for k, v in metrics.items():
        if not k.startswith(HIST_PREFIX):
            out[k] = float(v)
    if AUC_POS in metrics and AUC_NEG in metrics:
        out["auc"] = auc_from_histograms(metrics[AUC_POS], metrics[AUC_NEG])
    return out


class _ThreadPhases:
    """One thread's state in a ``PhaseTimers``: its nesting stack and the
    phase it is in.  Written by that thread alone."""

    __slots__ = ("stack", "open")

    def __init__(self):
        self.stack: list = []
        #: (name, perf_counter at entry, task) of the innermost open phase.
        self.open: Optional[tuple] = None


class PhaseTimers:
    """Cumulative wall-clock per named worker task-loop phase.

    The gap between a full job's throughput and the ingest bench's was
    guessed at until these timers: the worker decomposes its task wall into
    named phases so the gap is attributable instead of folklore.
    Phase names used by the worker loop:

    - ``prep_wait``   blocked on host ingest (bulk read + decode + stack, or
                      the prep-ahead future when pipelined)
    - ``dispatch``    issuing device work (H2D transfer + step/scan dispatch;
                      includes the first task's XLA compile)
    - ``step_wait``   draining device execution at the deferred metrics fetch
    - ``metrics``     host-side metric aggregation + the report RPC
    - ``checkpoint``  task-loop boundary cost of periodic checkpoints
                      (snapshot dispatch + in-flight-save joins + final save)
    - ``control``     task-boundary control-plane overhead (heartbeat +
                      membership checks; the lease RPC nests under it and
                      keeps only its own time)
    - ``lease_wait``  the task-lease RPC itself (GetTask/GetGroupTask) —
                      with batched leases (r9) this fires once per batch,
                      so its per-task share is the lease amortization win
    - ``checkpoint_bg``  background checkpoint write + commit-barrier time —
                      OFF the critical path, excluded from wall sums
    - ``decode_parallel``  cumulative ingest-pool thread time in parallel
                      chunk read+decode (r9) — runs CONCURRENTLY with the
                      foreground phases (and with itself, across threads),
                      so it is off the critical path like ``checkpoint_bg``;
                      compare it against ``prep_wait`` to see how much
                      decode the pool hid

    The snapshot rides every ReportTaskResult/ReportCheckpoint, so the
    master's view (JobStatus ``phase_times``) and the train-job artifact get
    the decomposition without a new RPC.  Cost per entry: two
    ``perf_counter`` calls and a locked dict add — noise next to any phase
    worth timing.

    Thread-safe: the background checkpoint thread records under its own key
    while the task loop records the foreground phases.

    Nested phases record SELF-time: a phase entered inside another phase
    (e.g. a membership change inside the ``control`` heartbeat draining a
    pipelined task through its dispatch/metrics/checkpoint phases)
    subtracts its wall from the enclosing phase, so each second of the
    task loop lands in exactly one bucket and the decomposition stays a
    partition of (bounded by) wall time.  The nesting stack is per-thread
    — a background phase never subtracts from a foreground one.
    """

    def __init__(self, gauges=None):
        self._lock = locksan.lock("PhaseTimers._lock", leaf=True)  # lock-order: leaf
        self._seconds: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._local = threading.local()
        # The per-thread state (``_ThreadPhases``) of the ONE thread that
        # called ``watch_this_thread`` (the worker's task loop, once), for
        # a reader on another thread: see ``watched_open``.
        self._watched: Optional[_ThreadPhases] = None  # single-writer: main
        # graftgauge (r14): with a registry wired, every phase ENTRY also
        # observes into a per-phase duration histogram (shared log grid),
        # so a live scrape shows the phase tail SHAPE — the cumulative
        # seconds alone cannot tell "one 2 s stall" from "2000 stalls of
        # 1 ms".  Histogram handles are cached per phase name: the add()
        # path pays one dict lookup + an O(1) observe, not a registry
        # walk.
        self._gauges = gauges
        self._phase_hists: Dict[str, object] = {}  # guarded-by: _lock

    def _thread(self) -> "_ThreadPhases":
        """The calling thread's state, made at its first phase."""
        mine = getattr(self._local, "mine", None)
        if mine is None:
            mine = self._local.mine = _ThreadPhases()
        return mine

    @contextlib.contextmanager
    def phase(self, name: str, **attrs):
        """``attrs`` go to the phase's trace span only (the task id that
        ties spans of one task together across threads); the cumulative
        timers are keyed by ``name`` alone."""
        mine = getattr(self._local, "mine", None) or self._thread()
        stack = mine.stack
        child_wall = [0.0]
        stack.append(child_wall)
        # Every phase doubles as a trace span (category "phase") when the
        # process recorder is on: the cross-process trace view decomposes
        # by the SAME names as the cumulative timers, and the span's
        # independent self-time arithmetic is pinned against ours by tests.
        # Disabled, span() is a shared no-op — two attribute checks.
        sp = trace.span(name, cat="phase", **attrs)
        sp.__enter__()
        t0 = time.perf_counter()
        # What this thread is in, for a reader on another thread (one
        # store here, one on the way out, ring on or off, no lock).
        enclosing = mine.open
        mine.open = (name, t0, attrs.get("task"))
        try:
            yield
        finally:
            mine.open = enclosing
            elapsed = time.perf_counter() - t0
            sp.__exit__(None, None, None)
            stack.pop()
            if stack:
                # Report the full wall to the enclosing phase so IT can
                # subtract; this phase keeps only its self-time.
                stack[-1][0] += elapsed
            self.add(name, elapsed - child_wall[0])

    def watch_this_thread(self) -> None:
        """Publish the calling thread's open phase to ``watched_open``:
        the task loop calls this once, before its first phase."""
        self._watched = self._thread()

    def watched_open(self) -> Optional[tuple]:
        """``(name, perf_counter at entry, task)`` of the innermost phase
        the watched thread is in right now, or None (outside every phase,
        or no thread is watched).  Read from ANOTHER thread, racily by
        design: one attribute load of a tuple its writer replaces whole,
        so the reader sees a phase that was open a moment ago, never a
        torn one."""
        watched = self._watched
        return None if watched is None else watched.open

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._seconds[name] = self._seconds.get(name, 0.0) + seconds
            self._counts[name] = self._counts.get(name, 0) + 1
            hist = self._phase_hists.get(name)
        if self._gauges is None:
            return
        if hist is None:
            # Created OUTSIDE our leaf lock (the registry lookup takes
            # the registry's own leaf; nesting the two would break both
            # declarations).  Registry.histogram is idempotent, so a
            # racing creation converges on the same series.
            hist = self._gauges.histogram(
                "edl_phase_ms",
                "per-entry wall of each task-loop phase (self-time)",
                labels={"phase": name},
            )
            with self._lock:
                self._phase_hists[name] = hist
        hist.observe(seconds * 1e3)

    def snapshot(self) -> Dict[str, float]:
        """Cumulative seconds per phase (plain floats — JSON/RPC-safe)."""
        with self._lock:
            return {k: round(v, 6) for k, v in self._seconds.items()}

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


#: Phases that consume task-loop wall-clock (everything but the background
#: checkpoint write and the ingest pool's parallel decode time, which run
#: concurrently with the foreground phases).  Consumers summing a
#: decomposition against wall time must restrict to these.
CRITICAL_PATH_PHASES = (
    "prep_wait", "dispatch", "step_wait", "metrics", "checkpoint", "control",
    "lease_wait", "collective_gate",
)


def critical_path_seconds(phase_times: Dict[str, float]) -> float:
    """Sum of the wall-consuming phases of one worker's snapshot."""
    return float(
        sum(v for k, v in phase_times.items() if k in CRITICAL_PATH_PHASES)
    )


#: The TensorBoard mirror's file under ``<metrics_dir>/tensorboard/``:
#: ``<prefix>.<epoch seconds>.<host>.<pid>`` (TensorBoard takes any name
#: that holds ``tfevents``).  Advisory like the JSONL stream, and for the
#: same reason: one unbuffered append a scalar, never fsync'd; a reader
#: (TensorBoard's loader) stops at a torn final record.
EVENTS_PREFIX = "events.out.tfevents"  # durable-file


def _crc32c_table() -> tuple:
    table = []
    for byte in range(256):
        crc = byte
        for _ in range(8):
            crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
        table.append(crc)
    return tuple(table)


#: CRC-32C (Castagnoli, reflected polynomial 0x82F63B78), one byte a step.
_CRC32C = _crc32c_table()


def _masked_crc32c(data: bytes) -> bytes:
    """TFRecord's checksum of ``data``: CRC-32C, rotated right by 15 bits
    plus a constant (so a record that holds records keeps a usable CRC),
    little-endian."""
    crc = 0xFFFFFFFF
    for byte in data:
        crc = _CRC32C[(crc ^ byte) & 0xFF] ^ (crc >> 8)
    crc ^= 0xFFFFFFFF
    return struct.pack("<I", (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF)


def _varint(n: int) -> bytes:
    n &= 0xFFFFFFFFFFFFFFFF  # int64 on the wire: a negative takes ten bytes
    out = bytearray()
    while n > 0x7F:
        out.append(0x80 | (n & 0x7F))
        n >>= 7
    out.append(n)
    return bytes(out)


def _delimited(field: int, payload: bytes) -> bytes:
    """A length-delimited protobuf field: a string or a nested message."""
    return bytes([field << 3 | 2]) + _varint(len(payload)) + payload


class _EventFile:
    """The one TensorBoard events file of a ``MetricsWriter``: TFRecord
    framing (length, masked CRC-32C of the length, payload, masked CRC-32C
    of the payload) around ``Event`` protobuf messages encoded by hand.
    The mirror needs two messages and five field kinds, all fixed by
    TensorBoard's ``event.proto`` / ``summary.proto``:

        Event{1: double wall_time, 2: int64 step, 3: string file_version}
        Event{1: wall_time, 2: step, 5: Summary{1: Value{1: string tag,
                                                         2: float simple_value}}}

    Not thread-safe on its own: every call runs under
    ``MetricsWriter._lock``.
    """

    def __init__(self, directory: str):
        os.makedirs(directory, exist_ok=True)
        now = time.time()
        path = os.path.join(
            directory,
            f"{EVENTS_PREFIX}.{int(now):010d}.{socket.gethostname()}.{os.getpid()}",
        )
        # Unbuffered: one ``write`` a record, so a record is with the OS
        # when ``add_scalar`` returns, as the JSONL line is after its flush.
        # graftlint: allow[durable-write-discipline] metrics are advisory: flush-only appends by contract (fsync per scalar would serialize report handlers on the disk); reader is torn-tolerant
        self._f = open(path, "ab", buffering=0)
        # A process's second writer inside one second appends to the first's file.
        if self._f.tell() == 0:
            self._record(self._event(now, 0, _delimited(3, b"brain.Event:2")))

    @staticmethod
    def _event(wall_time: float, step: int, body: bytes) -> bytes:
        return b"\x09" + struct.pack("<d", wall_time) + b"\x10" + _varint(step) + body

    def _record(self, payload: bytes) -> None:
        length = struct.pack("<Q", len(payload))
        self._f.write(
            length + _masked_crc32c(length) + payload + _masked_crc32c(payload)
        )

    def add_scalar(self, tag: str, value: float, step: int) -> None:
        try:
            simple_value = struct.pack("<f", value)
        except OverflowError:  # a diverged loss past float32's range reads inf
            simple_value = struct.pack("<f", math.copysign(math.inf, value))
        scalar = _delimited(1, tag.encode("utf-8")) + b"\x15" + simple_value
        summary = _delimited(1, scalar)
        self._record(self._event(time.time(), step, _delimited(5, summary)))

    def close(self) -> None:
        self._f.close()


class MetricsWriter:
    """Append-only JSONL scalar stream + optional TensorBoard mirror.

    One append handle for the stream's whole life (closed in ``close()``):
    the old open-per-record idiom paid an open/close syscall pair per
    report AND left a window where a crash mid-write tore the final line
    with no reader-side tolerance.  Crash-safe append now means what it
    says: each record is one ``write`` of a full line followed by a flush
    (the OS appends atomically for these sizes), and ``read_metrics``
    drops a torn FINAL line instead of raising.
    """

    def __init__(self, directory: str, tensorboard: bool = True):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._path = os.path.join(self.directory, METRICS_FILENAME)
        self._lock = locksan.lock("MetricsWriter._lock", leaf=True)  # lock-order: leaf
        # graftlint: allow[durable-write-discipline] metrics are advisory: buffered flush-only appends by contract (fsync per scalar would serialize report handlers on the disk); reader is torn-tolerant
        self._f = open(self._path, "a")  # guarded-by: _lock
        self._tb = (
            _EventFile(os.path.join(self.directory, "tensorboard"))
            if tensorboard
            else None
        )

    def write(
        self, kind: str, step: int, metrics: Dict[str, float],
        tensorboard: bool = True,
        text: Optional[Dict] = None,
    ) -> None:
        """Record one scalar group: kind is "train" | "eval" | custom.
        ``tensorboard=False`` keeps the group out of the TensorBoard
        mirror (0.14 ms per five scalars on the chip machine's host, five
        unbuffered appends, beside 0.02 ms for the JSONL line: PERF.md
        section 6, PR 43; on a report handler's path).  ``text`` holds what
        of the group is no scalar (a ``stall`` record's cause and stacks):
        JSON values, in the JSONL line as they are and never in the
        mirror."""
        record = {
            **(text or {}),
            "ts": time.time(),
            "kind": kind,
            "step": int(step),
            **{k: float(v) for k, v in metrics.items()},
        }
        line = json.dumps(record, sort_keys=True)
        with self._lock:
            if self._f is None:
                # A report racing close() (gRPC pool thread vs master
                # teardown) must not crash the handler: reopen for the
                # straggler record — append keeps the stream consistent.
                # graftlint: allow[durable-write-discipline] same advisory-append contract as the primary handle above
                self._f = open(self._path, "a")
            self._f.write(line + "\n")
            self._f.flush()
            if tensorboard and self._tb is not None:
                for key, value in metrics.items():
                    self._tb.add_scalar(f"{kind}/{key}", float(value), int(step))

    def close(self) -> None:
        with self._lock:
            if self._f is not None:
                self._f.close()
                self._f = None
            if self._tb is not None:
                self._tb.close()
                self._tb = None


# recovery-path
def read_metrics(directory: str) -> list:
    """All records of a job's metrics.jsonl (tests, CLI inspection).

    Tolerates a torn FINAL line — the one legal artifact of a crash mid-
    append — by dropping it; garbage anywhere earlier still raises (that is
    corruption, not a crash tail, and silently skipping it would hide it).
    The r12 stance, generalized: durable.read_wal is the one definition.
    """
    from elasticdl_tpu.common import durable

    path = os.path.join(os.path.abspath(directory), METRICS_FILENAME)
    if not os.path.exists(path):
        return []
    records, _torn = durable.read_wal(path)
    return records
