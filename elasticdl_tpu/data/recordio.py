"""A minimal recordio-style container: the reference stores training data in
RecordIO files whose numbered records make range-sharding natural (SURVEY.md
§2 #14 [U]).  Format, per record:

    [uint32 payload_len][uint32 crc32(payload)][payload bytes]

little-endian, no compression.  Files carry a 8-byte magic header.  A sidecar
index is NOT required: ``RecordIOReader.index()`` scans once and caches record
offsets, so shard handout (record ranges) and ranged reads are O(1) after the
first scan.  A C++ scanner for the hot ingest path lives in
``elasticdl_tpu/ps/native`` (built lazily; this module is the pure-Python
fallback and the format's source of truth).
"""

from __future__ import annotations

import os
import struct
import time
import zlib
from collections import OrderedDict
from concurrent.futures import Future
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from elasticdl_tpu.common import gauge, locksan

MAGIC = b"EDLRIO\x00\x01"
_HDR = struct.Struct("<II")

#: Process-level offsets cache, keyed by ``(path, mtime_ns, size)``, for
#: every container (recordio here, text lines in data/reader.py): the e2e
#: worker re-opens the same file once per task (and, since r9, once per
#: parallel ingest chunk), and every fresh reader used to pay the full
#: index scan again.  Keying on mtime+size means an appended or rewritten
#: file can never serve a stale index — its old entry just ages out.
#: Bounded LRU; an index is immutable after insertion (readers only read
#: it), so sharing one across reader instances and threads is safe.
_INDEX_CACHE: "OrderedDict[Tuple[str, int, int], Sequence[int]]" = OrderedDict()
_INDEX_CACHE_MAX = 64
#: The scans in flight, by the same key: a miss is SINGLE-FLIGHT.  The first
#: thread to miss on a key scans; every other thread that wants that key
#: waits on the future for that one result.  A cold worker's first task
#: sends its whole ingest pool to one file at once, and Python scans of one
#: file running together under the GIL each take several times what one
#: alone takes.
_INDEX_SCANS: Dict[Tuple[str, int, int], Future] = {}
# Guards both tables.  Never held during a scan, so two DIFFERENT files
# index in parallel.
_index_cache_lock = locksan.lock("_index_cache_lock", leaf=True)  # lock-order: leaf


def _count(name: str, help_: str, container: str) -> None:
    gauge.default().counter(name, help_, labels={"container": container}).inc()


def shared_index(
    path: str, container: str, scan: Callable[[str], Sequence[int]]
) -> Sequence[int]:
    """``scan(path)``'s record offsets for the file as it is now, built at
    most once a process: from the cache, from the scan another thread is
    running, or from this thread's own.  ``gauge.default()`` counts the
    scans and the waits by ``container``
    (``edl_reader_index_builds_total``, ``edl_reader_index_waits_total``)
    and keeps the newest scan's ``edl_reader_index_last_build_seconds``.  A
    scan that raises is not cached: its waiters raise the same error and
    the next call scans again."""
    st = os.stat(path)
    key = (path, st.st_mtime_ns, st.st_size)
    with _index_cache_lock:
        cached = _INDEX_CACHE.get(key)
        if cached is not None:
            _INDEX_CACHE.move_to_end(key)
            return cached
        flight = _INDEX_SCANS.get(key)
        mine = flight is None
        if mine:
            flight = _INDEX_SCANS[key] = Future()
    if not mine:
        _count(
            "edl_reader_index_waits_total",
            "reads that waited for another thread's scan of the same file",
            container,
        )
        return flight.result()
    t0 = time.monotonic()
    try:
        offsets = scan(path)
    except BaseException as e:
        with _index_cache_lock:
            del _INDEX_SCANS[key]
        flight.set_exception(e)
        raise
    seconds = time.monotonic() - t0
    with _index_cache_lock:
        _INDEX_CACHE[key] = offsets
        while len(_INDEX_CACHE) > _INDEX_CACHE_MAX:
            _INDEX_CACHE.popitem(last=False)
        del _INDEX_SCANS[key]
    flight.set_result(offsets)
    _count(
        "edl_reader_index_builds_total",
        "record-index scans of a data file this process ran", container,
    )
    gauge.default().gauge(
        "edl_reader_index_last_build_seconds",
        "seconds the newest record-index scan took",
        labels={"container": container},
    ).set(seconds)
    return offsets


def _scan_record_offsets(path: str) -> List[int]:
    # A chain of length-prefixed headers: each offset needs the one before.
    offsets = []
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        pos = len(MAGIC)
        while pos < size:
            offsets.append(pos)
            f.seek(pos)
            length, _ = _HDR.unpack(f.read(_HDR.size))
            pos += _HDR.size + length
    return offsets


class RecordIOWriter:
    def __init__(self, path: str):
        self._f = open(path, "wb")
        self._f.write(MAGIC)
        self._count = 0

    def write(self, payload: bytes) -> None:
        self._f.write(_HDR.pack(len(payload), zlib.crc32(payload)))
        self._f.write(payload)
        self._count += 1

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self) -> "RecordIOWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def count(self) -> int:
        return self._count


class RecordIOReader:
    def __init__(self, path: str):
        self.path = path
        self._offsets: Optional[List[int]] = None
        with open(path, "rb") as f:
            if f.read(len(MAGIC)) != MAGIC:
                raise ValueError(f"{path}: not a recordio file")

    def index(self) -> List[int]:
        """Byte offset of each record: one scan a process for the file as it
        is (``shared_index``: sub-chunk readers, per-task reader instances
        and threads that miss together all get that one scan's list)."""
        if self._offsets is None:
            self._offsets = shared_index(
                self.path, "recordio", _scan_record_offsets
            )
        return self._offsets

    def __len__(self) -> int:
        return len(self.index())

    def read_range(self, start: int, end: int) -> Iterator[bytes]:
        """Yield records [start, end) by record index, CRC-checked."""
        offsets = self.index()
        end = min(end, len(offsets))
        if start >= end:
            return
        with open(self.path, "rb") as f:
            f.seek(offsets[start])
            for _ in range(end - start):
                length, crc = _HDR.unpack(f.read(_HDR.size))
                payload = f.read(length)
                if zlib.crc32(payload) != crc:
                    raise IOError(f"{self.path}: CRC mismatch")
                yield payload

    def read_range_packed(self, start: int, end: int):
        """Records [start, end) as one PackedRecords (bulk C++ read + CRC on
        the ingest hot path; Python fallback when the native lib is absent).
        See data/packed.py for why the hot path avoids per-record objects."""
        from elasticdl_tpu.data.packed import PackedRecords

        offsets = self.index()
        end = min(end, len(offsets))
        if start >= end:
            return PackedRecords(
                np.empty((0,), np.uint8), np.zeros((1,), np.int64)
            )
        try:
            from elasticdl_tpu.ps.host_store import recordio_read_native

            buf, cum = recordio_read_native(
                self.path,
                np.asarray(offsets, np.int64),
                start,
                end,
                os.path.getsize(self.path),
            )
            return PackedRecords(buf, cum)
        except (RuntimeError, ImportError):
            return PackedRecords.from_records(list(self.read_range(start, end)))


def write_records(path: str, records: Sequence[bytes]) -> int:
    with RecordIOWriter(path) as w:
        for r in records:
            w.write(r)
        return w.count
