"""ctypes bindings for the native host embedding store (ps/native/).

The shared library is built on first use (g++ is in the image; no pybind11,
per environment constraints).  All APIs take/return numpy arrays; ids are
int64, rows float32.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from elasticdl_tpu.common import locksan
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("ps.host_store")

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libedl_native.so")
_OPTIMIZERS = {"sgd": 0, "momentum": 1, "adagrad": 2, "adam": 3}

_lib_lock = locksan.lock("_lib_lock", leaf=True)  # lock-order: leaf
_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None

_i64 = ctypes.c_int64
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")


def _build() -> None:
    subprocess.run(
        ["make", "-s", "-C", _NATIVE_DIR],
        check=True,
        capture_output=True,
        text=True,
    )


def _load() -> ctypes.CDLL:
    global _lib, _lib_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise RuntimeError(_lib_error)
        try:
            src = os.path.join(_NATIVE_DIR, "edl_native.cc")
            if not os.path.exists(_LIB_PATH) or os.path.getmtime(
                _LIB_PATH
            ) < os.path.getmtime(src):
                _build()
            try:
                lib = ctypes.CDLL(_LIB_PATH)
            except OSError:
                # A stale/foreign-arch binary that is newer than the source
                # still can't load — rebuild once from source and retry.
                _build()
                lib = ctypes.CDLL(_LIB_PATH)
        except (subprocess.CalledProcessError, OSError) as e:
            _lib_error = f"native lib unavailable: {e}"
            # Said ONCE, as an error: every ingest hot path (criteo/census
            # decode, bulk recordio reads, host stores) degrades to Python
            # fallbacks that are ~80x slower (CPU harness; docs/perf.md)
            # and the job still exits 0 — a profile-invisible collapse
            # unless it is logged.  Subsequent calls fail fast on the
            # cached error without re-logging; chip_smoke.py refuses a
            # worker whose boot line says the library is missing.
            logger.error(
                "%s — ingest/PS hot paths fall back to Python "
                "implementations (~80x slower decode; see docs/perf.md)",
                _lib_error,
            )
            raise RuntimeError(_lib_error) from e

        lib.edl_store_create.restype = ctypes.c_void_p
        lib.edl_store_create.argtypes = [
            _i64, ctypes.c_int,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_float, ctypes.c_float,
        ]
        lib.edl_store_destroy.argtypes = [ctypes.c_void_p]
        lib.edl_store_size.restype = _i64
        lib.edl_store_size.argtypes = [ctypes.c_void_p]
        lib.edl_store_pull.argtypes = [ctypes.c_void_p, _i64p, _i64, _f32p]
        lib.edl_store_try_pull.restype = _i64
        lib.edl_store_try_pull.argtypes = [ctypes.c_void_p, _i64p, _i64, _f32p]
        lib.edl_store_push_grad.argtypes = [ctypes.c_void_p, _i64p, _i64, _f32p]
        lib.edl_store_save.restype = _i64
        lib.edl_store_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.edl_store_load.restype = _i64
        lib.edl_store_load.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.edl_recordio_index.restype = _i64
        lib.edl_recordio_index.argtypes = [ctypes.c_char_p, _i64p, _i64]
        lib.edl_recordio_verify.restype = _i64
        lib.edl_recordio_verify.argtypes = [ctypes.c_char_p, _i64p, _i64, _i64]
        lib.edl_recordio_read.restype = _i64
        lib.edl_recordio_read.argtypes = [
            ctypes.c_char_p, _i64p, _i64, _i64, _i64, _u8p, _i64, _i64p,
        ]
        lib.edl_criteo_decode.restype = _i64
        lib.edl_criteo_decode.argtypes = [_u8p, _i64p, _i64, _i32p, _f32p, _i32p]
        lib.edl_criteo_decode_pre.restype = _i64
        lib.edl_criteo_decode_pre.argtypes = [
            _u8p, _i64p, _i64, _u8p, _u16p, _u16p, _i64,
        ]
        lib.edl_census_decode.restype = _i64
        lib.edl_census_decode.argtypes = [
            _u8p, _i64p, _i64, _i32p, _f32p, _i32p, _i64,
        ]
        _lib = lib
        return lib


def native_lib_available() -> bool:
    try:
        _load()
        return True
    except RuntimeError:
        return False


class HostEmbeddingStore:
    """Growable id->row store with server-side sparse optimizers.

    The host tier of the ParameterServer strategy: rows materialize on first
    pull (deterministic per-id init), ``push_grad`` applies one optimizer
    step per distinct id with duplicate contributions pre-accumulated
    (IndexedSlices semantics — same contract the mesh-sharded path's AD
    transpose provides on-device).
    """

    def __init__(
        self,
        dim: int,
        optimizer: str = "adagrad",
        learning_rate: float = 0.01,
        momentum: float = 0.9,
        beta1: float = 0.9,
        beta2: float = 0.999,
        eps: float = 1e-8,
        init_scale: float = 0.05,
    ):
        if optimizer not in _OPTIMIZERS:
            raise ValueError(
                f"unknown optimizer {optimizer!r}, pick from {sorted(_OPTIMIZERS)}"
            )
        self._lib = _load()
        self.dim = dim
        self.optimizer = optimizer
        self._ptr = self._lib.edl_store_create(
            dim, _OPTIMIZERS[optimizer],
            learning_rate, momentum, beta1, beta2, eps, init_scale,
        )

    def __len__(self) -> int:
        return int(self._lib.edl_store_size(self._ptr))

    def pull(self, ids: np.ndarray) -> np.ndarray:
        ids = np.ascontiguousarray(ids, np.int64)
        out = np.empty((ids.size, self.dim), np.float32)
        self._lib.edl_store_pull(self._ptr, ids.ravel(), ids.size, out)
        return out.reshape(ids.shape + (self.dim,))

    def try_pull(self, ids: np.ndarray):
        """Read-only gather: (rows, n_missing).  Safe to run concurrently
        with other readers (NOT with push/pull/load); the PS service uses it
        as the shared-lock fast path and falls back to the exclusive
        ``pull`` when ids are missing."""
        ids = np.ascontiguousarray(ids, np.int64)
        out = np.empty((ids.size, self.dim), np.float32)
        missing = int(
            self._lib.edl_store_try_pull(self._ptr, ids.ravel(), ids.size, out)
        )
        return out.reshape(ids.shape + (self.dim,)), missing

    def push_grad(self, ids: np.ndarray, grads: np.ndarray) -> None:
        ids = np.ascontiguousarray(ids, np.int64).ravel()
        grads = np.ascontiguousarray(grads, np.float32).reshape(ids.size, self.dim)
        self._lib.edl_store_push_grad(self._ptr, ids, ids.size, grads)

    def save(self, path: str) -> int:
        n = int(self._lib.edl_store_save(self._ptr, path.encode()))
        if n < 0:
            raise IOError(f"save to {path} failed")
        return n

    def load(self, path: str) -> int:
        n = int(self._lib.edl_store_load(self._ptr, path.encode()))
        if n == -2:
            raise ValueError("checkpoint optimizer/dim mismatch")
        if n < 0:
            raise IOError(f"load from {path} failed")
        return n

    def close(self) -> None:
        if self._ptr:
            self._lib.edl_store_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def recordio_index_native(path: str) -> np.ndarray:
    """Native recordio offset scan (data/recordio.py's fast path)."""
    lib = _load()
    # Every record costs at least its 8-byte header, so file_size/8 is a hard
    # bound on the record count — but allocating that many int64s up front
    # would cost as much memory as the file itself.  Start from a typical
    # record-count guess and grow on the scanner's -2 (capacity) signal.
    hard_bound = max(os.path.getsize(path) // 8, 1)
    cap = min(hard_bound, 1 << 20)
    while True:
        offsets = np.empty((cap,), np.int64)
        n = int(lib.edl_recordio_index(path.encode(), offsets, cap))
        if n == -2:
            if cap >= hard_bound:
                raise IOError(f"{path}: more records than the size bound allows")
            cap = min(cap * 16, hard_bound)
            continue
        if n < 0:
            raise IOError(f"{path}: malformed recordio")
        return offsets[:n].copy()


def recordio_verify_native(path: str, offsets: np.ndarray, start: int, end: int) -> int:
    lib = _load()
    offsets = np.ascontiguousarray(offsets, np.int64)
    return int(lib.edl_recordio_verify(path.encode(), offsets, start, end))


def recordio_read_native(
    path: str, offsets: np.ndarray, start: int, end: int, file_size: int
) -> tuple:
    """Bulk CRC-checked range read: one disk read + in-memory header walk.

    Returns (payloads: uint8[total], cumulative_offsets: int64[n+1]) — the
    packed form data.packed.PackedRecords wraps.  The ingest hot path
    (SURVEY.md §2 #14: the reference's tf.data C++ pipeline role).
    """
    lib = _load()
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = end - start
    if n <= 0:
        return np.empty((0,), np.uint8), np.zeros((1,), np.int64)
    span = (int(offsets[end]) if end < len(offsets) else file_size) - int(
        offsets[start]
    )
    out = np.empty((span - 8 * n,), np.uint8)
    lens = np.empty((n,), np.int64)
    got = int(
        lib.edl_recordio_read(
            path.encode(), offsets, start, end, span, out, len(out), lens
        )
    )
    if got == -2:
        raise IOError(f"{path}: CRC mismatch in records [{start}, {end})")
    if got < 0:
        raise IOError(f"{path}: malformed recordio in records [{start}, {end})")
    cum = np.empty((n + 1,), np.int64)
    cum[0] = 0
    np.cumsum(lens, out=cum[1:])
    return out[:got], cum


def criteo_decode_native(buf: np.ndarray, offsets: np.ndarray) -> tuple:
    """Decode n packed criteo TSV records -> (labels[n], dense[n,13], cat[n,26]).

    ``offsets`` is cumulative (n+1 entries) into ``buf``; blanks and missing
    trailing fields decode to 0 exactly like the Python feed in
    data/codecs.py (the format's source of truth, numerics-tested against it).
    """
    lib = _load()
    buf = np.ascontiguousarray(buf, np.uint8)
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = len(offsets) - 1
    labels = np.zeros((n,), np.int32)
    dense = np.zeros((n, 13), np.float32)
    cat = np.zeros((n, 26), np.int32)
    rc = int(lib.edl_criteo_decode(buf, offsets, n, labels, dense, cat))
    if rc < 0:
        i = -rc - 1
        bad = bytes(buf[offsets[i] : offsets[i + 1]])
        raise ValueError(f"malformed criteo record {i}: {bad[:120]!r}")
    return labels, dense, cat


def census_decode_native(
    buf: np.ndarray, offsets: np.ndarray, hash_bins: int
) -> tuple:
    """Census CSV decode -> (labels[n], dense[n,5] f32, cat[n,9] i32).

    Numerics follow preprocessing.ToNumber (strip; empty/invalid -> 0.0);
    strings follow preprocessing.Hashing (crc32 % hash_bins) — equality with
    the Python feed pinned by tests/test_data.py."""
    lib = _load()
    buf = np.ascontiguousarray(buf, np.uint8)
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = len(offsets) - 1
    labels = np.zeros((n,), np.int32)
    dense = np.zeros((n, 5), np.float32)
    cat = np.zeros((n, 9), np.int32)
    rc = int(
        lib.edl_census_decode(buf, offsets, n, labels, dense, cat, hash_bins)
    )
    if rc < 0:
        i = -rc - 1
        bad = bytes(buf[offsets[i] : offsets[i + 1]])
        raise ValueError(f"malformed census record {i}: {bad[:120]!r}")
    return labels, dense, cat


def criteo_decode_pre_native(
    buf: np.ndarray, offsets: np.ndarray, buckets: int
) -> tuple:
    """Preprocessed criteo decode: the model's host-side feature transforms
    (models/tabular.py hash_buckets + log_normalize) applied DURING the
    parse, emitting compact wire types — labels uint8, dense float16
    (log1p), cat uint16 in [0, buckets).  79 B/example vs the raw decode's
    160 B: the host->device link was the e2e bottleneck on the retired
    backend's remote-attached chip (docs/perf.md).  Requires buckets <=
    65536."""
    lib = _load()
    buf = np.ascontiguousarray(buf, np.uint8)
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = len(offsets) - 1
    labels = np.zeros((n,), np.uint8)
    dense = np.zeros((n, 13), np.uint16)
    cat = np.zeros((n, 26), np.uint16)
    rc = int(
        lib.edl_criteo_decode_pre(buf, offsets, n, labels, dense, cat, buckets)
    )
    if rc == -(n + 1):
        raise ValueError(f"buckets={buckets} out of range for uint16 decode")
    if rc < 0:
        i = -rc - 1
        bad = bytes(buf[offsets[i] : offsets[i + 1]])
        raise ValueError(f"malformed criteo record {i}: {bad[:120]!r}")
    return labels, dense.view(np.float16), cat
