"""The program store: a worker's compiled train step, kept on disk beside the
compile cache, so that a RELAUNCHED worker loads it instead of tracing it.

jax's persistent compilation cache is keyed by the lowered module: to find
its entry a process has to trace the model and lower every kernel again,
which is most of a warm launch of a large model (16 to 24 s of Python in
the large LM cells, PERF.md section 6, PR 57).  A relaunch needs less: the
SAME program for the same job on the same layout.  This store keys the
compiled step (``jax.stages.Compiled``, through
``jax.experimental.serialize_executable``) by a digest of everything the
program depends on, taken BEFORE any trace; a hit skips trace and lowering
and costs the executable's read.

Who uses it: the worker process alone (``worker/main.py`` hands one to its
``Worker``, which hands it to its ``Trainer``).  Any other ``Trainer`` has
none and traces as it always did: a source digest cannot see a test's or a
benchmark control's monkeypatch.

What an entry holds: the serialized executable with its argument and result
trees, and what the trace left on the HOST beside the callable (``host``:
the trainer's keep plan, the lines the trace logged).  What invalidates one
is its key (:func:`key_of` over the parts its owner names, with
:func:`environment` and :func:`source_digest` among them); what removes one
is a failure to read, load or first-call it, or :data:`MAX_ENTRIES`.

The directory is a SIBLING of the compile cache's (``<cache>_programs``),
never inside it: jax evicts in there by its own account of the files.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from elasticdl_tpu.common import durable
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("common.program_store")

#: Entries the directory holds; past it the least recently used go (a hit
#: touches its file).  An entry of a large LM cell is 100 to 250 MB.
MAX_ENTRIES = 16
#: A part of every key: a change of what an entry holds makes the older
#: ones miss.
FORMAT = 1
_SUFFIX = ".program"
#: A writer killed mid-write leaves its temporary file: swept when older.
_STALE_TMP_S = 600.0

#: The package whose sources every key digests.
PACKAGE_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def source_digest(root: str = "", also: Tuple[str, ...] = ()) -> str:
    """sha256 over every ``.py`` under ``root`` (the package, by default)
    and the files ``also`` names, path and content.  Not cached: a key is
    made once or twice a process, and 1.7 MB hash in milliseconds."""
    root = root or PACKAGE_ROOT
    h = hashlib.sha256()
    paths = []
    for directory, _, files in os.walk(root):
        paths += [os.path.join(directory, f) for f in files if f.endswith(".py")]
    for path in sorted(paths) + sorted(p for p in also if p):
        h.update(os.path.relpath(path, root).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    return h.hexdigest()


def environment(client: Any = None) -> Dict[str, str]:
    """What of the process, outside the job's own configuration, a traced
    and compiled program depends on: the versions of jax, jaxlib, libtpu and
    the backend's own (``platform_version`` names the libtpu build), the
    flags XLA and libtpu read from the environment, and every jax setting
    that keys jax's own trace cache."""
    import importlib.metadata

    import jax
    import jaxlib
    from jax._src import config as jax_config

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = ""
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "backend": "" if client is None else f"{client.platform} {client.platform_version}",
        "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
        "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
        "jax_settings": repr(jax_config.trace_context()),
    }


def key_of(parts: Dict[str, Any]) -> str:
    """The digest of ``parts``: a JSON text of them with sorted keys, what
    JSON cannot say by its ``repr``."""
    text = json.dumps(dict(parts, format=FORMAT), sort_keys=True, default=repr)
    return hashlib.sha256(text.encode()).hexdigest()


class ProgramStore:
    """Compiled programs under ``directory``, one file a key.

    ``restore`` and ``save_later`` never raise: whatever goes wrong is
    logged, counted (``failed``) and answered with "not there", and the
    caller takes the path it would take without a store."""

    def __init__(self, directory: str):
        self.directory = directory
        self._lock = threading.Lock()  # lock-order: leaf
        self.failed = 0  # guarded-by: _lock
        self.written = 0  # guarded-by: _lock
        #: seconds its owner spent on it before a first dispatch: the keys'
        #: digests and ``restore`` calls, hits and misses (``spent``)
        self.restore_s = 0.0  # guarded-by: _lock
        self._writers: List[threading.Thread] = []  # guarded-by: _lock

    @classmethod
    def beside_compile_cache(cls) -> "ProgramStore":
        """The worker's store: ``<compile cache directory>_programs``, the
        directory ``enable_compile_cache`` settled on (call that first)."""
        import jax

        from elasticdl_tpu.common.platform import DEFAULT_COMPILE_CACHE_DIR

        cache = jax.config.jax_compilation_cache_dir or DEFAULT_COMPILE_CACHE_DIR
        return cls(os.path.normpath(cache) + "_programs")

    def path(self, key: str) -> str:
        return os.path.join(self.directory, key + _SUFFIX)

    def counts(self) -> Dict[str, float]:
        with self._lock:
            return {
                "failed": float(self.failed), "written": float(self.written), "restore_s": self.restore_s,
            }

    def spent(self, seconds: float) -> None:
        """Seconds its owner spent making a key and calling ``restore``."""
        with self._lock:
            self.restore_s += seconds

    def discard(self, key: str, why: str) -> None:
        """An entry that could not be used: said once, counted, removed."""
        with self._lock:
            self.failed += 1
        logger.warning("stored program %s... is dropped and its step traced instead: %s", key[:12], why)
        try:
            os.remove(self.path(key))
        except OSError:
            pass

    def restore(self, key: str, devices: List[Any]) -> Optional[Tuple[Any, Dict[str, Any]]]:
        """``(the loaded jax.stages.Compiled, its host side)`` of ``key``
        to run on ``devices`` (the mesh's, in its order), or None where
        there is no usable entry."""
        path = self.path(key)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
        try:
            from jax.experimental import serialize_executable

            entry = pickle.loads(raw)
            if entry["key"] != key:
                raise ValueError(f"the entry of another key, {str(entry['key'])[:12]}...")
            compiled = serialize_executable.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                backend=devices[0].client, execution_devices=devices,
            )
        except Exception as e:  # noqa: BLE001 - a truncated file, another libtpu's executable, a moved class: all one answer
            self.discard(key, f"it did not load ({type(e).__name__}: {str(e)[:200]})")
            return None
        try:
            os.utime(path)  # last use, for the bound
        except OSError:
            pass
        logger.info(
            "restored the compiled program %s... (%.1f MB) from %s: nothing is traced",
            key[:12], len(raw) / 1e6, self.directory,
        )
        return compiled, entry["host"]

    def save_later(self, key: str, compiled: Any, host: Dict[str, Any]) -> None:
        """Serialize ``compiled`` and write it under ``key`` on a thread of
        its own: the caller (a launch's first dispatch) waits for nothing."""
        writer = threading.Thread(
            target=self._save, args=(key, compiled, host), name="edl-program-store", daemon=True
        )
        with self._lock:
            self._writers.append(writer)
        writer.start()

    def settle(self, timeout_s: float = 60.0) -> None:
        """Wait for the writes begun so far (a job's end, a test)."""
        with self._lock:
            writers, self._writers = self._writers, []
        for writer in writers:
            writer.join(timeout=timeout_s)

    def _save(self, key: str, compiled: Any, host: Dict[str, Any]) -> None:
        try:
            from jax.experimental import serialize_executable

            t0 = time.monotonic()
            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            raw = pickle.dumps(
                {"key": key, "payload": payload, "in_tree": in_tree, "out_tree": out_tree, "host": host},
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            durable.atomic_publish(self.path(key), raw)
            self._evict()
        except Exception as e:  # noqa: BLE001 - a program jax cannot serialize, a full disk: the next launch traces
            with self._lock:
                self.failed += 1
            logger.warning("the compiled program %s... was not stored: %s: %s", key[:12], type(e).__name__, str(e)[:200])
            return
        with self._lock:
            self.written += 1
        logger.info(
            "stored the compiled program %s... (%.1f MB, %.2f s off the task loop) in %s",
            key[:12], len(raw) / 1e6, time.monotonic() - t0, self.directory,
        )

    def _evict(self) -> None:
        """Hold the directory to :data:`MAX_ENTRIES` by last use, and sweep
        what a killed writer left."""
        now, entries = time.time(), []
        for name in os.listdir(self.directory):
            path = os.path.join(self.directory, name)
            try:
                mtime = os.stat(path).st_mtime
            except OSError:
                continue
            if name.endswith(_SUFFIX):
                entries.append((mtime, path))
            elif now - mtime > _STALE_TMP_S:
                _remove(path)
        for _, path in sorted(entries)[: max(len(entries) - MAX_ENTRIES, 0)]:
            _remove(path)


def _remove(path: str) -> None:
    try:
        os.remove(path)
    except OSError:
        pass
