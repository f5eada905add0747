"""Process start-up helpers: which device answered, where compiles are cached.

Backend selection is JAX's own: it honours ``JAX_PLATFORMS``, and with the
variable unset it takes the accelerator when one initialises and otherwise
falls back to the CPU with a warning.  Nothing here overrides that choice;
:func:`device_summary` reports what actually answered, from inside the
process that runs the steps, so a job that landed on the host by accident
says so in its log and its result.

One process owns a chip at a time.  Importing jax or the framework opens no
backend; the first ``jax.devices()`` / computation does, and from then on
any other process that needs the same chip fails or hangs.  Control-plane
processes (master, bench drivers, ``chip_smoke.py``'s parent) therefore stay
off jax entirely, and the helpers below import it lazily.
"""

from __future__ import annotations

import errno
import os
import threading
import time

#: Fixed in-checkout compile-cache directory used when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset.  The path is part of the cache
#: key, so it must not move between runs: derived from this file's
#: location only — never ``$HOME``, a temp name, a pid or a time.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
#: The other durations jax reports of a compile request, by the key
#: :func:`compile_phase_seconds` gives each: tracing to a jaxpr, lowering
#: it to a module, reading a cached executable (a part of the backend
#: compile's seconds, reported on a hit only).
_PHASE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
}


def free_port() -> int:
    """An OS-assigned free TCP port (bind-port-0 probe) — for coordinator
    ports in single-machine multi-process harnesses, where a fixed default
    would collide across concurrent gangs.  Inherently racy (the port is
    released before the caller binds it); fine for tests/benches, real
    deployments configure the coordinator port explicitly.  Lives here
    (not parallel.distributed) so jax-free master/bench processes can
    allocate ports without importing jax."""
    import socket

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _CompileStats:
    """Process-wide persistent-cache counters, fed by ``jax.monitoring``
    (the cache itself is process-global jax config, so its counters are
    too).  ``functions`` maps each jitted function's name to how its LAST
    backend compile was served — ``hit`` (loaded from the cache directory)
    or ``miss`` (compiled fresh, then written) — and the seconds it took."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.installed = False
        self.hits = 0
        self.misses = 0
        self.compiles = 0
        self.compile_s = 0.0
        self.phase_s = dict.fromkeys(_PHASE_DURATIONS.values(), 0.0)
        self.last = ""
        self.functions: dict = {}

    def on_event(self, event: str, **_) -> None:
        if event not in (_CACHE_HIT, _CACHE_MISS):
            return
        with self.lock:
            if event == _CACHE_HIT:
                self.hits += 1
                self.last = "hit"
            else:
                self.misses += 1
                self.last = "miss"

    def on_duration(self, event: str, duration: float, **kw) -> None:
        phase = _PHASE_DURATIONS.get(event)
        if phase is not None:
            with self.lock:
                self.phase_s[phase] += duration
            return
        if event != _BACKEND_COMPILE:
            return
        with self.lock:
            self.compiles += 1
            self.compile_s += duration
            # The hit/miss event fires INSIDE this compile's span, so the
            # newest one is this function's; "" means the cache was not
            # consulted (disabled, or an uncacheable computation).
            self.functions[str(kw.get("fun_name", "?"))] = {
                "cache": self.last or "uncached",
                "s": round(duration, 3),
            }
            self.last = ""


_stats = _CompileStats()


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache and count its traffic.

    Elastic resizes, relaunches and repeat runs re-jit the train step for a
    (program, topology) pair the cache may already hold; a hit loads the
    executable from disk instead of paying the XLA compile again.

    Which cache serves what: this one is keyed by the LOWERED module, so a
    hit still pays the trace and the lowering that make the key.  It serves
    every program a process traces (init_state, eval, predict, serving, a
    first launch's train step).  A relaunched worker's TRAIN STEP is served
    by the program store instead (common/program_store.py, a sibling
    directory ``<this cache's directory>_programs``), with nothing traced.

    Placement rule: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
    itself and this function sets NO directory, so whoever launches the
    process decides where entries land; unset, every process of the
    checkout shares :data:`DEFAULT_COMPILE_CACHE_DIR`.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    # Cache even fast compiles: elastic resizes re-trace many small steps.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    count_compiles()


def count_compiles() -> None:
    """Start counting this process's backend compiles and cache traffic
    (``jax.monitoring`` listeners; idempotent).  Touches no jax config:
    every worker calls it, cache or no cache, so the compile counters that
    ride its task reports are true in any process."""
    import jax

    with _stats.lock:
        if _stats.installed:
            return
        _stats.installed = True
    jax.monitoring.register_event_listener(_stats.on_event)
    jax.monitoring.register_event_duration_secs_listener(_stats.on_duration)


def compile_counts() -> tuple:
    """(backend compiles, their seconds) of this process so far; zeros
    until :func:`count_compiles` has run.  jax-free to call."""
    with _stats.lock:
        return _stats.compiles, _stats.compile_s


def compile_phase_seconds() -> dict:
    """What this process's compile requests have cost so far, cumulative,
    as ``jax.monitoring`` reported it: seconds tracing (``trace_s``),
    lowering (``lower_s``), in backend compiles (``compile_s``: a cache
    hit's read is inside it, and apart as ``cache_load_s``), and the
    requests the persistent cache served (``cache_hits``) or did not
    (``cache_misses``).  jax-free to call; zeros until
    :func:`count_compiles` has run."""
    with _stats.lock:
        return dict(
            _stats.phase_s, compile_s=_stats.compile_s,
            cache_hits=float(_stats.hits), cache_misses=float(_stats.misses),
        )


def compile_cache_stats() -> dict:
    """The cache directory in use and this process's hits/misses so far
    (zeros until :func:`enable_compile_cache` has run)."""
    import jax

    with _stats.lock:
        return {
            "dir": jax.config.jax_compilation_cache_dir,
            "hits": _stats.hits,
            "misses": _stats.misses,
            "backend_compile_s": round(_stats.compile_s, 3),
            "functions": dict(_stats.functions),
        }


def device_bytes_in_use() -> list:
    """``memory_stats()["bytes_in_use"]`` of each local device, in
    ``jax.local_devices()`` order; None where the backend reports no
    stats (XLA:CPU)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        out.append(None if stats is None else int(stats["bytes_in_use"]))
    return out


def device_bytes_limit(devices) -> "int | None":
    """The smallest ``memory_stats()["bytes_limit"]`` among ``devices`` that
    this process addresses: what a program may occupy on a chip.  None where
    the backend reports no stats (XLA:CPU, a described device)."""
    import jax

    limits = []
    for d in devices:
        if d.process_index != jax.process_index():
            continue
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 - a compile-only device has no runtime to ask
            stats = None
        if not stats or not stats.get("bytes_limit"):
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits) if limits else None


def device_peak_bytes() -> int:
    """Peak device memory on the fullest local chip, in bytes:
    ``peak_bytes_in_use + peak_bytes_reserved`` of ``memory_stats()``.  The
    TPU runtime keeps a program's temporaries in a region it reserves at
    the bottom of memory, outside ``bytes_in_use``, so the peak is the two
    peaks together.  0 where the backend reports no stats (XLA:CPU)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            peak = max(
                peak,
                int(stats.get("peak_bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)),
            )
    return peak


#: Where a TPU host lists its chips: one numbered VFIO group a chip (v5e),
#: beside the container's own entry ``vfio``.
VFIO_DIR = "/dev/vfio"
#: How long a process about to open the TPU waits for chips that a process
#: just ended still holds.  The kernel gives a killed process's groups back
#: 3-4 s (one chip) to 8-10 s (four) after it has left ``/proc``
#: (PERF.md section 6, PR 32), and since PR 43 a relaunched worker reaches
#: its TPU open 7 s after its launch.
CHIPS_FREE_DEADLINE_S = 60.0


def _busy(group: str) -> bool:
    try:
        os.close(os.open(group, os.O_RDWR))
    except OSError as err:
        return err.errno == errno.EBUSY
    return False


def wait_for_chips(
    vfio_dir: str = VFIO_DIR, deadline_s: float = CHIPS_FREE_DEADLINE_S
) -> tuple:
    """Before the process's first touch of the backend: poll the numbered
    groups under ``vfio_dir`` until none refuses ``open`` with EBUSY, or
    until the deadline.  ``(seconds waited, groups still busy)``.

    A TPU client that finds a group busy does not wait: it fails the
    process (``TPU initialization failed: open(/dev/vfio/1): Device or
    resource busy``), and a worker relaunched on the host of a worker just
    killed finds exactly that for some seconds.  A host without the
    directory (the CPU, another TPU generation) waits for nothing, and a
    group that stays busy past the deadline is left to the client's own
    error.  Nor does a process told to stay off the TPU
    (``JAX_PLATFORMS`` set, without ``tpu``) wait for chips it will not
    open."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0.0, []
    try:
        groups = [
            os.path.join(vfio_dir, g)
            for g in sorted(os.listdir(vfio_dir))
            if g.isdigit()
        ]
    except OSError:
        return 0.0, []
    t0 = time.time()
    while True:
        groups = [g for g in groups if _busy(g)]
        waited = time.time() - t0
        if not groups or waited >= deadline_s:
            return waited, groups
        time.sleep(0.25)


def device_summary() -> dict:
    """What backend answered, as observed in THIS process.

    Opens the backend if nothing has yet (this is ``jax.devices()``), so
    only a process that owns the device may call it.  Every worker boot
    line, bench artifact and ``chip_smoke.py`` phase result carries this
    dict: a number without it cannot be told from a CPU run."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "local_count": jax.local_device_count(),
        "jax": jax.__version__,
    }
