"""In-process probe of the job's worker, loaded through PYTHONPATH by the
benchmark's launcher only (``EDL_BENCH_PROBE_DIR`` set).

The program logs device bytes in use once, after init, and reports its
compile counts only in a final result that a job stopped at the window's
end never prints.  The contract wants the PEAK on the fullest chip and no
compile inside the window, and only the process that holds the chip can
read either.  So one daemon thread waits for ``<dir>/request.<n>`` files
and answers each with ``<dir>/answer.<n>.<pid>.json``: every local
device's ``memory_stats()`` and the count and seconds of XLA backend
compiles seen so far (``jax.monitoring``).  It never opens a backend
itself: a process that has not initialised jax (the master) answers
nothing.  PERF.md lists the program counters that will replace it.
"""

import os


def _start(probe_dir: str) -> None:
    import json
    import sys
    import threading
    import time

    compiles = {"count": 0, "seconds": 0.0, "listening": False}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["count"] += 1
            compiles["seconds"] += float(duration)

    def backend_ready():
        bridge = sys.modules.get("jax._src.xla_bridge")
        return bool(bridge is not None and getattr(bridge, "_backends", None))

    def loop():
        answered = set()
        while True:
            time.sleep(0.2)
            if not backend_ready():
                continue
            import jax

            if not compiles["listening"]:
                jax.monitoring.register_event_duration_secs_listener(on_duration)
                compiles["listening"] = True
            try:
                names = os.listdir(probe_dir)
            except OSError:
                continue
            for name in names:
                if not name.startswith("request.") or name in answered:
                    continue
                answered.add(name)
                stats = [d.memory_stats() for d in jax.local_devices()]
                answer = {
                    "pid": os.getpid(),
                    "time": time.time(),
                    "memory_stats": stats,
                    "compiles": dict(compiles),
                }
                final = os.path.join(
                    probe_dir, f"answer.{name.split('.', 1)[1]}.{os.getpid()}.json"
                )
                with open(final + ".tmp", "w") as f:
                    json.dump(answer, f)
                os.replace(final + ".tmp", final)

    threading.Thread(target=loop, name="edl-bench-probe", daemon=True).start()


if os.environ.get("EDL_BENCH_PROBE_DIR"):
    _start(os.environ["EDL_BENCH_PROBE_DIR"])
