"""Test harness: 8 fake CPU devices, mirroring the reference's no-cluster test
strategy (mock k8s + in-process master/worker — SURVEY.md §4) with JAX's
equivalent: XLA host-platform device multiplexing.

Must set the env vars BEFORE jax initializes its backends, hence this module
does it at import time (conftest is imported before any test module).
"""

import os
import shutil
import tempfile

# Tests run on 8 fake CPU devices whatever the machine holds: force (not
# setdefault) the platform and the device count before jax reads them.
# Subprocess workers inherit both.  And XLA:CPU generates its code at LLVM's
# level 1, not 3: tier-1's programs are stand-ins that run once at toy sizes,
# so two thirds of a compile-heavy module's seconds are the compiler's
# (PR 51: nine such modules 325 -> 247 s, the trainers' jobs 56 -> 57; level 0
# makes those five times slower).  The HLO passes are the default's and
# every pinned digest and bit-equality of the suite holds; LLVM orders some
# sums differently, and the one case that sits on its tolerance asks for
# ``default_compile_level`` below.  libtpu does not read the level
# (tests/test_chip_lowering.py holds that).
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8 --xla_backend_optimization_level=1"
)
os.environ["JAX_PLATFORMS"] = "cpu"

# ONE compile cache a run, in a throwaway directory, never the in-checkout
# default (common/platform.DEFAULT_COMPILE_CACHE_DIR: the chip tool copies the
# tree as it is on disk, and thousands of XLA:CPU entries have no business
# riding along) and never a developer's own.  The first process of a run
# makes the directory, exports it and removes it when its session ends; a
# process that inherits one of this rule's making (an xdist worker, a child
# pytest on a copy of the tree, a job's subprocess) keeps it and leaves it to
# its maker, so what one compiled the others read (jax writes an entry under
# a temporary name and renames it: concurrent writers are safe).  Set before
# jax is imported (it reads the variable at import).
_JAX_CACHE_PREFIX = "edl_tier1_jax_cache_"
_JAX_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
_MADE_JAX_CACHE_DIR = not (
    os.path.basename(_JAX_CACHE_DIR).startswith(_JAX_CACHE_PREFIX) and os.path.isdir(_JAX_CACHE_DIR)
)
if _MADE_JAX_CACHE_DIR:
    _JAX_CACHE_DIR = os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix=_JAX_CACHE_PREFIX)

# Runtime lock-order sanitizer (common/locksan.py) ON for the whole tier-1
# suite: every threaded path (worker task loop, servicer gRPC pool, PS
# handlers, pod-manager watchers — and their subprocess workers, which
# inherit the env) runs with acquisition-order assertions against the
# static '# lock-order:' declarations graftlint checks.  setdefault so a
# developer can force it off with GRAFT_LOCKSAN=0.
os.environ.setdefault("GRAFT_LOCKSAN", "1")

# Runtime shared-state sanitizer (common/racesan.py) ON for the whole
# tier-1 suite, the locksan pattern: opted-in control-plane classes record
# per-attribute (thread-role, held-locks) observations and raise on a
# cross-role unguarded write — the dynamic twin of graftlint's v5
# shared-state pass.  Must be set before the opted-in classes are
# imported (the decorator reads it at class-creation time).
os.environ.setdefault("GRAFT_RACESAN", "1")

# Runtime jit-compile sanitizer (common/jitsan.py) ON for the whole
# tier-1 suite — the dynamic twin of graftlint's v6 jit-discipline
# passes: every jax_compat.jit_compiled/jit_donating callable counts its
# XLA lowerings and raises deterministically past its declared
# expected_variants budget, so the entire suite PROVES the train step
# compiles exactly once after warmup (mask flips and elastic reforms add
# zero recompiles).  setdefault so GRAFT_JITSAN=0 forces it off; the
# stricter GRAFT_JITSAN_TRANSFER_GUARD stays opt-in (compilation itself
# may move constants).
os.environ.setdefault("GRAFT_JITSAN", "1")

# Runtime durability sanitizer (common/crashsan.py) ON for the whole
# tier-1 suite — the dynamic twin of graftlint's v7 durability passes:
# every durable-write crossing (common/durable.py append/publish/replace)
# is counted and indexed per file, so crash_at(op, mode) matrices and the
# chaos grammar's torn_write faults can target exact crossings.  Recording
# is one locked counter bump per durable op — noise next to the fsync the
# op itself pays.  setdefault so GRAFT_CRASHSAN=0 forces it off.
os.environ.setdefault("GRAFT_CRASHSAN", "1")

# Runtime wire-schema sanitizer (common/wiresan.py) ON for the whole
# tier-1 suite — the dynamic twin of graftlint's v8 wire passes: every
# request AND response crossing JsonRpcClient.call / make_generic_handler
# is validated against its MessageSchema (missing/mistyped fields raise
# deterministically; unknown fields are counted per method — the
# additive-compat stance).  The armed cost is one dict scan per message,
# noise next to the JSON serialization the call already pays.  setdefault
# so GRAFT_WIRESAN=0 forces it off; the version mask
# (GRAFT_WIRESAN_MASK / wiresan.set_mask) stays opt-in per test.
os.environ.setdefault("GRAFT_WIRESAN", "1")

import jax  # noqa: E402
import pytest  # noqa: E402


#: Collected FIRST, in this order (PR 56; two lowering files since PR 66).  The driver's command (``-n 6 --dist load``)
#: deals the collection out in CONSECUTIVE chunks: a 24th of it (``N // 24`` cases: 111 of 2,683) to each of the six
#: workers to begin with, in the workers' order, smaller ones as a worker runs dry, and nothing is ever taken back.
#: The two lowering files (AOT compiles of the cells' whole steps for a described v5e, up to a minute a case, one after
#: another within a file: two at once fit the host, 6.9 + 4.7 GiB for the two largest, which are ``slow`` now; and the
#: cases that lower a whole step and pin or read its text) are the longest blocks any worker is handed that this order
#: can place: as ONE file, collected where its name falls, the block started at 580 s and ended at 1,370 of a run whose
#: other workers were done at 1,100 (the driver's run of PR 56's tree), and collected first it was 923 s of one worker
#: from second 0 (PR 66's sitting).  So the first file opens the FIRST worker's deal, with 150 cases behind it that
#: take 0.2 s together; the second file then lies whole inside the SECOND worker's deal and starts at second 0 too; and
#: the cases that fill that deal up behind it are a file's that takes 27 s in all (what the collection begins with
#: otherwise is the benchmark's growth rehearsal, 640 s, which now opens the third worker's).  That holds while ``first
#: + 150 >= N // 24``, ``first + 150 + second <= 2 * (N // 24)`` and ``first + 150 + second + 89 >= 2 * (N // 24)``:
#: with 12 + 150 + 19 cases, for 2,184 <= N < 3,264 (``tests/test_platform.py`` holds the run to it).  Within a file
#: the order is the collection's.
_COLLECTED_FIRST = ("test_chip_lowering.py", "test_renamed_metrics.py", "test_chip_lowering_pins.py", "test_data.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_COLLECTED_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))  # stable: every other file stays where it was


def pytest_sessionfinish(session, exitstatus):
    if _MADE_JAX_CACHE_DIR:
        shutil.rmtree(_JAX_CACHE_DIR, ignore_errors=True)
        # its sibling: the program store of the run's worker processes (common/program_store.py)
        shutil.rmtree(_JAX_CACHE_DIR + "_programs", ignore_errors=True)


@pytest.fixture
def default_compile_level(monkeypatch):
    """For the case whose reading sits ON its tolerance at XLA:CPU's default
    level (LLVM's vectoriser there orders a reduction's sums its own way):
    what it compiles is generated at level 3, whatever ``XLA_FLAGS`` said
    when the process read them.  No tolerance moves for the run's level."""
    from jax._src import compiler

    options_of = compiler.get_compile_options

    def at_level_3(*args, **kwargs):
        options = options_of(*args, **kwargs)
        options.executable_build_options.debug_options.xla_backend_optimization_level = 3
        return options

    monkeypatch.setattr(compiler, "get_compile_options", at_level_3)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 fake devices, got {len(devs)}"
    return devs
