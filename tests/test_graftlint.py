"""graftlint: each pass catches its seeded fixture violation (and passes
the clean twin), waiver syntax is enforced, and the REPO ITSELF lints
clean — tier-1 is the enforcement gate the invariants ride on."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from elasticdl_tpu.analysis import all_passes
from elasticdl_tpu.analysis.blocking import BlockingPropagationPass
from elasticdl_tpu.analysis.collective_shim import CollectiveShimPass
from elasticdl_tpu.analysis.compat_shim import CompatShimPass
from elasticdl_tpu.analysis.core import SourceFile, lint_text, run_lint, run_passes
from elasticdl_tpu.analysis.durability import (
    DurableWriteDisciplinePass,
    RecoveryReadDisciplinePass,
)
from elasticdl_tpu.analysis.hot_path import HotPathSyncPass
from elasticdl_tpu.analysis.import_hygiene import ImportHygienePass, module_dependents
from elasticdl_tpu.analysis.lock_discipline import LockDisciplinePass
from elasticdl_tpu.analysis.lock_order import LockOrderPass
from elasticdl_tpu.analysis.rpc_discipline import RpcDisciplinePass
from elasticdl_tpu.analysis.thread_hygiene import ThreadHygienePass
from elasticdl_tpu.analysis.wire_discipline import (
    WireDisciplinePass,
    WireEvolutionPass,
    wire_fingerprint,
)
from tools import graftlint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(src: str, passes) -> list:
    return lint_text(textwrap.dedent(src), passes)


def _rules(findings) -> set:
    return {f.rule for f in findings}


# ---- lock-discipline ----

LOCK_SEEDED = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0  # guarded-by: _lock

        def bump(self):
            self._count += 1  # race: no lock held
"""

LOCK_CLEAN = """
    import threading

    class Counter:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0  # guarded-by: _lock

        def bump(self):
            with self._lock:
                self._count += 1

        def _bump_locked(self):  # guarded-by: _lock
            self._count += 1
"""


def test_lock_discipline_flags_unguarded_touch():
    findings = _lint(LOCK_SEEDED, [LockDisciplinePass()])
    assert len(findings) == 1
    assert findings[0].rule == "lock-discipline"
    assert "_count" in findings[0].message


def test_lock_discipline_clean_twin():
    assert _lint(LOCK_CLEAN, [LockDisciplinePass()]) == []


def test_lock_discipline_closure_does_not_inherit_with_block():
    # A closure runs AFTER the with-block releases the lock: the classic
    # background-thread race must be flagged even though the def sits
    # lexically inside the locked region.
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._x = 0  # guarded-by: _lock

            def go(self):
                with self._lock:
                    def bg():
                        self._x += 1
                    t = threading.Thread(target=bg, daemon=True)
                t.start()
    """
    findings = _lint(src, [LockDisciplinePass()])
    assert len(findings) == 1 and "_x" in findings[0].message


# ---- hot-path-sync ----

HOT_SEEDED = """
    import time

    class W:
        # hot-path: the dispatch loop
        def dispatch(self):
            time.sleep(0.1)
"""

HOT_CLEAN = """
    import time

    class W:
        # hot-path: the dispatch loop
        def dispatch(self):
            with self.phases.phase("control"):
                self.master.call("GetTask", {})

        def not_hot(self):
            time.sleep(0.1)
"""


def test_hot_path_flags_sleep():
    findings = _lint(HOT_SEEDED, [HotPathSyncPass()])
    assert _rules(findings) == {"hot-path-sync"}


def test_hot_path_clean_twin_phase_boundary_and_unmarked():
    # Blocking inside a phases.phase(...) boundary is accounted-by-design;
    # unmarked functions are out of scope.
    assert _lint(HOT_CLEAN, [HotPathSyncPass()]) == []


def test_hot_path_device_reads_and_rpc_flagged():
    src = """
        class W:
            # hot-path
            def f(self):
                x = self.metrics.item()
                y = int(self.state.step)
                self.master.call("Report", {})
    """
    findings = _lint(src, [HotPathSyncPass()])
    assert len(findings) == 3


def test_hot_path_except_handler_exempt():
    src = """
        import time

        class W:
            # hot-path
            def f(self):
                try:
                    self.go()
                except Exception:
                    time.sleep(1.0)  # error path: off the hot path
    """
    assert _lint(src, [HotPathSyncPass()]) == []


# ---- blocking-propagation (v2: interprocedural) ----

# The tentpole's motivating hole: the helper wraps block_until_ready, the
# hot-path caller has no primitive of its own.  r7's hot-path-sync is
# provably blind to it; blocking-propagation must fire on the call edge.
BLOCKING_VIA_HELPER = """
    class W:
        def _settle(self):
            self.state.block_until_ready()

        # hot-path
        def dispatch(self):
            self._settle()
"""


def test_blocking_via_helper_missed_by_r7_caught_by_propagation():
    src = textwrap.dedent(BLOCKING_VIA_HELPER)
    assert lint_text(src, [HotPathSyncPass()]) == []  # r7: provably silent
    findings = lint_text(src, [BlockingPropagationPass()])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "blocking-propagation"
    assert "_settle" in f.message and "block_until_ready" in f.message


def test_blocking_propagation_two_levels_deep_with_witness_chain():
    src = """
        import time

        def _inner():
            time.sleep(1.0)

        def _outer():
            _inner()

        class W:
            # hot-path
            def dispatch(self):
                self._go()

            def _go(self):
                _outer()
    """
    findings = _lint(src, [BlockingPropagationPass()])
    assert len(findings) == 1
    # The witness names every hop down to the primitive.
    msg = findings[0].message
    assert "_go" in msg and "_outer" in msg and "_inner" in msg
    assert "time.sleep" in msg


def test_blocking_propagation_clean_twins():
    # Accounted (phase boundary at the call site OR inside the helper),
    # waived primitives, and error-path calls do not propagate.
    src = """
        import time

        class W:
            def _accounted(self):
                with self.phases.phase("checkpoint"):
                    self.state.block_until_ready()

            def _waived(self):
                # graftlint: allow[hot-path-sync] idle poll is the work here
                time.sleep(0.1)

            def _blocks(self):
                time.sleep(0.1)

            # hot-path
            def dispatch(self):
                self._accounted()
                self._waived()
                with self.phases.phase("control"):
                    self._blocks()
                try:
                    pass
                except Exception:
                    self._blocks()
    """
    assert _lint(src, [BlockingPropagationPass()]) == []


def test_blocking_propagation_waivable_at_call_site():
    src = """
        class W:
            def _settle(self):
                self.state.block_until_ready()

            # hot-path
            def dispatch(self):
                # graftlint: allow[blocking-propagation] startup settle, runs once
                self._settle()
    """
    assert _lint(src, [BlockingPropagationPass()]) == []


# ---- lock-order (v2: interprocedural) ----

LOCK_INVERSION = """
    import threading

    class C:
        def __init__(self):
            self._l1 = threading.Lock()
            self._l2 = threading.Lock()

        def path_a(self):
            with self._l1:
                self._take2()

        def _take2(self):
            with self._l2:
                pass

        def path_b(self):
            with self._l2:
                with self._l1:
                    pass
"""


def test_lock_order_reports_cycle_with_witness_path():
    findings = _lint(LOCK_INVERSION, [LockOrderPass()])
    cycles = [f for f in findings if "potential deadlock" in f.message]
    assert len(cycles) == 1
    msg = cycles[0].message
    # Full witness: both lock names and the file:line of each hop.
    assert "C._l1" in msg and "C._l2" in msg
    assert "path_a" in msg or "_take2" in msg
    assert "path_b" in msg
    assert "fixture.py:" in msg


def test_lock_order_clean_consistent_nesting():
    src = """
        import threading

        class C:
            def __init__(self):
                self._l1 = threading.Lock()
                self._l2 = threading.Lock()

            def path_a(self):
                with self._l1:
                    self._take2()

            def _take2(self):
                with self._l2:
                    pass

            def path_b(self):
                with self._l1:
                    with self._l2:
                        pass
    """
    assert _lint(src, [LockOrderPass()]) == []


def test_lock_order_self_deadlock_through_helper():
    src = """
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self._inner()

            def _inner(self):
                with self._lock:
                    pass
    """
    findings = _lint(src, [LockOrderPass()])
    assert len(findings) == 1
    assert "self-deadlock" in findings[0].message


def test_lock_order_leaf_annotation_enforced():
    src = """
        import threading

        class C:
            def __init__(self):
                self._leaf = threading.Lock()  # lock-order: leaf
                self._other = threading.Lock()

            def bad(self):
                with self._leaf:
                    with self._other:
                        pass
    """
    findings = _lint(src, [LockOrderPass()])
    assert len(findings) == 1
    assert "leaf" in findings[0].message


def test_lock_order_before_annotation_enforced():
    src = """
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()  # lock-order: before(_b)
                self._b = threading.Lock()

            def ok(self):
                with self._a:
                    with self._b:
                        pass

            def bad(self):
                with self._b:
                    with self._a:
                        pass
    """
    findings = _lint(src, [LockOrderPass()])
    # The declared-order violation plus the cycle the two paths form.
    assert any("before" in f.message for f in findings)


def test_lock_order_closure_does_not_inherit_held_set():
    # A closure runs later on another thread: the lock held lexically
    # around the def is NOT held when the closure's body acquires.
    src = """
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()  # lock-order: leaf
                self._b = threading.Lock()

            def go(self):
                with self._a:
                    def bg():
                        with self._b:
                            pass
                    t = threading.Thread(target=bg, daemon=True)
                t.start()
    """
    assert _lint(src, [LockOrderPass()]) == []


def test_lock_order_locksan_kwargs_must_match_comment():
    src = """
        from elasticdl_tpu.common import locksan

        class C:
            def __init__(self):
                self._a = locksan.lock("C._a", leaf=True)
    """
    findings = _lint(src, [LockOrderPass()])
    assert len(findings) == 1
    assert "disagrees" in findings[0].message
    clean = """
        from elasticdl_tpu.common import locksan

        class C:
            def __init__(self):
                self._a = locksan.lock("C._a", leaf=True)  # lock-order: leaf
    """
    assert _lint(clean, [LockOrderPass()]) == []


def test_lock_order_locksan_name_must_match_attribute():
    src = """
        from elasticdl_tpu.common import locksan

        class C:
            def __init__(self):
                self._a = locksan.lock("C._wrong")
    """
    findings = _lint(src, [LockOrderPass()])
    assert len(findings) == 1 and "does not match" in findings[0].message


def test_lock_order_malformed_annotation_is_finding():
    src = """
        import threading

        class C:
            def __init__(self):
                self._a = threading.Lock()  # lock-order: sideways
    """
    findings = _lint(src, [LockOrderPass()])
    assert len(findings) == 1 and "malformed" in findings[0].message


# ---- stale-waiver ----

def test_stale_waiver_flagged_when_nothing_suppressed():
    src = """
        import time

        class W:
            # hot-path
            def f(self):
                # graftlint: allow[hot-path-sync] this line no longer blocks
                x = 1
                return x
    """
    findings = _lint(src, [HotPathSyncPass()])
    assert _rules(findings) == {"stale-waiver"}
    assert "suppresses no finding" in findings[0].message


def test_live_waiver_not_stale():
    src = """
        import time

        class W:
            # hot-path
            def f(self):
                # graftlint: allow[hot-path-sync] idle poll is the work here
                time.sleep(0.1)
    """
    assert _lint(src, [HotPathSyncPass()]) == []


def test_stale_waiver_only_judged_for_rules_that_ran():
    # A thread-hygiene waiver cannot be judged stale by a run that never
    # executed the thread-hygiene pass.
    src = """
        def f():
            # graftlint: allow[thread-hygiene] joined in caller scope
            pass
    """
    assert _lint(src, [HotPathSyncPass()]) == []
    findings = _lint(src, [ThreadHygienePass()])
    assert _rules(findings) == {"stale-waiver"}


def test_propagation_blocking_waiver_is_not_stale():
    # The waiver on a non-hot helper's primitive is load-bearing: it stops
    # the primitive from propagating to hot callers.  The full suite must
    # neither propagate NOR call the waiver stale.
    src = """
        import time

        class W:
            def _poll(self):
                # graftlint: allow[hot-path-sync] idle poll is the work here
                time.sleep(0.1)

            # hot-path
            def dispatch(self):
                self._poll()
    """
    assert _lint(src, all_passes()) == []


def test_lock_order_condition_is_reentrant():
    # threading.Condition() wraps an RLock: same-thread nested entry (even
    # through a helper) is legal and must not read as a self-deadlock.
    src = """
        import threading

        class C:
            def __init__(self):
                self._cond = threading.Condition()

            def outer(self):
                with self._cond:
                    self._inner()

            def _inner(self):
                with self._cond:
                    pass
    """
    assert _lint(src, [LockOrderPass()]) == []


# ---- --changed dependents ----

def test_module_dependents_transitive_closure():
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/helper.py": "x = 1\n",
        "pkg/mid.py": "from pkg.helper import x\n",
        "pkg/root.py": "from pkg.mid import x\n",
        "pkg/unrelated.py": "y = 2\n",
    })
    deps = module_dependents(srcs, {"pkg/helper.py"})
    assert deps == {"pkg/helper.py", "pkg/mid.py", "pkg/root.py"}


def test_module_dependents_changed_package_init():
    # Importing pkg.sub.mod executes pkg/sub/__init__: a changed package
    # __init__ makes every importer underneath it a dependent.
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/sub/__init__.py": "",
        "pkg/sub/mod.py": "y = 2\n",
        "pkg/user.py": "from pkg.sub.mod import y\n",
    })
    deps = module_dependents(srcs, {"pkg/sub/__init__.py"})
    assert "pkg/user.py" in deps


# ---- compat-shim ----

SHIM_SEEDED = """
    from jax.experimental.shard_map import shard_map

    def f(mesh):
        return shard_map(lambda x: x, mesh=mesh)
"""

SHIM_CLEAN = """
    from elasticdl_tpu.common.jax_compat import axis_size, shard_map

    def f(mesh):
        return shard_map(lambda x: x, mesh=mesh)
"""


def test_compat_shim_flags_raw_import():
    findings = _lint(SHIM_SEEDED, [CompatShimPass()])
    assert _rules(findings) == {"compat-shim"}


def test_compat_shim_clean_twin():
    assert _lint(SHIM_CLEAN, [CompatShimPass()]) == []


def test_compat_shim_flags_attr_spellings_but_not_in_shim_module():
    src = """
        import jax
        from jax import lax

        def f():
            jax.distributed.initialize(coordinator_address="x")
            return lax.axis_size("dp")
    """
    findings = _lint(src, [CompatShimPass()])
    assert len(findings) == 2
    # The shim module itself is the one place allowed to spell these.
    clean = lint_text(
        textwrap.dedent(src), [CompatShimPass()],
        path="elasticdl_tpu/common/jax_compat.py",
    )
    assert clean == []


# ---- collective-shim (graftreduce r15) ----

COLLECTIVE_SEEDED = """
    from jax import lax

    def local_step(grads, axes):
        loss = lax.psum(grads, axes)
        mean = lax.pmean(grads, axes)
        shard = lax.psum_scatter(grads, "dp", scatter_dimension=0, tiled=True)
        return loss, mean, shard
"""

COLLECTIVE_CLEAN = """
    from jax import lax
    from elasticdl_tpu.parallel import collectives as coll

    def local_step(grads, axes, topo):
        loss = coll.psum(grads, axes, topo)
        mean = coll.pmean(grads, axes, topo)
        shard = coll.psum_scatter(grads, "dp", scatter_dimension=0, tiled=True)
        gathered = lax.all_gather(grads, "dp")  # moves data, not a reduction
        return loss, mean, shard, gathered
"""


def test_collective_shim_flags_raw_reductions():
    findings = _lint(COLLECTIVE_SEEDED, [CollectiveShimPass()])
    assert _rules(findings) == {"collective-shim"}
    assert len(findings) == 3  # psum + pmean + psum_scatter


def test_collective_shim_clean_twin():
    assert _lint(COLLECTIVE_CLEAN, [CollectiveShimPass()]) == []


def test_collective_shim_flags_import_alias():
    # ``from jax.lax import psum`` would smuggle the raw spelling past
    # the attribute check — the import itself is the finding.
    src = """
        from jax.lax import psum, all_gather

        def f(x):
            return psum(x, "dp"), all_gather(x, "dp")
    """
    findings = _lint(src, [CollectiveShimPass()])
    assert len(findings) == 1  # all_gather stays legal


def test_collective_shim_exempts_shim_modules():
    src = textwrap.dedent(COLLECTIVE_SEEDED)
    for path in (
        "elasticdl_tpu/parallel/collectives.py",
        "elasticdl_tpu/common/jax_compat.py",
    ):
        assert lint_text(src, [CollectiveShimPass()], path=path) == []


def test_collective_shim_jax_lax_spelling():
    src = """
        import jax

        def f(x):
            return jax.lax.psum(x, "dp")
    """
    findings = _lint(src, [CollectiveShimPass()])
    assert _rules(findings) == {"collective-shim"}


# ---- rpc-discipline ----

RPC_SEEDED = """
    class Store:
        def probe(self):
            return self._client.call("Stats", {})
"""

RPC_CLEAN = """
    class Store:
        def probe(self):
            return self._client.call("Stats", {}, timeout_s=5.0)

        def _retry(self, fn):
            return fn()

        def pull(self):
            return self._retry(lambda: self._client.call("Pull", {}))

        def inside_wrapper(self):
            # wrapper functions own deadline+backoff for their bodies
            pass

        def via_master(self):
            return self.master.call("GetTask", {})  # proxy owns the deadline

        def not_rpc(self):
            import subprocess
            return subprocess.call(["true"])
"""


def test_rpc_discipline_flags_bare_stub_call():
    findings = _lint(RPC_SEEDED, [RpcDisciplinePass()])
    assert _rules(findings) == {"rpc-discipline"}


def test_rpc_discipline_clean_twin():
    assert _lint(RPC_CLEAN, [RpcDisciplinePass()]) == []


# r18: bare readiness waits — the raw channel_ready_future primitive is a
# hand-rolled reconnect loop (one hard timeout, no retry accounting, no
# jitter) and is legal only inside common/rpc.py, whose
# wait_channel_ready wraps it in the shared backoff helper.

READY_SEEDED = """
    import grpc

    class Client:
        def wait_ready(self, timeout_s=10.0):
            grpc.channel_ready_future(self._channel).result(timeout=timeout_s)
"""

READY_CLEAN = """
    from elasticdl_tpu.common.rpc import wait_channel_ready

    class Client:
        def wait_ready(self, timeout_s=10.0):
            wait_channel_ready(
                self._channel, service="x", budget_s=timeout_s
            )
"""


def test_rpc_discipline_flags_bare_readiness_wait():
    findings = _lint(READY_SEEDED, [RpcDisciplinePass()])
    assert _rules(findings) == {"rpc-discipline"}
    assert "channel_ready_future" in findings[0].message


def test_rpc_discipline_readiness_clean_twin():
    assert _lint(READY_CLEAN, [RpcDisciplinePass()]) == []


def test_rpc_discipline_readiness_owner_module_exempt():
    src = textwrap.dedent(READY_SEEDED)
    assert lint_text(
        src, [RpcDisciplinePass()],
        path="elasticdl_tpu/common/rpc.py",
    ) == []


# ---- thread-hygiene ----

THREAD_SEEDED = """
    import threading

    def leak():
        threading.Thread(target=print).start()
"""

THREAD_CLEAN = """
    import threading

    def daemonized():
        threading.Thread(target=print, daemon=True).start()

    def joined():
        ts = [threading.Thread(target=print) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
"""


def test_thread_hygiene_flags_leaked_thread():
    findings = _lint(THREAD_SEEDED, [ThreadHygienePass()])
    assert _rules(findings) == {"thread-hygiene"}


def test_thread_hygiene_clean_twin():
    assert _lint(THREAD_CLEAN, [ThreadHygienePass()]) == []


# ---- import-hygiene ----

def _sources(files: dict) -> list:
    return [
        SourceFile(path, textwrap.dedent(text)) for path, text in files.items()
    ]


def test_import_hygiene_flags_transitive_jax():
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/control.py": "from pkg.helper import x\n",
        "pkg/helper.py": "import jax\nx = 1\n",
    })
    p = ImportHygienePass(roots=("pkg.control",))
    findings = run_passes(srcs, [p])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "import-hygiene" and f.path == "pkg/control.py"
    assert "pkg.helper" in f.message and f.line == 1


def test_import_hygiene_deferred_import_is_clean():
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/control.py": "from pkg.helper import x\n",
        "pkg/helper.py": "def f():\n    import jax\n    return jax\nx = 1\n",
    })
    findings = run_passes(srcs, [ImportHygienePass(roots=("pkg.control",))])
    assert findings == []


def test_import_hygiene_counts_package_init():
    # Importing pkg.sub.mod executes pkg/__init__ and pkg/sub/__init__ —
    # a jax import hiding in an ancestor package must be caught.
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/root.py": "from pkg.sub.mod import y\n",
        "pkg/sub/__init__.py": "import jax\n",
        "pkg/sub/mod.py": "y = 2\n",
    })
    findings = run_passes(srcs, [ImportHygienePass(roots=("pkg.root",))])
    assert len(findings) == 1


def test_import_hygiene_flags_module_level_platform_call():
    # The real leak this pass closed: a common/platform.py helper imports
    # jax inside its body, so a module-level CALL executes the import even
    # though no 'import jax' statement is visible at module scope.
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/control.py": (
            "from elasticdl_tpu.common.platform import device_summary\n"
            "device_summary()\n"
        ),
    })
    findings = run_passes(srcs, [ImportHygienePass(roots=("pkg.control",))])
    assert len(findings) == 1 and findings[0].line == 2


def test_master_process_is_jax_free_at_runtime():
    # The runtime twin of the static pass: importing the master stack in a
    # fresh interpreter must not pull jax into the process.
    code = (
        "import sys; "
        "import elasticdl_tpu.master.main, elasticdl_tpu.master.servicer, "
        "elasticdl_tpu.master.pod_manager, elasticdl_tpu.common.platform; "
        "sys.exit(1 if 'jax' in sys.modules else 0)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, timeout=120,
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]


# ---- waivers ----

def test_valid_waiver_suppresses_finding():
    src = """
        import time

        class W:
            # hot-path
            def f(self):
                # graftlint: allow[hot-path-sync] idle poll is the work here
                time.sleep(0.1)
    """
    assert _lint(src, [HotPathSyncPass()]) == []


def test_waiver_same_line_form():
    src = """
        import time

        class W:
            # hot-path
            def f(self):
                time.sleep(0.1)  # graftlint: allow[hot-path-sync] idle poll
    """
    assert _lint(src, [HotPathSyncPass()]) == []


def test_waiver_wrong_rule_does_not_suppress():
    src = """
        import time

        class W:
            # hot-path
            def f(self):
                # graftlint: allow[thread-hygiene] reason for another rule
                time.sleep(0.1)
    """
    findings = _lint(src, [HotPathSyncPass()])
    assert _rules(findings) == {"hot-path-sync"}


@pytest.mark.parametrize(
    "waiver, expect",
    [
        ("# graftlint: allow[hot-path-sync]", "no reason"),
        ("# graftlint: allow[] why not", "names no rule"),
        ("# graftlint: allow hot-path-sync why", "malformed"),
        ("# graftlint: allow[not-a-rule] why", "unknown rule"),
    ],
)
def test_malformed_waivers_are_findings(waiver, expect):
    src = f"""
        def f():
            {waiver}
            pass
    """
    findings = _lint(src, [])
    assert len(findings) == 1
    assert findings[0].rule == "waiver-syntax"
    assert expect in findings[0].message


def test_malformed_waiver_cannot_waive_itself():
    src = """
        def f():
            # graftlint: allow[waiver-syntax] trying to excuse myself
            # graftlint: allow[]
            pass
    """
    findings = _lint(src, [])
    assert any("names no rule" in f.message for f in findings)


def test_import_hygiene_module_level_loop_body_counts():
    # A top-level loop body executes at import time too — it must not be
    # a smuggling route.
    srcs = _sources({
        "pkg/__init__.py": "",
        "pkg/control.py": "for _ in range(1):\n    import jax\n",
    })
    findings = run_passes(srcs, [ImportHygienePass(roots=("pkg.control",))])
    assert len(findings) == 1


# ---- parse errors and scoping ----

def test_parse_error_has_its_own_rule(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    findings = run_lint([str(tmp_path)])
    assert [f.rule for f in findings] == ["parse-error"]


def test_only_paths_scopes_parse_errors_too(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    (tmp_path / "ok.py").write_text("x = 1\n")
    findings = run_lint(
        [str(tmp_path)], rel_to=str(tmp_path), only_paths={"ok.py"}
    )
    assert findings == []


# ---- trace-discipline ----

TRACE_SEEDED = """
    from elasticdl_tpu.common import trace

    class Worker:
        # hot-path: the steady-state task loop
        def poll(self):
            rec = trace.default()
            rec.instant("tick", cat="loop")
            return rec.drain_slice(512)  # export from the hot path: finding
"""

TRACE_CLEAN = """
    from elasticdl_tpu.common import trace

    class Worker:
        # hot-path: the steady-state task loop
        def poll(self):
            with trace.span("poll", cat="loop"):
                trace.instant("tick", cat="loop")

        def ship(self):
            # Not hot-path: draining from a control-plane boundary is the
            # intended pattern.
            return trace.default().drain_slice(512)
"""


def test_trace_discipline_seeded_and_clean():
    from elasticdl_tpu.analysis.trace_discipline import TraceDisciplinePass

    findings = _lint(TRACE_SEEDED, [TraceDisciplinePass()])
    assert _rules(findings) == {"trace-discipline"}
    assert len(findings) == 1
    assert _lint(TRACE_CLEAN, [TraceDisciplinePass()]) == []


def test_trace_discipline_flags_export_and_chrome_events():
    from elasticdl_tpu.analysis.trace_discipline import TraceDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self, rec):
                rec.export()
                rec.chrome_events()
    """
    findings = _lint(src, [TraceDisciplinePass()])
    assert len(findings) == 2


def test_trace_discipline_ignores_unrelated_export():
    from elasticdl_tpu.analysis.trace_discipline import TraceDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self, model):
                model.export()  # not a trace recorder: no finding
    """
    assert _lint(src, [TraceDisciplinePass()]) == []


def test_trace_discipline_waivable_and_exempts_error_paths():
    from elasticdl_tpu.analysis.trace_discipline import TraceDisciplinePass

    src = """
        from elasticdl_tpu.common import trace

        class W:
            # hot-path
            def step(self, rec):
                # graftlint: allow[trace-discipline] deliberate debug drain
                rec.drain_slice(8)
                try:
                    pass
                except Exception:
                    rec.drain_slice(8)  # error path: exempt
    """
    assert _lint(src, [TraceDisciplinePass()]) == []


# ---- chaos-discipline ----

CHAOS_SEEDED = """
    from elasticdl_tpu import chaos

    class Worker:
        # hot-path: the steady-state task loop
        def poll(self):
            chaos.hook("worker:task", rank=0, step=1)
            chaos.configure("stall:ms=5")  # plan mutation on the hot path: finding
"""

CHAOS_CLEAN = """
    from elasticdl_tpu import chaos

    class Worker:
        def __init__(self, config):
            # Arming at a process boundary is the intended pattern.
            chaos.configure(config.chaos)
            chaos.set_context(rank=0)

        # hot-path: the steady-state task loop
        def poll(self):
            chaos.hook("worker:task", rank=0, step=1)
"""


def test_chaos_discipline_seeded_and_clean():
    from elasticdl_tpu.analysis.chaos_discipline import ChaosDisciplinePass

    findings = _lint(CHAOS_SEEDED, [ChaosDisciplinePass()])
    assert _rules(findings) == {"chaos-discipline"}
    assert len(findings) == 1
    assert _lint(CHAOS_CLEAN, [ChaosDisciplinePass()]) == []


def test_chaos_discipline_flags_fire_set_context_and_construction():
    from elasticdl_tpu.analysis.chaos_discipline import ChaosDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self, chaos, inj):
                chaos.default().fire("worker:task", {})
                inj.set_context(rank=1)
                ChaosInjector()
    """
    findings = _lint(src, [ChaosDisciplinePass()])
    assert len(findings) == 3


def test_chaos_discipline_ignores_unrelated_receivers():
    from elasticdl_tpu.analysis.chaos_discipline import ChaosDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self, model, logger):
                model.configure(lr=0.1)   # not a chaos receiver
                logger.fire("event")      # nor this
    """
    assert _lint(src, [ChaosDisciplinePass()]) == []


def test_chaos_discipline_waivable_and_exempts_error_paths():
    from elasticdl_tpu.analysis.chaos_discipline import ChaosDisciplinePass

    src = """
        from elasticdl_tpu import chaos

        class W:
            # hot-path
            def step(self):
                # graftlint: allow[chaos-discipline] deliberate hot rearm in a test harness
                chaos.configure("stall:ms=1")
                try:
                    pass
                except Exception:
                    chaos.configure("")  # error path: exempt
    """
    assert _lint(src, [ChaosDisciplinePass()]) == []


# ---- gauge-discipline ----

GAUGE_SEEDED = """
    from elasticdl_tpu.common import gauge

    class Worker:
        def __init__(self):
            self.gauges = gauge.Registry()
            self._g_examples = self.gauges.counter("edl_examples_trained_total")

        # hot-path: the steady-state task loop
        def step(self):
            self._g_examples.inc(64)
            return self.gauges.snapshot()  # scrape from the hot path: finding
"""

GAUGE_CLEAN = """
    from elasticdl_tpu.common import gauge

    class Worker:
        def __init__(self):
            self.gauges = gauge.Registry()
            self._g_examples = self.gauges.counter("edl_examples_trained_total")
            self._g_step_ms = self.gauges.histogram("edl_step_ms")

        # hot-path: the steady-state task loop
        def step(self):
            # O(1) ring/counter API: the only gauge calls legal here.
            self._g_examples.inc(64)
            self._g_step_ms.observe(8.2)
            self.gauges.gauge("edl_lease_depth").set(3)

        def gauge_payload(self):
            # Not hot-path: snapshotting at a control-plane boundary is
            # the intended pattern.
            return {"families": self.gauges.snapshot()}
"""


def test_gauge_discipline_seeded_and_clean():
    from elasticdl_tpu.analysis.gauge_discipline import GaugeDisciplinePass

    findings = _lint(GAUGE_SEEDED, [GaugeDisciplinePass()])
    assert _rules(findings) == {"gauge-discipline"}
    assert len(findings) == 1
    assert _lint(GAUGE_CLEAN, [GaugeDisciplinePass()]) == []


def test_gauge_discipline_flags_render_and_aggregation_calls():
    from elasticdl_tpu.analysis.gauge_discipline import GaugeDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self, reg, fleet):
                reg.render_prometheus()
                fleet.fleet_snapshot()
                reg.scalar_values(["edl_examples_trained_total"])
    """
    assert len(_lint(src, [GaugeDisciplinePass()])) == 3


def test_gauge_discipline_ignores_unrelated_snapshot():
    from elasticdl_tpu.analysis.gauge_discipline import GaugeDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self):
                # PhaseTimers/trainer snapshots are not gauge scrapes.
                self.phases.snapshot()
                self.trainer.snapshot_state()
    """
    assert _lint(src, [GaugeDisciplinePass()]) == []


def test_gauge_discipline_waivable_and_exempts_error_paths():
    from elasticdl_tpu.analysis.gauge_discipline import GaugeDisciplinePass

    src = """
        class W:
            # hot-path
            def step(self, reg):
                # graftlint: allow[gauge-discipline] deliberate debug scrape
                reg.render_prometheus()
                try:
                    pass
                except Exception:
                    reg.render_prometheus()  # error path: exempt
    """
    assert _lint(src, [GaugeDisciplinePass()]) == []


# ---- the repo-wide gate ----

@pytest.fixture(scope="module")
def repo_lint():
    """ONE analysis of the tree a process (``tools/graftlint.py``'s default
    roots, every pass): ``(findings, sources, waivers)``.  Every mode of the
    CLI prints a view of exactly this, so the cases below read the views
    in-process, and one case keeps the subprocess for the exit codes."""
    from elasticdl_tpu.analysis import collect_waivers
    from elasticdl_tpu.analysis.core import run_lint_full
    roots = [os.path.join(REPO, p) for p in graftlint.DEFAULT_PATHS]
    findings, sources = run_lint_full(roots, all_passes(), rel_to=REPO)
    return findings, sources, collect_waivers(sources)


def test_repo_lints_clean(repo_lint):
    findings, _, _ = repo_lint
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exits_zero_on_repo_and_one_on_violation(tmp_path):
    """The ONE case that runs the tool as a process: its exit code, and
    which stream carries what, are the CLI's contract with the pre-commit
    hook and with whoever pipes a dump."""
    out = subprocess.run(
        [sys.executable, "tools/graftlint.py"],  # the default roots
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout == "" and "graftlint: 0 finding(s) across" in out.stderr
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import threading\n"
        "threading.Thread(target=print).start()\n"
    )
    out = subprocess.run(
        [sys.executable, "tools/graftlint.py", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 1
    assert "thread-hygiene" in out.stdout
    # a dump of a tree with a finding: exit 1 still, the finding on stderr, and stdout parseable all the same
    out = subprocess.run(
        [sys.executable, "tools/graftlint.py", str(bad), "--callgraph"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 1 and "thread-hygiene" in out.stderr
    assert "lock_edges" in json.loads(out.stdout)


def test_cli_json_includes_waiver_inventory(repo_lint):
    findings, _, waivers = repo_lint
    doc = json.loads(json.dumps(graftlint.findings_json(findings, waivers), sort_keys=True))
    assert set(doc) == {"findings", "waivers"}
    assert doc["findings"] == []
    # The repo carries reasoned waivers; each inventory entry is complete,
    # and names a rule there is (the per-rule counts of the waivers).
    assert len(doc["waivers"]) > 0
    rules = {p.name for p in all_passes()}
    assert "shared-state" in rules
    for w in doc["waivers"]:
        assert set(w) == {"path", "line", "rule", "reason"}
        assert w["reason"] and w["rule"] in rules
    # a finding prints as its fields
    (finding,) = _lint("import threading\nthreading.Thread(target=print).start()\n", [ThreadHygienePass()])
    (printed,) = graftlint.findings_json([finding], [])["findings"]
    assert printed["rule"] == "thread-hygiene" and {"path", "line", "message"} <= set(printed)


def test_cli_callgraph_dump(repo_lint):
    doc = json.loads(json.dumps(graftlint.VIEWS["callgraph"](repo_lint[1])))
    assert doc["functions"] > 100
    assert any("Worker._run" in q for q in doc["hot_path_functions"])
    assert "elasticdl_tpu.worker.worker:Worker._ckpt_lock" in doc["locks"]
    assert doc["locks"]["elasticdl_tpu.worker.worker:Worker._ckpt_lock"]["leaf"]
    # the lock graph and the blocking roots
    assert len(doc["blocking_roots"]) > 0
    assert len(doc["locks"]) > 10
    assert sum(1 for d in doc["locks"].values() if d["locksan"]) > 10
    # The one statically visible nesting: GetGroupTask -> GetTask.
    assert [
        "elasticdl_tpu.master.servicer:MasterServicer._group_lock",
        "elasticdl_tpu.master.servicer:MasterServicer._lock",
    ] in [[e["held"], e["acquired"]] for e in doc["lock_edges"]]


def test_cli_changed_fails_loud_when_git_unreadable():
    # 'git broke' must never be reported as 'nothing changed, gate clean'.
    out = subprocess.run(
        [sys.executable, "tools/graftlint.py", "--changed"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "GIT_DIR": "/nonexistent"},
    )
    assert out.returncode == 2
    assert "git" in out.stderr


def test_cli_changed_mode_runs(capsys):
    # --changed must run and exit cleanly whatever the current diff is;
    # findings it reports are restricted to changed files.
    assert graftlint.main(["--changed", "--json"]) in (0, 1), capsys.readouterr().err
    json.loads(capsys.readouterr().out)  # valid JSON either way


# ---- thread-hygiene v5: Timer + executor shapes ----

def test_timer_seeded_and_clean_twins():
    seeded = """
        import threading

        def fire():
            threading.Timer(5.0, print).start()
    """
    findings = _lint(seeded, [ThreadHygienePass()])
    assert _rules(findings) == {"thread-hygiene"}
    assert "Timer" in findings[0].message
    clean = """
        import threading

        def daemonized():
            t = threading.Timer(5.0, print)
            t.daemon = True
            t.start()

        def cancelled():
            t = threading.Timer(5.0, print)
            t.start()
            t.cancel()

        def joined():
            t = threading.Timer(0.0, print)
            t.start()
            t.join()
    """
    assert _lint(clean, [ThreadHygienePass()]) == []


def test_executor_seeded_and_clean_twins():
    seeded = """
        from concurrent.futures import ThreadPoolExecutor

        def leak():
            pool = ThreadPoolExecutor(4)
            pool.submit(print)
    """
    findings = _lint(seeded, [ThreadHygienePass()])
    assert _rules(findings) == {"thread-hygiene"}
    assert "executor" in findings[0].message
    clean = """
        from concurrent.futures import ThreadPoolExecutor, futures

        class Owner:
            def __init__(self, par):
                # Conditional construction still counts as owned.
                self._pool = ThreadPoolExecutor(4) if par else None

        def handed_to_owner(grpc):
            return grpc.server(ThreadPoolExecutor(8))

        def scoped():
            with ThreadPoolExecutor(2) as pool:
                pool.submit(print)

        def shut_down():
            pool = ThreadPoolExecutor(2)
            pool.submit(print)
            pool.shutdown()
    """
    assert _lint(clean, [ThreadHygienePass()]) == []


# ---- thread-map (v5) ----

def _tmap(files: dict):
    from elasticdl_tpu.analysis.thread_map import shared_thread_map

    return shared_thread_map(_sources(files))


THREADED_MODULE = """
    import threading
    from concurrent.futures import ThreadPoolExecutor

    class W:
        def __init__(self):
            self._pool = ThreadPoolExecutor(2)

        def start(self):
            threading.Thread(
                target=self._watch, name="watcher", daemon=True
            ).start()
            threading.Timer(1.0, self._retry).start()
            fut = self._pool.submit(self._prep, 1)
            fut.add_done_callback(self._done)

        def _watch(self):
            self._tick()

        def _tick(self):
            pass

        def _retry(self):
            pass

        def _prep(self, n):
            pass

        def _done(self, fut):
            pass

        def loop(self):
            pass


    def main():
        w = W()
        w.loop()
"""


def test_thread_map_infers_spawn_shapes_and_propagates():
    tmap = _tmap({"pkg/__init__.py": "", "pkg/mod.py": THREADED_MODULE})
    roles = {
        q.split(":")[-1]: sorted(r) for q, r in tmap.roles.items()
    }
    assert roles["W._watch"] == ["thread:watcher"]
    # Propagated over the call edge, not just the entry.
    assert roles["W._tick"] == ["thread:watcher"]
    assert roles["W._retry"] == ["timer:_retry"]
    assert roles["W._prep"] == ["pool:_prep"]
    assert roles["W._done"] == ["callback:_done"]
    # Constructor-typed local: main's `w = W(); w.loop()` edges into W.loop.
    assert roles["W.loop"] == ["main"]
    # start() itself has no inferred role (nothing spawns INTO it).
    assert "W.start" not in roles


def test_thread_map_closure_target_and_inheritance():
    tmap = _tmap({"pkg/__init__.py": "", "pkg/mod.py": """
        import threading

        def main():
            def bg():
                helper()

            def inline():
                helper2()

            threading.Thread(target=bg, daemon=True).start()
            inline()

        def helper():
            pass

        def helper2():
            pass
    """})
    by_fn = {q.split(":")[-1]: sorted(r) for q, r in tmap.roles.items()}
    # The spawned closure runs ONLY on its thread; calls propagate.
    assert by_fn["helper"] == ["thread:bg"]
    # A non-spawned closure inherits the enclosing function's role.
    assert by_fn["helper2"] == ["main"]


def test_thread_map_grpc_method_table_and_dict_literal():
    tmap = _tmap({"pkg/__init__.py": "", "pkg/svc.py": """
        import grpc

        class FooServicer:
            def method_table(self):
                return {name: getattr(self, name) for name in ("GetTask",)}

            def GetTask(self, req):
                return self._inner()

            def _inner(self):
                pass

        class Shard:
            def __init__(self):
                self._server = grpc.server(None)
                self._server.add_generic_rpc_handlers(())

            def _make(self):
                return {"Pull": self._pull}

            def _pull(self, req):
                pass
    """})
    by_fn = {q.split(":")[-1]: sorted(r) for q, r in tmap.roles.items()}
    assert by_fn["FooServicer.GetTask"] == ["grpc:FooServicer"]
    assert by_fn["FooServicer._inner"] == ["grpc:FooServicer"]
    assert by_fn["Shard._pull"] == ["grpc:Shard"]
    # An ordinary dispatch table in a non-grpc class is NOT an entry.
    tmap2 = _tmap({"pkg/__init__.py": "", "pkg/plain.py": """
        class Plain:
            def table(self):
                return {"a": self._a}

            def _a(self):
                pass
    """})
    assert not any("grpc" in r for rs in tmap2.roles.values() for r in rs)


def test_thread_role_annotation_seeds_and_malformed_is_finding():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    tmap = _tmap({"pkg/__init__.py": "", "pkg/mod.py": """
        class W:
            # thread-role: thread:beat — reached through a holder dict
            def tick(self):
                pass
    """})
    by_fn = {q.split(":")[-1]: sorted(r) for q, r in tmap.roles.items()}
    assert by_fn["W.tick"] == ["thread:beat"]
    findings = _lint("""
        class W:
            # thread-role: !!nope
            def tick(self):
                pass
    """, [SharedStatePass()])
    assert _rules(findings) == {"shared-state"}
    assert "malformed thread-role" in findings[0].message


# ---- shared-state (v5) ----

SHARED_SEEDED = """
    import threading

    class W:
        def __init__(self):
            self._depth = 0

        def run(self):
            self._depth = 1

        def start(self):
            threading.Thread(target=self._bg, daemon=True).start()

        def _bg(self):
            self._depth = 2


    def main():
        w = W()
        w.run()
"""

SHARED_CLEAN = """
    import threading

    class W:
        def __init__(self):
            self._lock = threading.Lock()
            self._depth = 0

        def run(self):
            with self._lock:
                self._depth = 1

        def start(self):
            threading.Thread(target=self._bg, daemon=True).start()

        def _bg(self):
            with self._lock:
                self._depth = 2


    def main():
        w = W()
        w.run()
"""


def test_shared_state_cross_role_unguarded_write():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    findings = _lint(SHARED_SEEDED, [SharedStatePass()])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "shared-state"
    assert "_depth" in f.message and "thread:_bg" in f.message
    assert "main" in f.message


def test_shared_state_clean_twin_common_lock():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    assert _lint(SHARED_CLEAN, [SharedStatePass()]) == []


def test_shared_state_guarded_by_helper_annotation_counts_as_held():
    # The *_locked helper convention: a def-line '# guarded-by: <lock>'
    # marks the lock held by contract, so the helper's sites share it.
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._depth = 0

            def run(self):
                with self._lock:
                    self._bump_locked()

            def _bump_locked(self):  # guarded-by: _lock
                self._depth = 1

            def start(self):
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                with self._lock:
                    self._depth = 2


        def main():
            w = W()
            w.run()
    """
    assert _lint(src, [SharedStatePass()]) == []


def test_shared_state_init_and_roleless_sites_exempt():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = """
        import threading

        class W:
            def __init__(self):
                self._cfg = {}

            def helper_nobody_calls(self):
                self._cfg = {"x": 1}

            def start(self):
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                print(self._cfg)
    """
    # The only roled site is the _bg read; writes are __init__ (exempt)
    # and an unreachable helper (unknown role): no finding.
    assert _lint(src, [SharedStatePass()]) == []


def test_shared_state_single_writer_declared_and_violated():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    clean = """
        import threading

        class W:
            def __init__(self):
                self._step = 0  # single-writer: main

            def run(self):
                self._step += 1

            def start(self):
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                print(self._step)


        def main():
            w = W()
            w.run()
    """
    assert _lint(clean, [SharedStatePass()]) == []
    violated = clean.replace(
        "def _bg(self):\n                print(self._step)",
        "def _bg(self):\n                self._step = 9",
    )
    findings = _lint(violated, [SharedStatePass()])
    assert len(findings) == 1
    assert "single-writer" in findings[0].message
    assert "thread:_bg" in findings[0].message


def test_shared_state_single_writer_unknown_role_is_finding():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = """
        class W:
            def __init__(self):
                self._step = 0  # single-writer: thread:nope
    """
    findings = _lint(src, [SharedStatePass()])
    assert len(findings) == 1
    assert "unknown role" in findings[0].message


def test_shared_state_gil_atomic_and_rmw_violation():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    clean = """
        import threading

        class W:
            def __init__(self):
                self._last = 0.0  # gil-atomic

            def run(self):
                self._last = 1.0

            def start(self):
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                self._last = 2.0


        def main():
            w = W()
            w.run()
    """
    assert _lint(clean, [SharedStatePass()]) == []
    violated = clean.replace("self._last = 2.0", "self._last += 2.0")
    findings = _lint(violated, [SharedStatePass()])
    assert len(findings) == 1
    assert "read-modify-write" in findings[0].message


def test_shared_state_waivable_with_reason():
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = SHARED_SEEDED.replace(
        "        def run(self):\n            self._depth = 1",
        "        def run(self):\n"
        "            # graftlint: allow[shared-state] benign telemetry value;"
        " a torn read costs one stale sample\n"
        "            self._depth = 1",
    )
    assert _lint(src, [SharedStatePass()]) == []


def test_shared_state_full_suite_keeps_waiver_live():
    # The waiver must neither be bypassed nor flagged stale by the full
    # pass suite (the r7/r8 adoption pattern).
    src = SHARED_SEEDED.replace(
        "        def run(self):\n            self._depth = 1",
        "        def run(self):\n"
        "            # graftlint: allow[shared-state] benign telemetry value;"
        " a torn read costs one stale sample\n"
        "            self._depth = 1",
    )
    assert _lint(src, all_passes()) == []


# ---- --threadmap CLI ----

def test_cli_threadmap_dump(repo_lint):
    from collections import Counter

    doc = json.loads(json.dumps(graftlint.VIEWS["threadmap"](repo_lint[1])))
    assert doc["functions_with_role"] > 100
    assert "grpc:MasterServicer" in doc["roles"]
    assert any(
        "Worker._prep_fused_host" in q
        for q in doc["roles"].get("pool:_prep_fused_host", [])
    )
    assert "thread:heartbeat" in doc["roles"]
    kinds = Counter(e["kind"] for e in doc["entries"])
    assert {"thread", "timer", "pool", "grpc", "main", "annotation"} <= set(kinds)
    # the map's size
    assert len(doc["roles"]) > 10 and len(doc["entries"]) > 20
    assert 0 < doc["functions_with_role"] <= doc["functions_total"]
    assert kinds["grpc"] >= 15


def test_shared_state_container_mutation_is_a_write():
    # self._counts[k] += 1 mutates the SHARED CONTAINER through the
    # attribute — the _known_workers-style check-and-set must flag even
    # though no attribute rebind ever happens.
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = """
        import threading

        class W:
            def __init__(self):
                self._counts = {}

            def run(self):
                self._counts["k"] = self._counts.get("k", 0) + 1

            def start(self):
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                self._counts["k"] = 0


        def main():
            w = W()
            w.run()
    """
    findings = _lint(src, [SharedStatePass()])
    assert len(findings) == 1 and "_counts" in findings[0].message
    # And an augmented item assignment is a read-modify-write: illegal
    # under gil-atomic.
    aug = src.replace(
        'self._counts["k"] = self._counts.get("k", 0) + 1',
        'self._counts["k"] += 1',
    ).replace(
        "self._counts = {}",
        "self._counts = {}  # gil-atomic",
    )
    findings = _lint(aug, [SharedStatePass()])
    assert len(findings) == 1
    assert "read-modify-write" in findings[0].message


def test_shared_state_same_role_unlocked_read_not_flagged():
    # The writer role's own unlocked read cannot race writes it is
    # sequenced with: the judgement is per cross-role PAIR, not a global
    # all-site lock intersection.
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._depth = 0

            def run(self):
                with self._lock:
                    self._depth = 1
                print(self._depth)  # same role as the sole writer: safe

            def start(self):
                threading.Thread(target=self._bg, daemon=True).start()

            def _bg(self):
                with self._lock:
                    print(self._depth)


        def main():
            w = W()
            w.run()
    """
    assert _lint(src, [SharedStatePass()]) == []


# ---- jit-shim (v6) ----

JIT_SHIM_SEEDED = """
    import jax
    from jax import jit
    from elasticdl_tpu.common.jax_compat import jit_compiled

    def build(fn):
        return jax.jit(fn)

    def build_shimmed(fn):
        return jit_compiled(fn)
"""

JIT_SHIM_CLEAN = """
    from elasticdl_tpu.common.jax_compat import jit_compiled, jit_donating

    def build(fn):
        return jit_compiled(fn, name="mod.step", expected_variants=1)

    def build_donating(fn):
        return jit_donating(fn, name="mod.train", expected_variants=2)
"""


def test_jit_shim_seeded_and_clean():
    from elasticdl_tpu.analysis.jit_discipline import JitShimPass

    findings = _lint(JIT_SHIM_SEEDED, [JitShimPass()])
    msgs = [f.message for f in findings]
    assert _rules(findings) == {"jit-shim"}
    assert len(findings) == 3  # raw attr, raw import alias, missing name=
    assert any("from jax import jit" in m for m in msgs)
    assert any("raw jax.jit" in m for m in msgs)
    assert any("declares no name=" in m for m in msgs)
    assert _lint(JIT_SHIM_CLEAN, [JitShimPass()]) == []


def test_jit_shim_exempts_the_shim_module():
    from elasticdl_tpu.analysis.jit_discipline import JitShimPass
    import textwrap

    src = SourceFile(
        "elasticdl_tpu/common/jax_compat.py",
        textwrap.dedent("""
            import jax

            def jit_compiled(fun, name=None, expected_variants=1):
                return jax.jit(fun)
        """),
    )
    assert run_passes([src], [JitShimPass()]) == []


# ---- jit-stability (v6) ----

JIT_STABILITY_SEEDED = """
    from elasticdl_tpu.common.jax_compat import jit_compiled

    class Stepper:
        def step(self, x):
            out = jit_compiled(self._fn, name="s.direct")(x)
            return out

        def step2(self, x):
            f = jit_compiled(self._fn, name="s.local")
            return f(x)
"""

JIT_STABILITY_CLEAN = """
    import jax
    from elasticdl_tpu.common.jax_compat import jit_compiled

    _module_step = jit_compiled(lambda x: x, name="s.mod")
    _module_step(1)

    class Stepper:
        def step(self, x):
            if self._fn is None:
                self._fn = jit_compiled(self._impl, name="s.memo")
            return self._fn(x)

        def build(self):
            return jit_compiled(self._impl, name="s.builder")

        def bucketed(self, shapes):
            for n in shapes:
                self._cache[n] = jit_compiled(self._impl, name="s.bucket")
"""


def test_jit_stability_seeded_and_clean():
    from elasticdl_tpu.analysis.jit_discipline import JitStabilityPass

    findings = _lint(JIT_STABILITY_SEEDED, [JitStabilityPass()])
    assert _rules(findings) == {"jit-stability"}
    assert len(findings) == 2  # direct-invoke + local-bound-and-called
    assert any("created and invoked in one expression" in f.message
               for f in findings)
    assert any("bound to local 'f'" in f.message for f in findings)
    # Module-level bind, self-attr memo, builder return, cache subscript:
    # every legal ownership shape is silent.
    assert _lint(JIT_STABILITY_CLEAN, [JitStabilityPass()]) == []


def test_jit_stability_waivable_with_reason():
    from elasticdl_tpu.analysis.jit_discipline import JitStabilityPass

    src = """
        from elasticdl_tpu.common.jax_compat import jit_compiled

        def probe(fn, x):
            # graftlint: allow[jit-stability] one-shot probe: runs once per process
            f = jit_compiled(fn, name="p.probe")
            return f(x)
    """
    assert _lint(src, [JitStabilityPass()]) == []


# ---- transfer-discipline (v6) ----

TRANSFER_SEEDED = """
    import numpy as np

    class Trainer:
        # jit-boundary: returns device buffers off the compiled step
        def step(self, state, batch):
            return state

    class Worker:
        def __init__(self):
            self.trainer = Trainer()

        # hot-path
        def loop(self, state, batch):
            out = self.trainer.step(state, batch)
            return float(out)
"""

TRANSFER_CLEAN = """
    import numpy as np

    class Trainer:
        # jit-boundary
        def step(self, state, batch):
            return state

    class Worker:
        def __init__(self):
            self.trainer = Trainer()

        # hot-path
        def loop(self, state, batch):
            out = self.trainer.step(state, batch)
            with self.phases.phase("step_wait"):
                host = float(out)  # accounted: the deliberate drain
            return host

        def offline_report(self, state, batch):
            out = self.trainer.step(state, batch)
            return float(out)  # not hot-path: scoping is the point
"""


def test_transfer_discipline_seeded_and_clean():
    from elasticdl_tpu.analysis.jit_discipline import TransferDisciplinePass

    findings = _lint(TRANSFER_SEEDED, [TransferDisciplinePass()])
    assert _rules(findings) == {"transfer-discipline"}
    assert len(findings) == 1
    assert "float() over a jit-boundary value" in findings[0].message
    assert _lint(TRANSFER_CLEAN, [TransferDisciplinePass()]) == []


def test_transfer_discipline_propagates_through_helpers():
    # The wrapped transfer the per-function view cannot see: a hot-path
    # function reaching np.asarray-of-step-output through a helper — the
    # blocking-propagation shape, with the witness chain in the message.
    from elasticdl_tpu.analysis.jit_discipline import TransferDisciplinePass

    src = """
        import numpy as np

        class Worker:
            # jit-boundary
            def step(self, state):
                return state

            def _settle(self, state):
                out = self.step(state)
                return np.asarray(out)

            # hot-path
            def loop(self, state):
                return self._settle(state)
    """
    findings = _lint(src, [TransferDisciplinePass()])
    assert len(findings) == 1
    f = findings[0]
    assert "callee chain materializes" in f.message
    assert "np.asarray" in f.message  # witness down to the primitive


def test_transfer_discipline_infers_boundary_through_returns():
    # run_step returns self.step(...): boundary-ness propagates through
    # the return fixpoint, so only the innermost function needs the
    # annotation (the Trainer.run_* adoption shape).
    from elasticdl_tpu.analysis.jit_discipline import TransferDisciplinePass

    src = """
        class Worker:
            # jit-boundary
            def step(self, state):
                return state

            def run_step(self, state):
                return self.step(state)

            # hot-path
            def loop(self, state):
                out = self.run_step(state)
                return out.item()
    """
    findings = _lint(src, [TransferDisciplinePass()])
    assert len(findings) == 1
    assert ".item() materializes" in findings[0].message


def test_transfer_discipline_jit_bound_local_flow():
    # out = step(x) where step came from jit_compiled: jit-flow without
    # any annotation — the syntactic half of the boundary model.
    from elasticdl_tpu.analysis.jit_discipline import TransferDisciplinePass

    src = """
        from elasticdl_tpu.common.jax_compat import jit_compiled

        # hot-path
        def loop(fn, x):
            step = jit_compiled(fn, name="m.step")
            out = step(x)
            return out.tolist()
    """
    findings = _lint(src, [TransferDisciplinePass()])
    assert len(findings) == 1
    assert ".tolist() materializes" in findings[0].message


def test_transfer_discipline_waived_primitive_does_not_propagate():
    from elasticdl_tpu.analysis.jit_discipline import TransferDisciplinePass

    src = """
        import numpy as np

        class Worker:
            # jit-boundary
            def step(self, state):
                return state

            def _settle(self, state):
                out = self.step(state)
                # graftlint: allow[transfer-discipline] the settle IS the product
                return np.asarray(out)

            # hot-path
            def loop(self, state):
                return self._settle(state)
    """
    assert _lint(src, [TransferDisciplinePass()]) == []


def test_transfer_discipline_except_handler_exempt():
    from elasticdl_tpu.analysis.jit_discipline import TransferDisciplinePass

    src = """
        class Worker:
            # jit-boundary
            def step(self, state):
                return state

            # hot-path
            def loop(self, state):
                out = self.step(state)
                try:
                    return out
                except Exception:
                    return float(out)  # error path: off the hot path
    """
    assert _lint(src, [TransferDisciplinePass()]) == []


# ---- thread-map: functools.partial targets (v6 satellite) ----

def test_thread_map_resolves_partial_targets():
    from elasticdl_tpu.analysis.thread_map import shared_thread_map

    src = SourceFile("mod.py", textwrap.dedent("""
        import functools
        import threading
        from functools import partial

        class W:
            def start(self, pool):
                t = threading.Thread(
                    target=functools.partial(self._beat, 1), daemon=True
                )
                t.start()
                pool.submit(partial(self._load, "k"))

            def _beat(self, n):
                pass

            def _load(self, key):
                pass
    """))
    tmap = shared_thread_map([src])
    roles = tmap.dump()["roles"]
    assert "mod:W._beat" in roles.get("thread:_beat", [])
    assert "mod:W._load" in roles.get("pool:_load", [])


def test_shared_state_sees_through_partial_spawn():
    # The muted-check regression the satellite fixes: a racy write inside
    # a partial-wrapped thread target must now be a shared-state finding.
    from elasticdl_tpu.analysis.shared_state import SharedStatePass

    src = """
        import functools
        import threading

        class W:
            def __init__(self):
                self._hits = 0

            def start(self):
                threading.Thread(
                    target=functools.partial(self._bump, 1), daemon=True
                ).start()

            def _bump(self, n):
                self._hits += n

            def report(self):
                print(self._hits)

        def main():
            w = W()
            w.start()
            w.report()
    """
    findings = _lint(src, [SharedStatePass()])
    assert len(findings) == 1
    assert "_hits" in findings[0].message


# ---- declared_sites (the static budget table) ----

def test_declared_sites_harvest():
    from elasticdl_tpu.analysis.jit_discipline import declared_sites

    src = SourceFile("mod.py", textwrap.dedent("""
        from elasticdl_tpu.common.jax_compat import jit_compiled, jit_donating

        def a(fn):
            return jit_compiled(fn, name="m.step", expected_variants=2)

        def b(fn):
            return jit_donating(fn, name="m.step", expected_variants=1)

        def c(fn, n):
            return jit_compiled(fn, name="m.buckets", expected_variants=n)

        def d(fn, variant_budget=3):
            return jit_compiled(
                fn, name="m.param", expected_variants=variant_budget)
    """))
    sites = declared_sites([src])
    assert sites["m.step"]["budget"] == 2  # max across sites
    assert len(sites["m.step"]["sites"]) == 2
    assert not sites["m.step"]["dynamic"]
    assert sites["m.buckets"]["budget"] is None  # unresolvable expression
    # expected_variants=<param>: resolved through the parameter default
    # (the trainer-builder shape), marked dynamic since callers may
    # override upward.
    assert sites["m.param"]["budget"] == 3 and sites["m.param"]["dynamic"]


# ---- durable-write-discipline (v7) ----

DURABLE_SEEDED = """
    import json
    import os

    JOURNAL_FILENAME = "master_journal.wal"  # durable-file

    def persist(directory, rec):
        path = os.path.join(directory, JOURNAL_FILENAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, path)
"""

DURABLE_CLEAN = """
    import os

    from elasticdl_tpu.common import durable

    JOURNAL_FILENAME = "master_journal.wal"  # durable-file

    def persist(directory, rec):
        path = os.path.join(directory, JOURNAL_FILENAME)
        durable.atomic_publish_json(path, rec)
"""


def test_durable_write_seeded_vs_clean():
    findings = _lint(DURABLE_SEEDED, [DurableWriteDisciplinePass()])
    # three independent violations in one hand-rolled publish: the
    # hand-rolled temp name, the raw write-mode open of the tainted local,
    # and the raw os.replace.
    assert _rules(findings) == {"durable-write-discipline"}
    assert len(findings) == 3
    assert _lint(DURABLE_CLEAN, [DurableWriteDisciplinePass()]) == []


DURABLE_ATTR_SEEDED = """
    import os

    REGISTRY_FILENAME = "pod_registry.json"  # durable-file

    class Registry:
        def __init__(self, directory):
            self._path = os.path.join(directory, REGISTRY_FILENAME)

        def save(self, blob):
            fd = os.open(self._path, os.O_WRONLY | os.O_CREAT)
            os.write(fd, blob)
            os.close(fd)
"""


def test_durable_write_taint_flows_through_self_attr():
    # The path reaches the write as self._path, assigned from the
    # constant in __init__: the class-wide attr taint must carry it to
    # the write-flavored os.open in save().
    findings = _lint(DURABLE_ATTR_SEEDED, [DurableWriteDisciplinePass()])
    assert _rules(findings) == {"durable-write-discipline"}
    assert any("os.open" in f.message for f in findings)


def test_hand_rolled_rename_flagged_without_constants():
    # os.replace/os.rename are unconditional: every rename IS a publish
    # commit and belongs in durable.py, tainted operands or not.
    findings = _lint(
        """
        import os

        def swap(a, b):
            os.rename(a, b)
        """,
        [DurableWriteDisciplinePass()],
    )
    assert _rules(findings) == {"durable-write-discipline"}


def test_durable_module_itself_exempt():
    src = """
        import os

        def commit(tmp, path):
            os.replace(tmp, path)
    """
    assert (
        lint_text(
            textwrap.dedent(src),
            [DurableWriteDisciplinePass()],
            path="elasticdl_tpu/common/durable.py",
        )
        == []
    )
    # the same text anywhere else is a violation
    assert _lint(src, [DurableWriteDisciplinePass()]) != []


def test_durable_write_waiver_and_stale():
    waived = """
        import os

        JOURNAL_FILENAME = "j.wal"  # durable-file

        def persist(directory, data):
            path = os.path.join(directory, JOURNAL_FILENAME)
            # graftlint: allow[durable-write-discipline] migration staged for next PR
            with open(path, "w") as f:
                f.write(data)
    """
    assert _lint(waived, [DurableWriteDisciplinePass()]) == []
    stale = """
        from elasticdl_tpu.common import durable

        JOURNAL_FILENAME = "j.wal"  # durable-file

        def persist(path, data):
            # graftlint: allow[durable-write-discipline] nothing here needs this
            durable.atomic_publish(path, data)
    """
    assert _rules(_lint(stale, [DurableWriteDisciplinePass()])) == {
        "stale-waiver"
    }


# ---- recovery-read-discipline (v7) ----

RECOVERY_SEEDED = """
    import json

    # recovery-path
    def load(path):
        with open(path) as f:
            return json.load(f)
"""

RECOVERY_CLEAN = """
    from elasticdl_tpu.common import durable

    # recovery-path
    def load(path):
        records, torn = durable.read_wal(path)
        return records
"""


def test_recovery_read_seeded_vs_clean():
    findings = _lint(RECOVERY_SEEDED, [RecoveryReadDisciplinePass()])
    assert _rules(findings) == {"recovery-read-discipline"}
    assert _lint(RECOVERY_CLEAN, [RecoveryReadDisciplinePass()]) == []


def test_raw_read_of_durable_path_outside_recovery_fn():
    # Reading a durable file from an UNANNOTATED function is the other
    # half: crash states (torn tail, non-compliant tear) reach every
    # reader, so every reader must route through the tolerant API.
    findings = _lint(
        """
        import os

        REGISTRY_FILENAME = "pod_registry.json"  # durable-file

        def peek(directory):
            path = os.path.join(directory, REGISTRY_FILENAME)
            with open(path) as f:
                return f.read()
        """,
        [RecoveryReadDisciplinePass()],
    )
    assert _rules(findings) == {"recovery-read-discipline"}


def test_v7_passes_registered():
    kinds = {type(p) for p in all_passes()}
    assert DurableWriteDisciplinePass in kinds
    assert RecoveryReadDisciplinePass in kinds


def test_cli_durables_dump(repo_lint):
    doc = json.loads(json.dumps(graftlint.VIEWS["durables"](repo_lint[1])))
    assert {
        "JOURNAL_FILENAME", "MANIFEST_NAME", "METRICS_FILENAME",
        "PROGRESS_FILENAME", "REGISTRY_FILENAME",
    } <= set(doc)
    j = doc["JOURNAL_FILENAME"]
    assert j["file"] == "master_journal.wal"
    assert any(w.endswith(" rotate") for w in j["writers"])
    assert any("read_journal" in r for r in j["recovery_readers"])


# ---- wire-discipline (v8) ----

# The schema header every v8 fixture shares: the pass EVALUATES these
# literals (never imports them), so the fixture only has to parse.
WIRE_HEADER = """
    from elasticdl_tpu.common.rpc import JsonRpcClient, MessageSchema

    _STR = (str,)
    _INT = (int,)
    _DICT = (dict,)

    PROTOCOL_VERSION = 1

    MASTER_SCHEMAS = {
        "Ping": MessageSchema(
            required={"worker_id": _STR}, optional={"lease": _INT},
            since={"lease": 9},
        ),
    }
    for _method_schema in MASTER_SCHEMAS.values():
        _method_schema.optional.setdefault("trace", _DICT)
        _method_schema.since.setdefault("trace", 12)

    MASTER_RESPONSE_SCHEMAS = {
        "Ping": MessageSchema(
            required={"version": _INT}, optional={"eta": _INT},
            since={"eta": 9},
        ),
    }
"""

WIRE_SENDER_SEEDED = WIRE_HEADER + """
    def poll(client, wid):
        return client.call("Ping", {"worker_id": wid, "leese": 1})
"""

WIRE_SENDER_CLEAN = WIRE_HEADER + """
    def poll(client, wid):
        payload = {"worker_id": wid}
        payload["lease"] = 4
        payload.setdefault("trace", {})
        return client.call("Ping", payload)
"""


def test_wire_sender_undeclared_key_seeded_vs_clean():
    findings = _lint(WIRE_SENDER_SEEDED, [WireDisciplinePass()])
    assert _rules(findings) == {"wire-discipline"}
    assert len(findings) == 1
    assert "'leese'" in findings[0].message
    # The clean twin also proves the tracked-local grammar (literal
    # assign + const-subscript grow + setdefault) and the envelope-loop
    # evaluation ("trace" only exists via the setdefault loop).
    assert _lint(WIRE_SENDER_CLEAN, [WireDisciplinePass()]) == []


WIRE_RECEIVER_SEEDED = WIRE_HEADER + """
    class Servicer:
        def __init__(self):
            self._handlers = {"Ping": self._ping}

        def _ping(self, req):
            return {"version": req["lease"]}
"""

WIRE_RECEIVER_CLEAN = WIRE_HEADER + """
    class Servicer:
        def __init__(self):
            self._handlers = {"Ping": self._ping}

        def _ping(self, req):
            wid = req["worker_id"]
            return {"version": int(req.get("lease", 1)), "w": wid}
"""


def test_wire_receiver_optional_subscript_seeded_vs_clean():
    findings = _lint(WIRE_RECEIVER_SEEDED, [WireDisciplinePass()])
    assert _rules(findings) == {"wire-discipline"}
    assert len(findings) == 1
    assert "OPTIONAL" in findings[0].message
    assert ".get()" in findings[0].message
    # Clean twin: REQUIRED subscript is legal, optional via .get().
    # NOTE the response dict's "w" key is NOT judged — only reads are.
    assert _lint(WIRE_RECEIVER_CLEAN, [WireDisciplinePass()]) == []


WIRE_RECEIVER_HELPER_SEEDED = WIRE_HEADER + """
    class Servicer:
        def __init__(self):
            self._handlers = {"Ping": self._ping}

        def _ping(self, req):
            self._bank(req)
            return {"version": 1}

        def _bank(self, msg):
            return msg["trace"]
"""


def test_wire_receiver_helper_propagation():
    # The message param's methods flow through the same-file helper call:
    # the optional-subscript finding lands in _bank, not _ping.
    findings = _lint(WIRE_RECEIVER_HELPER_SEEDED, [WireDisciplinePass()])
    assert _rules(findings) == {"wire-discipline"}
    assert "'trace'" in findings[0].message


WIRE_RESPONSE_SEEDED = WIRE_HEADER + """
    def poll(client, wid):
        resp = client.call("Ping", {"worker_id": wid})
        return resp["eta"]
"""

WIRE_RESPONSE_CLEAN = WIRE_HEADER + """
    def poll(client, wid):
        resp = client.call("Ping", {"worker_id": wid})
        return resp["version"], resp.get("eta")
"""


def test_wire_client_response_subscript_seeded_vs_clean():
    findings = _lint(WIRE_RESPONSE_SEEDED, [WireDisciplinePass()])
    assert _rules(findings) == {"wire-discipline"}
    assert "response" in findings[0].message
    assert _lint(WIRE_RESPONSE_CLEAN, [WireDisciplinePass()]) == []


def test_wire_discipline_waiver_and_stale():
    waived = WIRE_HEADER + """
    def poll(client, wid):
        # graftlint: allow[wire-discipline] probing the master's unknown-field counter
        return client.call("Ping", {"worker_id": wid, "probe": 1})
    """
    assert _lint(waived, [WireDisciplinePass()]) == []
    stale = WIRE_HEADER + """
    def poll(client, wid):
        # graftlint: allow[wire-discipline] nothing here needs this
        return client.call("Ping", {"worker_id": wid})
    """
    assert _rules(_lint(stale, [WireDisciplinePass()])) == {"stale-waiver"}


# ---- wire-evolution (v8) ----


def _wire_sources(src: str):
    return [SourceFile("fixture.py", textwrap.dedent(src))]


def test_wire_evolution_clean_against_matching_lock():
    lock = wire_fingerprint(_wire_sources(WIRE_HEADER))
    assert lock["protocol_version"] == 1
    assert "request:Ping" in lock["methods"]
    # since from both the literal and the envelope loop evaluated:
    assert lock["methods"]["request:Ping"]["since"] == {
        "lease": 9, "trace": 12,
    }
    assert _lint(WIRE_HEADER, [WireEvolutionPass(lock_data=lock)]) == []


def test_wire_evolution_breaking_drift_without_bump():
    lock = wire_fingerprint(_wire_sources(WIRE_HEADER))
    # The lock remembers a field the code no longer declares (= the diff
    # REMOVED it) ...
    lock["methods"]["request:Ping"]["optional"]["gone"] = ["str"]
    findings = _lint(WIRE_HEADER, [WireEvolutionPass(lock_data=lock)])
    assert _rules(findings) == {"wire-evolution"}
    assert any("removed field 'gone'" in f.message for f in findings)
    assert any("bump PROTOCOL_VERSION" in f.message for f in findings)
    # ... and a type change / new REQUIRED field are the other two
    # breaking classes.
    lock2 = wire_fingerprint(_wire_sources(WIRE_HEADER))
    lock2["methods"]["request:Ping"]["required"]["worker_id"] = ["int"]
    del lock2["methods"]["response:Ping"]["required"]["version"]
    findings2 = _lint(WIRE_HEADER, [WireEvolutionPass(lock_data=lock2)])
    msgs = " | ".join(f.message for f in findings2)
    assert "changed accepted types" in msgs
    assert "added REQUIRED field 'version'" in msgs


def test_wire_evolution_drift_with_version_bump():
    bumped = WIRE_HEADER.replace(
        "PROTOCOL_VERSION = 1", "PROTOCOL_VERSION = 2"
    )
    stale_lock = wire_fingerprint(_wire_sources(WIRE_HEADER))
    # Bumped but the lock still records v1: ONE finding — regenerate —
    # regardless of how breaking the drift is.
    findings = _lint(bumped, [WireEvolutionPass(lock_data=stale_lock)])
    assert len(findings) == 1
    assert "regenerate" in findings[0].message
    # Bump + regenerated lock in the same diff: clean by construction.
    fresh_lock = wire_fingerprint(_wire_sources(bumped))
    assert _lint(bumped, [WireEvolutionPass(lock_data=fresh_lock)]) == []


def test_wire_evolution_additive_drift_asks_regenerate_only():
    grown = WIRE_HEADER.replace(
        'optional={"lease": _INT}', 'optional={"lease": _INT, "tags": _DICT}'
    )
    lock = wire_fingerprint(_wire_sources(WIRE_HEADER))
    findings = _lint(grown, [WireEvolutionPass(lock_data=lock)])
    assert len(findings) == 1
    assert "additive" in findings[0].message
    assert "bump" not in findings[0].message


def test_wire_evolution_silent_on_schema_free_fixtures():
    # Fixture files with no *_SCHEMAS tables must not drag the repo lock
    # into every other test's lint run.
    assert _lint(LOCK_SEEDED, [WireEvolutionPass(lock_data={})]) == []


def test_wire_lock_matches_committed_schemas():
    # The committed lock IS the current fingerprint — wire-evolution
    # judges the real repo against it in test_repo_lints_clean, so a
    # schema edit without --update-wire-lock fails tier-1 twice over.
    from elasticdl_tpu.analysis.core import load_sources

    sources, errs = load_sources(
        [os.path.join(REPO, "elasticdl_tpu", "common", "rpc.py")],
        rel_to=REPO,
    )
    assert errs == []
    with open(os.path.join(REPO, "artifacts", "wire_schema.lock.json")) as f:
        lock = json.load(f)
    assert lock == wire_fingerprint(sources)


def test_v8_passes_registered():
    kinds = {type(p) for p in all_passes()}
    assert WireDisciplinePass in kinds
    assert WireEvolutionPass in kinds


def test_cli_wire_dump(repo_lint):
    doc = json.loads(json.dumps(graftlint.VIEWS["wire"](repo_lint[1])))
    assert doc["protocol_version"] == 1
    methods = doc["methods"]
    assert {"GetTask", "ReportTaskResult", "Heartbeat", "Predict"} <= set(
        methods
    )
    gt = methods["GetTask"]
    assert gt["request"]["required"] == {"worker_id": ["str"]}
    assert gt["response"]["required"] == {"finished": ["bool"]}
    # Both resolution paths: the master's method_table form and the
    # serving tier's dict-literal wiring.
    assert any("servicer.py" in r for r in gt["receivers"])
    assert any(
        "serving/server.py" in r for r in methods["Predict"]["receivers"]
    )
    assert gt["senders"], "worker GetTask call site must resolve"
