"""common/platform.py: where compiles are cached, what device answered.

The cache directory and the platform are process-global jax config read at
import, so each rule is checked in a fresh interpreter.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh(code: str, cwd: str = REPO, **env_overrides) -> subprocess.CompletedProcess:
    env = dict(os.environ, PYTHONPATH=REPO)
    for key, value in env_overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


_REPORT_CACHE_DIR = """
import jax
updates = []
real_update = jax.config.update
def recording_update(name, value):
    updates.append(name)
    return real_update(name, value)
jax.config.update = recording_update
from elasticdl_tpu.common.platform import compile_cache_stats, enable_compile_cache
enable_compile_cache()
import json
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "stats_dir": compile_cache_stats()["dir"],
    "min_s": jax.config.jax_persistent_cache_min_compile_time_secs,
    "updates": updates,
}))
"""


def test_cache_dir_from_the_environment_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "placed_cache")
    out = _fresh(_REPORT_CACHE_DIR, JAX_COMPILATION_CACHE_DIR=placed)
    assert out.returncode == 0, out.stderr
    report = json.loads(out.stdout.splitlines()[-1])
    assert report["dir"] == placed == report["stats_dir"]
    # JAX read the variable itself; no code path passed a directory.
    assert "jax_compilation_cache_dir" not in report["updates"]
    assert report["min_s"] == 0.0


def test_default_cache_dir_is_fixed_inside_the_checkout(tmp_path):
    from elasticdl_tpu.common.platform import DEFAULT_COMPILE_CACHE_DIR

    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    seen = set()
    for i in range(2):  # another $HOME, another cwd, another pid
        home, cwd = tmp_path / f"home{i}", tmp_path / f"cwd{i}"
        home.mkdir()
        cwd.mkdir()
        out = _fresh(
            _REPORT_CACHE_DIR, cwd=str(cwd), HOME=str(home),
            JAX_COMPILATION_CACHE_DIR=None,
        )
        assert out.returncode == 0, out.stderr
        seen.add(json.loads(out.stdout.splitlines()[-1])["dir"])
    assert seen == {DEFAULT_COMPILE_CACHE_DIR}


_COMPILE_ONCE = """
import jax, jax.numpy as jnp, json
from elasticdl_tpu.common.platform import compile_cache_stats, enable_compile_cache
enable_compile_cache()
def smoke_fn(x):
    return jnp.sin(x) @ x.T
jax.block_until_ready(jax.jit(smoke_fn)(jnp.ones((64, 64))))
print(json.dumps(compile_cache_stats()))
"""


def test_second_process_reports_cache_hits_not_fresh_compiles(tmp_path):
    cache = str(tmp_path / "cache")
    first, second = (
        json.loads(
            _fresh(_COMPILE_ONCE, JAX_COMPILATION_CACHE_DIR=cache)
            .stdout.splitlines()[-1]
        )
        for _ in range(2)
    )
    assert first["functions"]["jit(smoke_fn)"]["cache"] == "miss"
    assert first["misses"] >= 1 and first["hits"] == 0
    assert second["functions"]["jit(smoke_fn)"]["cache"] == "hit"
    assert second["hits"] >= 1 and second["misses"] == 0
    assert os.listdir(cache)  # every entry landed where the variable said


#: a process of a tier-1 run: ``tests/conftest.py``'s rule for the compile
#: cache at import, one program, the session's end
_A_TIER1_PROCESS = (
    "import os, sys\nsys.path.insert(0, 'tests')\nimport conftest\n"
    + _COMPILE_ONCE
    + "conftest.pytest_sessionfinish(None, 0)\n"
    "print(json.dumps({'stats': compile_cache_stats(), 'there_at_the_end': os.path.isdir(compile_cache_stats()['dir'])}))\n"
)


def test_two_processes_of_a_run_share_the_cache_one_of_them_made(tmp_path):
    """What an xdist worker or a child pytest inherits: a directory that
    exists and carries the rule's prefix.  It is kept, the second process
    reads what the first compiled, and neither removes it (its maker does)."""
    made = tmp_path / "edl_tier1_jax_cache_of_the_run"
    made.mkdir()
    first, second = (
        json.loads(_fresh(_A_TIER1_PROCESS, JAX_COMPILATION_CACHE_DIR=str(made)).stdout.splitlines()[-1])
        for _ in range(2)
    )
    assert first["stats"]["dir"] == second["stats"]["dir"] == str(made)
    assert first["stats"]["functions"]["jit(smoke_fn)"]["cache"] == "miss"
    assert second["stats"]["functions"]["jit(smoke_fn)"]["cache"] == "hit"
    assert first["there_at_the_end"] and second["there_at_the_end"] and os.listdir(made)


def test_a_cache_directory_the_rule_did_not_make_is_not_adopted(tmp_path):
    """A developer's own ``JAX_COMPILATION_CACHE_DIR``: the process makes a
    throwaway directory instead, and removes it when its session ends."""
    own = tmp_path / "a_developers_cache"
    own.mkdir()
    out = json.loads(_fresh(_A_TIER1_PROCESS, JAX_COMPILATION_CACHE_DIR=str(own)).stdout.splitlines()[-1])
    used = out["stats"]["dir"]
    assert used != str(own) and os.path.basename(used).startswith("edl_tier1_jax_cache_")
    assert out["stats"]["functions"]["jit(smoke_fn)"]["cache"] == "miss"
    assert not out["there_at_the_end"] and not os.path.exists(used) and os.listdir(own) == []


def test_the_runs_longest_serial_block_is_collected_first_with_nothing_that_weighs_behind_it(request):
    """``tests/conftest.py``'s order (``_COLLECTED_FIRST``): wherever the lowering files are collected with other
    files the first opens the collection, whole and in its own order, and the 24th of the collection that xdist hands
    the first worker to begin with holds only it and cases of the file named second; the second lowering file lies
    WHOLE inside the second 24th, the second worker's (so added cases cannot silently put both blocks back on one
    worker), and what fills that 24th up behind it is the light file named last."""
    from conftest import _COLLECTED_FIRST

    first, light, second, behind = _COLLECTED_FIRST
    names = [item.path.name for item in request.session.items]
    if first not in names or len(set(names)) < 24:
        pytest.skip("not a whole run: nothing to order")
    deal = len(names) // 24
    n_first = names.count(first)
    assert set(names[:n_first]) == {first}
    assert set(names[n_first:deal]) == {light}
    at, n_second = names.index(second), names.count(second)
    assert set(names[at:at + n_second]) == {second} and deal <= at and at + n_second <= 2 * deal, (deal, at, n_second)
    assert set(names[deal:at]) == {light} and set(names[at + n_second:2 * deal]) <= {behind}


def test_device_summary_names_what_answered():
    import jax

    from elasticdl_tpu.common.platform import (
        device_bytes_in_use,
        device_summary,
    )

    summary = device_summary()
    assert summary == {
        "platform": "cpu",
        "device_kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
        "local_count": jax.local_device_count(),
        "jax": jax.__version__,
    }
    # XLA:CPU reports no memory stats; the helper says so per device.
    assert device_bytes_in_use() == [None] * jax.local_device_count()


@pytest.mark.parametrize(
    "module", ["elasticdl_tpu.ops.embedding", "elasticdl_tpu.ops.flash_attention"]
)
def test_ops_modules_import_first_in_a_fresh_interpreter(module):
    """ops.embedding -> parallel.collectives -> parallel/__init__ -> trainer
    -> ops.embedding was a circular import whenever ops came first (it kept
    tools/ragged_smoke.py from importing at all)."""
    out = _fresh(f"import {module}")
    assert out.returncode == 0, out.stderr


@pytest.mark.parametrize("case, platforms, refusals, deadline_s", [
    ("free_at_once", None, 0, 30.0),
    ("opens_after_refusals", None, 3, 30.0),
    ("asked_for_the_tpu_by_name", "tpu,cpu", 3, 30.0),
    ("never_opens", None, 10**9, 0.4),
    ("no_group_directory", None, 0, 30.0),
    ("told_to_stay_on_the_cpu", "cpu", 10**9, 30.0),
])
def test_a_worker_waits_for_chips_an_ended_process_still_holds(tmp_path, monkeypatch, case, platforms, refusals, deadline_s):
    """A fake ``/dev/vfio``: two numbered groups that refuse ``open`` with
    EBUSY a number of times (what a relaunched worker finds for some
    seconds after the kill of the one before it: PR 43), and the
    container's own entry ``vfio``, which is no chip's and is never tried."""
    import errno

    from elasticdl_tpu.common import platform

    vfio = tmp_path / "vfio"
    if case != "no_group_directory":
        vfio.mkdir()
        for name in ("0", "1", "vfio"):
            (vfio / name).write_text("")
    tried, real_open = {}, os.open

    def fake_open(path, flags, *args, **kwargs):
        if str(path).startswith(str(vfio) + os.sep):
            name = os.path.basename(path)
            tried[name] = tried.get(name, 0) + 1
            assert flags == os.O_RDWR
            if tried[name] <= refusals:
                raise OSError(errno.EBUSY, "Device or resource busy", str(path))
        return real_open(path, flags, *args, **kwargs)

    monkeypatch.setattr(os, "open", fake_open)
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    waited, still_busy = platform.wait_for_chips(str(vfio), deadline_s)
    if case in ("opens_after_refusals", "asked_for_the_tpu_by_name"):
        assert tried == {"0": 4, "1": 4} and 0.5 <= waited < 10.0 and still_busy == []
    elif case == "never_opens":
        assert deadline_s <= waited < 5.0 and still_busy == [str(vfio / "0"), str(vfio / "1")]
    elif case == "free_at_once":
        assert tried == {"0": 1, "1": 1} and waited < 0.25 and still_busy == []
    else:
        assert tried == {} and (waited, still_busy) == (0.0, [])
