"""chip_smoke.py's control flow, on the CPU: it refuses anything that is
not the chip, names the phase that failed, keeps stdout empty unless every
phase passed, and its rehearsal exercises every phase without ever printing
the pass marker.  The chip run itself is the driver's (and the builder's)
to make."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(argv, cwd=REPO, **env_overrides):
    env = dict(os.environ)
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )


def test_inherited_cpu_platform_cannot_redirect_the_chip_run():
    """JAX_PLATFORMS=cpu in the caller's environment (this sandbox exports
    it): the children still demand the TPU, jax fails hard on the missing
    chip, and the run fails at the first chip-touching phase."""
    out = _run([SCRIPT], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout == ""
    assert "FAILED phase=kernels" in out.stderr
    assert "Unable to initialize backend 'tpu'" in out.stderr


def test_alone_in_a_directory_it_fails_before_starting_anything(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run([str(tmp_path / "chip_smoke.py")], cwd=str(tmp_path))
    assert out.returncode != 0
    assert out.stdout == ""
    assert "FAILED phase=layout" in out.stderr
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def test_unbuildable_native_library_fails_the_run():
    out = _run([SCRIPT, "--rehearsal"], CXX="false")
    assert out.returncode != 0
    assert out.stdout == ""
    assert "FAILED phase=native_build" in out.stderr


def test_rehearsal_exercises_every_phase_and_never_prints_the_pass_marker():
    # Two fake devices: the multi-device branches (ring attention, a real
    # mesh under the ragged routing) without 8 devices' compile time.
    out = _run(
        [SCRIPT, "--rehearsal"],
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(line) for line in out.stdout.splitlines()]
    assert [r.get("phase") for r in lines[:-1]] == [
        "native_build", "kernels", "data", "deepfm", "deepfm_resume",
        "transformer",
    ]
    phases = {r["phase"]: r for r in lines[:-1]}
    for name in ("kernels", "deepfm", "deepfm_resume", "transformer"):
        assert phases[name]["device"] == {
            "platform": "cpu", "device_kind": "cpu", "count": 2,
            "jax": phases[name]["device"]["jax"],
        }
        cache = phases[name]["compile_cache"]
        # conftest placed the cache; every process of the run used it.
        assert cache["dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert cache["hits"] + cache["misses"] > 0
    first, resume = phases["deepfm"], phases["deepfm_resume"]
    assert first["native_lib"] and first["embedding_route"] == "dense"
    assert first["steps"] in first["checkpoints_on_disk"]
    assert resume["joined_from_checkpoint_step"] == first["steps"]
    assert resume["steps"] > first["steps"]
    # The resumed job re-jits the same program: served from the cache.
    assert resume["compile_cache"]["misses"] == 0
    assert phases["transformer"]["attention_path"] == "xla-ring"
    assert phases["kernels"]["ragged_lookup"]["impl"] == "ragged_emulated"
    summary = lines[-1]
    assert summary["rehearsal"] is True
    assert "ok" not in summary and '"ok"' not in out.stdout
