"""What PR 32 added to the harness: a traced run waits until the chips the
job held can be opened again (``benchmark/job.py``), the harness judges the
configuration's named ``checks`` on the reference child's readings beside
the loss (``benchmark/run.py``), and the first user of that hook, the router
as the model runs it (``benchmark/configs/olmoe_1b_7b_l1_reference.py``).
CPU only."""

from __future__ import annotations

import errno
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import job  # noqa: E402
import resolve  # noqa: E402
import run as bench_run  # noqa: E402

# ------------------------------------------------ Job.stop and the chips

#: A job that opens the groups named on its command line (as the worker
#: holds its chips' groups), says so, and sleeps.
HOLDER = "import sys, time; held = [open(p, 'r+') for p in sys.argv[2:]]; open(sys.argv[1], 'w').close(); time.sleep(120)"


@pytest.mark.parametrize("case,platform,holds,refusals,deadline_s", [
    ("opens_after_refusals", "tpu", ["0", "1"], 3, 30.0),
    ("only_the_groups_the_job_held", "tpu", ["1"], 3, 30.0),
    ("none_seen_held_so_every_numbered_group", "tpu", [], 3, 30.0),
    ("no_group_directory", "tpu", [], 0, 30.0),
    ("never_opens", "tpu", ["0", "1"], 10**9, 0.4),
    ("not_a_tpu", "cpu", ["0", "1"], 10**9, 30.0),
])
def test_the_chips_are_waited_for_after_stop_and_only_when_asked(tmp_path, monkeypatch, capfd, case, platform, holds, refusals, deadline_s):
    """A fake ``/dev/vfio``: two numbered groups that refuse ``open`` with
    EBUSY a number of times, and the container's own entry ``vfio``, which
    is no chip's and is never tried.  ``stop`` kills and waits for the
    processes only; ``wait_for_chips`` (what a traced run calls before its
    reference child) polls the groups the job held."""
    vfio = tmp_path / "vfio"
    if case != "no_group_directory":
        vfio.mkdir()
        for name in ("0", "1", "vfio"):
            (vfio / name).write_text("")
    tried, real_open = {}, os.open

    def fake_open(path, flags, *args, **kwargs):
        if str(path).startswith(str(vfio) + os.sep):
            tried[os.path.basename(path)] = tried.get(os.path.basename(path), 0) + 1
            assert flags == os.O_RDWR
            if tried[os.path.basename(path)] <= refusals:
                raise OSError(errno.EBUSY, "Device or resource busy", str(path))
        return real_open(path, flags, *args, **kwargs)

    ready = tmp_path / "ready"
    held = [str(vfio / name) for name in holds] if case != "no_group_directory" else []
    running = job.Job(
        [sys.executable, "-c", HOLDER, str(ready)] + held, str(tmp_path / "work"), platform,
        str(tmp_path / "cache"), vfio_dir=str(vfio), chips_deadline_s=deadline_s,
    )
    running.wait_for(ready.exists, 30.0, "the job to open its groups")
    monkeypatch.setattr(os, "open", fake_open)
    running.stop()
    assert running.proc.poll() is not None and job._pids_in_group(running.proc.pid) == []
    assert tried == {} and running.chips_wait_s == 0.0  # an untraced run stops here: nothing polled, nothing waited
    assert running.groups_held == (held if platform == "tpu" else [])
    waited = running.wait_for_chips()
    said = capfd.readouterr().err
    assert waited == running.chips_wait_s
    if case == "opens_after_refusals" or case.startswith("none_seen_held"):
        assert tried == {"0": 4, "1": 4} and 0.5 <= waited < 10.0 and "still busy" not in said
        assert running.chips_waited_for == [str(vfio / "0"), str(vfio / "1")]
    elif case == "only_the_groups_the_job_held":
        assert tried == {"1": 4} and 0.5 <= waited < 10.0 and running.chips_waited_for == [str(vfio / "1")]
    elif case == "never_opens":
        assert deadline_s <= waited < 5.0
        assert "still busy" in said and str(vfio / "0") in said and str(vfio / "1") in said
    else:
        assert tried == {} and waited == 0.0 and said == ""


def test_a_run_directory_stands_in_for_work_in_job_flags():
    config = {"name": "c", "model_def": "m.spec", "model_params": {}, "distribution_strategy": "AllReduce",
              "job_flags": {"checkpoint_dir": "{work}/ckpt", "checkpoint_steps": 8}}
    traffic = {"minibatch_size": 2, "minibatches_per_task": 2, "num_epochs": 1, "job_flags": {"output": "{work}/out/{work}"}}
    argv = job.job_argv(config, traffic, "/d", "/runs/x", {"profile_dir": "/p/{work}"})
    flag = lambda name: argv[argv.index("--" + name) + 1]  # noqa: E731
    assert flag("checkpoint_dir") == "/runs/x/ckpt" and flag("checkpoint_steps") == "8"
    assert flag("output") == "/runs/x/out//runs/x"
    assert flag("profile_dir") == "/p/{work}"  # the harness's own flags are taken as they are


#: A job that writes its environment where its command line says.
ENV_DUMP = "import json, os, sys; json.dump(dict(os.environ), open(sys.argv[1] + '.tmp', 'w')); os.rename(sys.argv[1] + '.tmp', sys.argv[1])"


@pytest.mark.parametrize("case,outside,own,wanted", [
    ("the_harness_sizes_the_transfer_buffer", {}, None, str(256 << 20)),
    ("whatever_the_caller_exported", {"TPU_PREMAPPED_BUFFER_SIZE": "17179869184"}, None, str(256 << 20)),
    ("a_data_file_comes_last", {"TPU_PREMAPPED_BUFFER_SIZE": "1"}, {"TPU_PREMAPPED_BUFFER_SIZE": "4294967296", "OWN": "x"}, "4294967296"),
])
def test_the_jobs_environment_is_the_harnesses_then_the_data_files(tmp_path, monkeypatch, case, outside, own, wanted):
    """PR 63: the TPU's open pins a host buffer whose default size made
    ``setup_s`` wander by seconds; the job gets ``JOB_ENV`` whatever the
    caller's environment says (both sides of a check run alike), and a
    configuration's or a traffic mix's ``job_env`` after it."""
    for key, value in outside.items():
        monkeypatch.setenv(key, value)
    monkeypatch.setenv("BENCH_RUN", "7")
    out = tmp_path / "env.json"
    running = job.Job([sys.executable, "-c", ENV_DUMP, str(out)], str(tmp_path / "work"), "cpu", str(tmp_path / "cache"), job_env=own)
    try:
        running.wait_for(out.exists, 30.0, "the job's environment")
    except job.JobFailed:  # it wrote and left between two polls
        assert out.exists()
    finally:
        running.stop()
    env = json.loads(out.read_text())
    assert job.JOB_ENV == {"TPU_PREMAPPED_BUFFER_SIZE": str(256 << 20)}
    assert env["TPU_PREMAPPED_BUFFER_SIZE"] == wanted and env.get("OWN") == (own or {}).get("OWN")
    assert env["JAX_PLATFORMS"] == "cpu" and env["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path / "cache") and "BENCH_RUN" not in env


# ------------------------------------------- the reference child's report

LOSS = {"loss": 11.34, "step_losses": [11.35, 11.33]}
LIMITS = {"router_logits": {"limit": 1e-5, "why": "..."}, "router_choices_differing": {"limit": 64}}
SOUND = {"router_logits": 2e-7, "router_choices_differing": 2}


@pytest.mark.parametrize("reference,first_loss,checks,wanted", [
    (dict(LOSS), 11.34, {}, []),
    (dict(LOSS, checks=SOUND), 11.34, LIMITS, []),
    (dict(LOSS, checks=dict(SOUND, router_choices_differing=64)), 11.34, LIMITS, []),
    (dict(LOSS, checks=dict(SOUND, router_logits=1.9e-3)), 11.34, LIMITS, ["check router_logits: 0.0019 over 1e-05"]),
    (dict(LOSS, checks=dict(SOUND, router_logits=1.9e-3)), 12.0, LIMITS, ["first task's loss 12.0 differs", "check router_logits: 0.0019 over 1e-05"]),
    # the harness judges: a configuration's check without a reading fails, whatever else the child reports
    (dict(LOSS), 11.34, LIMITS, ["check router_logits: no reading (limit 1e-05)", "check router_choices_differing: no reading (limit 64)"]),
    (dict(LOSS, checks={}), 11.34, LIMITS, ["check router_logits: no reading", "check router_choices_differing: no reading"]),
    (dict(LOSS, checks={"router_logit": 2e-7, "router_choices_differing": 0}), 11.34, LIMITS, ["check router_logits: no reading"]),
    (dict(LOSS, checks=dict(SOUND, router_logits=float("nan"))), 11.34, LIMITS, ["check router_logits: nan over 1e-05"]),
    (dict(LOSS, checks=dict(SOUND, router_logits="2e-7")), 11.34, LIMITS, ["check router_logits: 2e-7 over 1e-05"]),
    # ... and the child's own opinion of a reading counts for nothing
    (dict(LOSS, checks=dict(SOUND, router_logits={"value": 1.9e-3, "limit": 1.0, "ok": True})), 11.34, LIMITS, ["check router_logits: {"]),
    ({"error": "reference child exited 1: RuntimeError: open(/dev/vfio/2): Device or resource busy"}, 11.34, LIMITS,
     ["reference: reference child exited 1: RuntimeError: open(/dev/vfio/2): Device or resource busy"]),
], ids=["no_checks", "all_inside", "on_the_limit", "one_over", "loss_and_check", "none_reported", "empty_report", "name_mistyped",
        "not_a_number", "not_a_reading", "childs_own_ok", "no_report"])
def test_the_harness_judges_each_named_check_beside_the_loss(reference, first_loss, checks, wanted):
    problems = bench_run.reference_problems(reference, first_loss, 2e-4, checks)
    assert len(problems) == len(wanted), problems
    for problem, start in zip(problems, wanted):
        assert problem.startswith(start), problem
    assert ("relative_difference" in reference) == ("loss" in reference)
    if checks and "loss" in reference:  # what [bench-info] and the last stderr lines show
        assert sorted(reference["checks"]) == sorted(checks)
        assert all(set(c) == {"value", "limit", "ok"} for c in reference["checks"].values())
        assert [n for n, c in reference["checks"].items() if not c["ok"]] == [w.split(":")[0][len("check "):] for w in wanted if w.startswith("check ")]


class _OneReference:
    def __init__(self, path):
        self.path = str(path)

    def reference_path(self, name):
        return self.path


@pytest.mark.parametrize("body,wanted", [
    ("import json, sys; json.dump({'loss': 1.0, 'checks': {'a': 3}}, open(sys.argv[sys.argv.index('--out') + 1], 'w'))", None),
    ("import sys; print('step 0'); print('RuntimeError: open(/dev/vfio/2): Device or resource busy'); print('-----'); "
     "print('For simplicity, JAX has removed its internal frames from the traceback.'); print('  '); sys.exit(1)",
     "reference child exited 1: RuntimeError: open(/dev/vfio/2): Device or resource busy"),
    ("import sys; print('step 0: loss 11.3'); sys.exit(2)", "reference child exited 2: step 0: loss 11.3"),
    ("import sys; sys.exit(3)", "reference child exited 3: "),
], ids=["report_with_checks", "dies_naming_an_error", "dies_after_a_line", "dies_silent"])
def test_the_reference_child_reports_its_checks_or_its_last_words(tmp_path, body, wanted):
    script = tmp_path / "c_reference.py"
    script.write_text(body + "\n")
    got = bench_run.run_reference(_OneReference(script), {"name": "c"}, {"name": "t"}, "/no/file", str(tmp_path), "cpu")
    assert got["seconds"] >= 0
    if wanted is None:
        assert bench_run.reference_problems(got, 1.0, 1e-3, {"a": {"limit": 2}}) == ["check a: 3 over 2"]
    else:
        assert got["error"] == wanted and "loss" not in got


# ------------------------------------- the router, as the model runs it


@pytest.fixture(scope="module")
def olmoe():
    """(the configuration, its reference module, the model at the rehearsal's
    widths with its initial weights, one minibatch of tokens)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    bench = resolve.Bench(ROOT)
    config = bench.config("olmoe_1b_7b_l1")
    with open(os.path.join(BENCH_DIR, "rehearsal", "olmoe_job.json")) as f:
        p = dict(config["model_params"], **json.load(f)["model_params"])
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    tokens = np.random.default_rng(7).integers(0, p["vocab_size"], (4, p["seq_len"] + 1)).astype(np.int32)
    return config, resolve.load_module(bench.reference_path("olmoe_1b_7b_l1")), spec, spec.init(jax.random.key(0)), p, tokens


def test_the_configuration_states_each_check_with_its_limit_and_its_readings(olmoe):
    config = olmoe[0]
    assert sorted(config["checks"]) == ["router_choices_differing", "router_logits"]
    for name, check in config["checks"].items():
        # a limit stands between what sound runs read and what the precision below reads, with room on both sides
        assert 3 * check["system_reads"]["largest"] <= check["limit"] <= check["bfloat16_reads"]["smallest"] / 3, name
        assert check["system_reads"]["seeds"] >= 12 and len(check["why"]) > 40
    # what is still not held: the head's logits, and the job's own compiled step
    assert "head" in config["correct_does_not_cover"] and "needs a hook" not in config["correct_does_not_cover"]


@pytest.mark.parametrize("model", [
    "as_it_is", "weight_rounded_on_its_way_to_the_op", "bfloat16_product_in_the_op", "another_router",
])
def test_the_router_checks_read_the_model_and_fail_the_precision_below(olmoe, monkeypatch, model):
    """The control, at a size a test can hold (the rehearsal's widths, two
    expert layers): the checks run the model's own ``apply``.  They pass on
    the model as it is and fail (the harness's judgement, on the
    configuration's limits) on a model that rounds the router's weight to
    bfloat16 before it calls the op, on an op whose product is taken in
    bfloat16 — the precision below the one the configuration states — and
    on a model that routes by a function of its own, which gives the checks
    nothing to read."""
    import types

    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import moe_lm
    from elasticdl_tpu.ops import moe

    config, reference, spec, params, p, tokens = olmoe
    real = moe.route
    proxy = lambda route: types.SimpleNamespace(route=route, expert_ffn=moe.expert_ffn, router_stats=moe.router_stats)  # noqa: E731
    if model == "weight_rounded_on_its_way_to_the_op":
        monkeypatch.setattr(moe_lm, "moe", proxy(lambda u, wg, k: moe.route(u, wg.astype(jnp.bfloat16), k)))
    elif model == "bfloat16_product_in_the_op":
        def low(u, wg, k):
            r = (u @ wg.astype(jnp.bfloat16)).astype(jnp.float32)
            return real(u, wg, k)._replace(logits=r, choices=jax.lax.top_k(jax.nn.softmax(r, -1), k)[1])

        monkeypatch.setattr(moe, "route", low)
    elif model == "another_router":
        monkeypatch.setattr(moe_lm, "moe", proxy(real))
    routed = reference.routers_of_the_model(spec)(params, tokens[:, :-1], tokens[:, 1:])
    readings = reference.router_checks(routed, params, int(p["num_experts_per_tok"]))
    assert moe.route is (low if model == "bfloat16_product_in_the_op" else real)  # the tap is gone with the trace
    problems = bench_run.reference_problems({"loss": 1.0, "checks": readings}, 1.0, 1e-3, config["checks"])
    if model == "as_it_is":
        assert len(routed) == 2 and routed[0]["u"].dtype == jnp.bfloat16 and routed[0]["logits"].dtype == jnp.float32
        assert problems == [] and readings["router_logits"] < 1e-6 and readings["router_choices_differing"] == 0
    elif model == "another_router":
        assert routed == [] and readings == {}
        assert problems == ["check router_logits: no reading (limit 1e-05)", "check router_choices_differing: no reading (limit 64)"]
    else:
        assert readings["router_logits"] > 1e-3 and readings["router_choices_differing"] > 0, readings
        assert [problem.split(":")[0] for problem in problems] == ["check router_logits"]  # 1,024 slots: the choices stay under 64
