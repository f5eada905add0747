"""The worker's program store (common/program_store.py, PR 57): a trainer WITH
one restores its compiled train step where an earlier trainer of the same job
left it, and traces nothing; every component of the key misses when changed;
an entry that cannot be used falls back to the trace, is counted and removed;
a trainer WITHOUT one never restores, whatever the directory holds."""

import contextlib
import copy
import dataclasses
import os

import jax
import jaxlib
import numpy as np
import pytest

from elasticdl_tpu.common import platform, program_store
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.program_store import ProgramStore
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel import trainer as trainer_mod
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

#: a one-layer OLMoE block (tests/test_moe.py's smaller shape)
SMALL = dict(vocab_size=64, hidden_size=32, num_attention_heads=2, num_hidden_layers=1,
             num_experts=4, num_experts_per_tok=2, intermediate_size=32, seq_len=32)
#: tests/test_kimi_linear.py's keys with ONE delta-rule layer after the dense one
KIMI = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, num_hidden_layers=1,
    linear_attn_config={"kda_layers": [1], "full_attn_layers": [], "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
    mla_use_nope=True, kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000,
    num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_token=3, intermediate_size=48, moe_intermediate_size=24,
    num_shared_experts=1, first_k_dense_replace=1, moe_layer_freq=1, moe_router_activation_func="sigmoid", moe_renormalize=True,
    routed_scaling_factor=2.446, use_grouped_topk=True, num_expert_group=1, topk_group=1, bias_update_speed=0.001,
    rms_norm_eps=1e-5, tie_word_embeddings=False, decay_matrices_only=True, seq_len=128, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=0, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)


@pytest.fixture(scope="module", autouse=True)
def compiles_are_this_process_own():
    """The run's persistent compile cache off for this file: XLA:CPU cannot
    serialize an executable the cache SERVED (the trainer does not store
    such a one there), and these cases want each first compile stored."""
    from jax.experimental.compilation_cache import compilation_cache

    platform.count_compiles()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(keys=SMALL, **kw):
    return load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", compute_dtype="float32", **{**keys, **kw})


def _stacked(keys=SMALL, mb=2, steps=2, seed=0):
    tokens = np.random.default_rng(seed).integers(0, keys["vocab_size"], size=(steps, mb, keys["seq_len"] + 1), dtype=np.int32)
    return {"tokens": tokens[..., :-1].copy(), "labels": tokens[..., 1:].copy()}


def _train(trainer, keys=SMALL, tasks=3):
    """``tasks`` scans of two steps from the same seed: (every task's losses,
    the final state as numpy, whether each call consumed its state)."""
    state = trainer.init_state(jax.random.key(0))
    losses, donated = [], []
    for i in range(tasks):
        before = state
        state, metrics = trainer.train_scan(state, trainer.shard_stacked_batch(_stacked(keys, seed=i)))
        losses.append(np.asarray(metrics["loss"]))
        donated.append(all(leaf.is_deleted() for leaf in jax.tree.leaves(before.params)))
    return losses, jax.tree.map(np.asarray, state), donated


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True))


@pytest.fixture(scope="module")
def first_launch(tmp_path_factory):
    """A trainer with a store on an empty directory, run for three tasks:
    (the directory with its one entry, what the run gave)."""
    directory = str(tmp_path_factory.mktemp("programs"))
    store = ProgramStore(directory)
    trainer = Trainer(_spec(), JobConfig(), create_mesh(num_devices=1), programs=store)
    gave = _train(trainer)
    store.settle()
    assert (trainer.programs_restored, trainer.programs_traced) == (0, 1)
    assert store.counts()["written"] == 1 and store.counts()["failed"] == 0
    (entry,) = os.listdir(directory)
    assert entry.endswith(".program")
    return directory, gave


def test_a_second_trainer_restores_the_step_traces_nothing_and_trains_to_the_same_bits(first_launch):
    directory, (losses, state, donated) = first_launch
    assert all(donated)
    store = ProgramStore(directory)
    trainer = Trainer(_spec(), JobConfig(), create_mesh(num_devices=1), programs=store)
    state0 = trainer.init_state(jax.random.key(0))  # the init program is traced, as ever
    stacked = trainer.shard_stacked_batch(_stacked(seed=0))
    trainer._active_device()  # the mask's placement is a program of its own
    before = platform.compile_phase_seconds()
    state1, metrics = trainer.train_scan(state0, stacked)
    after = platform.compile_phase_seconds()
    # (the batch's one-step shapes are an eval_shape of a slice at every call: under a millisecond of "trace")
    assert after["trace_s"] - before["trace_s"] < 0.01 and after["lower_s"] == before["lower_s"] and after["compile_s"] == before["compile_s"]
    assert (trainer.programs_restored, trainer.programs_traced) == (1, 0)
    assert store.counts()["restore_s"] > 0
    assert np.array_equal(np.asarray(metrics["loss"]), losses[0])
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(state0.params)), "the restored step donates as the jitted one"
    again, state_again, donated_again = _train(trainer)
    assert _same(again, losses) and _same(state_again, state) and all(donated_again)
    assert trainer.programs_restored == 1, "one restore serves every later call"
    store.settle()
    assert store.counts()["written"] == 0, "a restored program is not written again"


class _Key(Exception):
    pass


def _key(spec=None, config=None, mesh=None, mb=2, token_dtype=np.int32, state=None):
    """The key the trainer would look its scan up by, with nothing traced
    but the init program's shapes (or, given a real ``state``, the key a
    real call makes: its arguments' shardings are in it)."""
    trainer = Trainer(spec or _spec(), config or JobConfig(), mesh or create_mesh(num_devices=1), programs=ProgramStore("/nonexistent"))

    def grab(self, cache, slot, make, args):
        raise _Key(self._program_key(make, args))

    if state is None:
        state = jax.eval_shape(trainer.init_state, jax.random.key(0))
    else:
        trainer.init_state(jax.random.key(0))
    stacked = {k: v.astype(token_dtype) for k, v in _stacked(mb=mb).items()}
    with pytest.MonkeyPatch.context() as patch, pytest.raises(_Key) as caught:
        patch.setattr(Trainer, "_train_program", grab)
        trainer.train_scan(state, trainer.shard_stacked_batch(stacked))
    return caught.value.args[0]


#: what changes, as ``(monkeypatch, the moved package root, an ExitStack) -> _key's keywords``
CHANGES = {
    "a source byte": lambda mp, root, stack: (root / "module.py").write_text("x = 2\n") and {},
    "a jax setting": lambda mp, root, stack: stack.enter_context(jax.default_matmul_precision("highest")),
    "a shape": lambda mp, root, stack: {"mb": 4},
    "a dtype": lambda mp, root, stack: {"token_dtype": np.uint32},
    "the mesh": lambda mp, root, stack: {"mesh": create_mesh(num_devices=2)},
    "a spec parameter": lambda mp, root, stack: {"spec": _spec(learning_rate=2e-3)},
    "the donation flag": lambda mp, root, stack: {"config": JobConfig(donate_train_state=False)},
    "the keep line": lambda mp, root, stack: mp.setattr(trainer_mod, "REMAT_HEADROOM", trainer_mod.REMAT_HEADROOM + 1),
    "the device's memory": lambda mp, root, stack: mp.setattr(trainer_mod, "device_bytes_limit", lambda devices: 1 << 34),
    "a version string": lambda mp, root, stack: mp.setattr(jaxlib, "__version__", "0.0.0"),
    "a flag libtpu reads": lambda mp, root, stack: mp.setenv("LIBTPU_INIT_ARGS", "--xla_tpu_some_flag=true"),
}


@pytest.mark.parametrize("what", sorted(CHANGES))
def test_every_component_of_the_key_misses_when_changed(what, monkeypatch, tmp_path):
    (tmp_path / "module.py").write_text("x = 1\n")  # the package's sources, for the key's digest
    monkeypatch.setattr(program_store, "PACKAGE_ROOT", str(tmp_path))
    base = _key()
    assert _key() == base, "the same job on the same layout has the same key"
    with contextlib.ExitStack() as stack:
        changed = _key(**(CHANGES[what](monkeypatch, tmp_path, stack) or {}))
    assert changed != base, what


def test_a_traced_runs_flags_and_this_launchs_addresses_leave_the_key_alone():
    traced = JobConfig(
        profile_dir="/tmp/profile", profile_tasks=2, profile_inline=True, trace=True, trace_buffer_events=16,
        metrics_dir="/tmp/metrics", pod_log_dir="/tmp/pods", gauge_port=0, log_level="DEBUG",
        master_addr="localhost:1234", master_port=1234, training_data="/tmp/data", job_name="another",
    )
    assert _key(config=traced) == _key()


def test_every_job_config_field_is_in_the_key_or_in_the_one_list_of_those_no_program_reads():
    fields = {f.name for f in dataclasses.fields(JobConfig)}
    listed = dict(trainer_mod.CONFIG_FIELDS_NO_PROGRAM_READS)
    assert len(listed) == len(trainer_mod.CONFIG_FIELDS_NO_PROGRAM_READS), "a field is listed once"
    assert set(listed) <= fields, sorted(set(listed) - fields)
    assert all(len(why) > 10 for why in listed.values()), "each with its reason beside it"
    # every other field moves the key: one added to JobConfig later is IN until somebody lists it
    trainer = Trainer(_spec(), JobConfig(), create_mesh(num_devices=1), programs=ProgramStore("/nonexistent"))
    keys = {}

    def grab(self, cache, slot, make, args):
        for name in sorted(fields):
            value = getattr(JobConfig(), name)
            other = copy.copy(JobConfig())
            object.__setattr__(other, name, (not value) if isinstance(value, bool) else value + 1 if isinstance(value, (int, float)) else value + "x")
            self.config = other
            keys[name] = self._program_key(make, args)
        self.config = JobConfig()
        raise _Key(self._program_key(make, args))

    state = jax.eval_shape(trainer.init_state, jax.random.key(0))
    with pytest.MonkeyPatch.context() as patch, pytest.raises(_Key) as caught:
        patch.setattr(Trainer, "_train_program", grab)
        trainer.train_scan(state, trainer.shard_stacked_batch(_stacked()))
    base = caught.value.args[0]
    assert {name for name, key in keys.items() if key == base} == set(listed)


@pytest.mark.parametrize("damage", ["truncated", "garbage"])
def test_an_entry_that_cannot_be_read_falls_back_to_the_trace_is_counted_and_is_deleted(damage, first_launch, tmp_path):
    directory, (losses, _, _) = first_launch
    (entry,) = os.listdir(directory)
    with open(os.path.join(directory, entry), "rb") as f:
        raw = f.read()
    with open(tmp_path / entry, "wb") as f:
        f.write(raw[: len(raw) // 2] if damage == "truncated" else os.urandom(4096))
    store = ProgramStore(str(tmp_path))
    trainer = Trainer(_spec(), JobConfig(), create_mesh(num_devices=1), programs=store)
    state = trainer.init_state(jax.random.key(0))
    state, metrics = trainer.train_scan(state, trainer.shard_stacked_batch(_stacked(seed=0)))
    assert np.array_equal(np.asarray(metrics["loss"]), losses[0])
    assert (trainer.programs_restored, trainer.programs_traced) == (0, 1)
    assert store.counts()["failed"] == 1
    store.settle()
    assert os.listdir(tmp_path) == [entry] and store.counts()["written"] == 1, "the bad entry went, the traced program took its place"
    assert os.path.getsize(tmp_path / entry) > len(raw) // 2


def test_a_first_call_the_program_refuses_falls_back_before_anything_is_consumed(first_launch, tmp_path):
    """An entry that loads and then refuses its arguments (the entry of
    ANOTHER batch size under this job's key, as a key that forgot a
    component would find it): the step is traced and the entry dropped."""
    import pickle

    directory, _ = first_launch
    (entry,) = os.listdir(directory)
    with open(os.path.join(directory, entry), "rb") as f:
        stored = pickle.loads(f.read())
    store = ProgramStore(str(tmp_path))
    trainer = Trainer(_spec(), JobConfig(), create_mesh(num_devices=1), programs=store)
    state = trainer.init_state(jax.random.key(0))
    key = _key(mb=4, state=state)
    with open(store.path(key), "wb") as f:
        f.write(pickle.dumps(dict(stored, key=key)))
    state, metrics = trainer.train_scan(state, trainer.shard_stacked_batch(_stacked(mb=4)))
    assert np.asarray(metrics["loss"]).shape == (2,) and np.all(np.isfinite(np.asarray(metrics["loss"])))
    assert (trainer.programs_restored, trainer.programs_traced) == (1, 1)
    assert store.counts()["failed"] == 1
    state, metrics = trainer.train_scan(state, trainer.shard_stacked_batch(_stacked(mb=4, seed=1)))  # the slot holds the traced step
    assert trainer.programs_traced == 1
    store.settle()
    assert store.counts()["written"] == 1 and os.listdir(tmp_path) == [os.path.basename(store.path(key))]


def test_a_trainer_without_a_store_never_restores_and_a_patched_op_gives_the_patched_program(tmp_path):
    """What the benchmark's reference children do: a ``Trainer`` built by
    anybody but the worker has no store, so a control that swaps an op by
    module attribute (no source byte changes) gets ITS program traced,
    although the directory holds the sound program of the very same key."""
    from elasticdl_tpu.ops import delta_rule

    def losses(programs, tasks=1):
        trainer = Trainer(_spec(KIMI), JobConfig(), create_mesh(num_devices=1), programs=programs)
        return trainer, _train(trainer, KIMI, tasks=tasks)[0]

    store = ProgramStore(str(tmp_path))
    first, sound = losses(store)
    store.settle()
    assert first.programs_traced == 1 and len(os.listdir(tmp_path)) == 1
    carry = delta_rule._carry

    def forgetful(ends, decay, left, right, first, reverse=False):
        starts, last = carry(ends, decay, left, right, first, reverse)
        return jax.numpy.zeros_like(starts), last

    delta_rule._carry = forgetful
    jax.clear_caches()  # the op's inner checkpoints keep their traces by shape
    try:
        control, faulty = losses(None)
        restoring, from_the_store = losses(ProgramStore(str(tmp_path)))
    finally:
        delta_rule._carry = carry
        jax.clear_caches()
    assert (control.programs_restored, control.programs_traced) == (0, 1)
    assert not _same(faulty, sound), "the control's own program ran"
    # and what the separation is for: a trainer WITH the store, under the same patch, would have been handed the sound program
    assert restoring.programs_restored == 1 and _same(from_the_store, sound)


def test_the_directory_is_a_sibling_of_the_compile_caches_and_holds_its_newest_entries(tmp_path, monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax_cache"))
    try:
        store = ProgramStore.beside_compile_cache()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert store.directory == str(tmp_path / "jax_cache_programs")
    os.makedirs(store.directory)
    monkeypatch.setattr(program_store, "MAX_ENTRIES", 3)
    for i in range(5):
        path = store.path(f"{i:064d}")
        with open(path, "wb") as f:
            f.write(b"x")
        os.utime(path, (1000 + i, 1000 + i))
    os.utime(store.path(f"{0:064d}"))  # a hit touches its entry: the oldest written is the newest used
    with open(os.path.join(store.directory, "left.by.a.killed.writer.tmp1.2"), "wb") as f:
        f.write(b"x")
    os.utime(os.path.join(store.directory, "left.by.a.killed.writer.tmp1.2"), (1000, 1000))
    store._evict()
    assert sorted(os.listdir(store.directory)) == sorted(os.path.basename(store.path(f"{i:064d}")) for i in (0, 3, 4))
