import pytest

from elasticdl_tpu.common.config import (
    DistributionStrategy,
    JobConfig,
    parse_args,
)


def test_defaults_valid():
    cfg = JobConfig()
    cfg.validate()


def test_parse_reference_style_flags():
    cfg = parse_args(
        [
            "--model_zoo", "elasticdl_tpu.models",
            "--model_def", "mnist.model_spec",
            "--distribution_strategy", "ParameterServer",
            "--minibatch_size", "128",
            "--num_epochs", "2",
            "--num_workers", "4",
            "--checkpoint_steps", "100",
        ]
    )
    assert cfg.distribution_strategy == DistributionStrategy.PARAMETER_SERVER
    assert cfg.minibatch_size == 128
    assert cfg.num_workers == 4


def test_json_roundtrip_env_bus():
    cfg = JobConfig(minibatch_size=256, job_name="j1")
    env = cfg.to_env()
    restored = JobConfig.from_env(env)
    assert restored == cfg


def test_invalid_strategy_rejected():
    import pytest

    cfg = JobConfig(distribution_strategy="Horovod")
    with pytest.raises(ValueError):
        cfg.validate()


def test_model_params_parsing():
    cfg = JobConfig(model_params="learning_rate=0.01;hidden=[64, 32];name=deep")
    parsed = cfg.parsed_model_params()
    assert parsed == {"learning_rate": 0.01, "hidden": [64, 32], "name": "deep"}


def test_learning_rate_flag_reaches_model():
    import optax

    from elasticdl_tpu.models import load_model_spec_for_job

    cfg = JobConfig(model_def="mnist.model_spec", learning_rate=0.5)
    spec = load_model_spec_for_job(cfg)
    # The optimizer must have been built with the flag's LR, not the default.
    params = {"w": __import__("jax.numpy", fromlist=["x"]).ones((2,))}
    state = spec.optimizer.init(params)
    grads = {"w": __import__("jax.numpy", fromlist=["x"]).ones((2,))}
    updates, _ = spec.optimizer.update(grads, state, params)
    assert abs(float(updates["w"][0])) == 0.5


@pytest.mark.parametrize("literal, json_spelling", [("False", "false"), ("True", "true"), ("None", "null")])
def test_model_params_reject_python_literals(literal, json_spelling):
    """Values are JSON: ``host_tier=False`` used to arrive as the truthy
    string "False" and turn the host tier on."""
    with pytest.raises(ValueError, match=f"host_tier={literal}.*{json_spelling}"):
        JobConfig(model_params=f"host_tier={literal}").parsed_model_params()
    assert JobConfig(model_params="host_tier=false").parsed_model_params() == {"host_tier": False}


def test_model_params_override_learning_rate_flag():
    from elasticdl_tpu.models import load_model_spec_for_job

    cfg = JobConfig(
        model_def="mnist.model_spec",
        learning_rate=0.5,
        model_params="learning_rate=0.25",
    )
    spec = load_model_spec_for_job(cfg)
    params = {"w": __import__("jax.numpy", fromlist=["x"]).ones((2,))}
    state = spec.optimizer.init(params)
    updates, _ = spec.optimizer.update(
        {"w": __import__("jax.numpy", fromlist=["x"]).ones((2,))}, state, params
    )
    assert abs(float(updates["w"][0])) == 0.25


def test_optimizer_sharding_knob_validation():
    import pytest

    JobConfig(optimizer_sharding="sharded").validate()
    JobConfig(optimizer_sharding="auto").validate()
    with pytest.raises(ValueError):
        JobConfig(optimizer_sharding="zero3").validate()
    with pytest.raises(ValueError):
        JobConfig(optimizer_sharding_auto_mb=0).validate()


def test_optimizer_sharding_flags_parse_and_roundtrip():
    cfg = parse_args(
        [
            "--optimizer_sharding", "auto",
            "--optimizer_sharding_auto_mb", "16.5",
            "--donate_train_state", "false",
        ]
    )
    assert cfg.optimizer_sharding == "auto"
    assert cfg.optimizer_sharding_auto_mb == 16.5
    assert cfg.donate_train_state is False
    assert JobConfig.from_env(cfg.to_env()) == cfg
