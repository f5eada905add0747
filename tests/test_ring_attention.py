"""Ring attention vs full attention: exactness (causal and not), gradients,
and degenerate single-device behavior — on the 8-fake-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.ops.ring_attention import attention_reference, ring_attention
from elasticdl_tpu.parallel.mesh import create_mesh

from elasticdl_tpu.common.jax_compat import shard_map

B, L, H, D = 2, 64, 4, 16


def _qkv(seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (
        jax.random.normal(ks[0], (B, L, H, D), jnp.float32),
        jax.random.normal(ks[1], (B, L, H, D), jnp.float32),
        jax.random.normal(ks[2], (B, L, H, D), jnp.float32),
    )


def _ring(mesh, causal):
    axis = mesh.axis_names[0]
    spec = P(None, axis)  # shard the sequence axis

    def fn(q, k, v):
        return ring_attention(q, k, v, axis_name=axis, causal=causal)

    mapped = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, spec))
    return mapped, sh


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_ring_matches_full(devices, causal, n_dev):
    mesh = create_mesh(devices, num_devices=n_dev, axis_name="sp")
    q, k, v = _qkv()
    expected = attention_reference(q, k, v, causal=causal)
    mapped, sh = _ring(mesh, causal)
    out = jax.jit(mapped)(sh(q), sh(k), sh(v))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=2e-4, atol=2e-5
    )


def test_ring_gradients_match(devices):
    mesh = create_mesh(devices, num_devices=4, axis_name="sp")
    q, k, v = _qkv(1)
    cot = jax.random.normal(jax.random.key(9), (B, L, H, D))

    ref_grads = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attention_reference(q, k, v, causal=True) * cot),
        argnums=(0, 1, 2),
    ))(q, k, v)

    axis = mesh.axis_names[0]
    spec = P(None, axis)

    def local_loss(q, k, v, c):
        return jnp.sum(ring_attention(q, k, v, axis_name=axis, causal=True) * c)

    mapped = shard_map(
        jax.grad(local_loss, argnums=(0, 1, 2)),
        mesh=mesh,
        in_specs=(spec,) * 4,
        out_specs=(spec,) * 3,
        check_vma=False,
    )
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, spec))
    grads = jax.jit(mapped)(sh(q), sh(k), sh(v), sh(cot))
    for got, want in zip(grads, ref_grads):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-4
        )


def test_no_axis_is_plain_attention():
    q, k, v = _qkv(2)
    out = ring_attention(q, k, v, axis_name=None, causal=True)
    expected = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-6)


def test_causal_first_token_attends_self_only(devices):
    """Position 0 must see only itself: its output is v[0] exactly."""
    mesh = create_mesh(devices, num_devices=4, axis_name="sp")
    q, k, v = _qkv(3)
    mapped, sh = _ring(mesh, True)
    out = jax.jit(mapped)(sh(q), sh(k), sh(v))
    np.testing.assert_allclose(
        np.asarray(out)[:, 0], np.asarray(v)[:, 0], rtol=1e-5
    )
