"""ResNet-50 on CIFAR-10 — BASELINE.json config #2 ("ResNet-50 on CIFAR-10,
AllReduce mode").

Reference parity [D: config list; sources unverifiable — mount empty at survey
time]: the reference trains a Keras ResNet via Horovod allreduce.  Rebuilt as
a pure-JAX bottleneck ResNet whose whole train step jits over the mesh.

TPU-first choices:
- **GroupNorm instead of BatchNorm.**  BatchNorm's running stats are mutable
  state and need cross-replica sync to be correct under data parallelism;
  GroupNorm is the standard stat-free substitute on TPU pods (same accuracy
  class on CIFAR) and keeps ``apply`` a pure function of the param pytree,
  so the AllReduce step stays a single fused XLA program.
- CIFAR stem (3x3 stride-1 conv, no maxpool) instead of the ImageNet 7x7/s2
  stem, as is standard for 32x32 inputs.
- Compute in bfloat16 (MXU), f32 params, f32 norm statistics.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.data.codecs import cifar10_feed
from elasticdl_tpu.models.spec import ModelSpec

NUM_CLASSES = 10


def _conv_init(rng, shape):
    return jax.nn.initializers.he_normal()(rng, shape, jnp.float32)


def _conv(x, w, stride=1):
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NHWC", "HWIO", "NHWC"))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=dn
    )


def _group_norm(x, scale, bias, groups=8, eps=1e-5):
    """GroupNorm with no full-size f32 intermediate.

    The naive form (upcast x to f32, mean/var, normalize, affine, downcast)
    spent ~40% of the ResNet-50 step in convert_element_type + f32
    elementwise + multi-pass reduces (per-op device trace of
    resnet50_imagenet; today --profile_dir + benchmark/xplane.py).
    TPU-native form:

    - moments in ONE pass: sum and sum-of-squares reduced directly from the
      bf16 input with f32 accumulation (XLA fuses the upcast/square into the
      reduction input; no [b,h,w,c] f32 tensor is ever materialized);
    - statistics + affine folded into per-(batch, channel) a/b vectors in
      f32 (tiny), applied to the activation as a single fused bf16
      multiply-add — one read + one write of x instead of five+.

    Gradients flow through the folded a/b exactly as through the unfolded
    math (they are the same function of x); only the dtype of the big
    elementwise stream changes, which is the point.

    Rounding caveat of the fold: a/b are computed in f32 but CAST TO x's
    dtype before the fused multiply-add, so in bf16 both the product
    ``x * a`` and the pre-added offset ``b - mean * a`` round to 8
    mantissa bits — the unfolded form would subtract the mean from x at
    higher effective precision before scaling.  When |bias| ≈ |mean * a|
    the offset suffers bf16 cancellation ON TOP of the one-pass variance
    cancellation noted above.  Accepted because post-norm activations are
    O(1) (absolute rounding error ~2^-8 of a unit-scale stream, below the
    noise the bf16 convs already inject) and the fold is what buys the
    single-pass memory shape; models sensitive to it should run the norm
    stream in f32, not un-fold.
    """
    b, h, w, c = x.shape
    g = min(groups, c)
    cg = c // g
    xg = x.reshape(b, h, w, g, cg)
    n = h * w * cg
    s = jnp.sum(xg, axis=(1, 2, 4), dtype=jnp.float32)  # [b, g]
    ss = jnp.sum(
        jnp.square(xg.astype(jnp.float32)), axis=(1, 2, 4)
    )  # [b, g]
    mean = s / n
    # One-pass variance; activations are O(1) post-norm/relu so the
    # E[x^2]-E[x]^2 cancellation is benign in f32.  Clamp for safety.
    var = jnp.maximum(ss / n - jnp.square(mean), 0.0)
    inv = jax.lax.rsqrt(var + eps)  # [b, g]
    a = inv[:, :, None] * scale.reshape(g, cg)  # [b, g, cg]
    off = bias.reshape(g, cg) - mean[:, :, None] * a
    a = a.reshape(b, 1, 1, c).astype(x.dtype)
    off = off.reshape(b, 1, 1, c).astype(x.dtype)
    return x * a + off


def _init_block(rng, in_ch: int, mid_ch: int, stride: int) -> Dict[str, Any]:
    out_ch = mid_ch * 4
    ks = jax.random.split(rng, 4)
    block = {
        "conv1": _conv_init(ks[0], (1, 1, in_ch, mid_ch)),
        "gn1": {"scale": jnp.ones((mid_ch,)), "bias": jnp.zeros((mid_ch,))},
        "conv2": _conv_init(ks[1], (3, 3, mid_ch, mid_ch)),
        "gn2": {"scale": jnp.ones((mid_ch,)), "bias": jnp.zeros((mid_ch,))},
        "conv3": _conv_init(ks[2], (1, 1, mid_ch, out_ch)),
        # Zero-init the last norm scale: residual branches start as identity,
        # the standard trick for stable large-batch training.
        "gn3": {"scale": jnp.zeros((out_ch,)), "bias": jnp.zeros((out_ch,))},
    }
    if stride != 1 or in_ch != out_ch:
        block["proj"] = _conv_init(ks[3], (1, 1, in_ch, out_ch))
        block["gn_proj"] = {"scale": jnp.ones((out_ch,)), "bias": jnp.zeros((out_ch,))}
    return block


def _apply_block(params, x, stride: int):
    y = _conv(x, params["conv1"].astype(x.dtype))
    y = jax.nn.relu(_group_norm(y, **params["gn1"]))
    y = _conv(y, params["conv2"].astype(x.dtype), stride)
    y = jax.nn.relu(_group_norm(y, **params["gn2"]))
    y = _conv(y, params["conv3"].astype(x.dtype))
    y = _group_norm(y, **params["gn3"])
    if "proj" in params:
        x = _conv(x, params["proj"].astype(x.dtype), stride)
        x = _group_norm(x, **params["gn_proj"])
    return jax.nn.relu(x + y)


def _init_params(
    rng,
    stages: Tuple[int, ...],
    width: int,
    num_classes: int = NUM_CLASSES,
    imagenet_stem: bool = False,
) -> Dict[str, Any]:
    ks = jax.random.split(rng, 2 + len(stages))
    # ImageNet stem: 7x7/s2 conv (+ 3x3/s2 maxpool in apply) — the standard
    # 224x224 configuration and the honest MXU-utilization benchmark shape
    # (32x32 CIFAR convs are too small to tile the systolic array well).
    stem_kernel = (7, 7, 3, width) if imagenet_stem else (3, 3, 3, width)
    params: Dict[str, Any] = {
        "stem": {
            "conv": _conv_init(ks[0], stem_kernel),
            "gn": {"scale": jnp.ones((width,)), "bias": jnp.zeros((width,))},
        },
        "stages": {},
    }
    in_ch = width
    for s, n_blocks in enumerate(stages):
        mid = width * (2**s)
        stage = {}
        block_keys = jax.random.split(ks[1 + s], n_blocks)
        for b in range(n_blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            stage[f"block{b}"] = _init_block(block_keys[b], in_ch, mid, stride)
            in_ch = mid * 4
        params["stages"][f"stage{s}"] = stage
    params["head"] = {
        "w": jax.nn.initializers.glorot_normal()(
            ks[-1], (in_ch, num_classes), jnp.float32
        ),
        "b": jnp.zeros((num_classes,), jnp.float32),
    }
    return params


def _apply(
    params,
    batch,
    train: bool = False,
    stages: Tuple[int, ...] = (),
    compute_dtype=jnp.bfloat16,
    imagenet_stem: bool = False,
    **_,
):
    x = batch["images"].astype(compute_dtype)
    stem = params["stem"]
    x = _conv(x, stem["conv"].astype(compute_dtype), 2 if imagenet_stem else 1)
    x = jax.nn.relu(_group_norm(x, **stem["gn"]))
    if imagenet_stem:
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
        )
    for s, n_blocks in enumerate(stages):
        for b in range(n_blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            x = _apply_block(params["stages"][f"stage{s}"][f"block{b}"], x, stride)
    x = jnp.mean(x, axis=(1, 2), dtype=jnp.float32)
    head = params["head"]
    return x @ head["w"] + head["b"]


def _loss(logits, batch, mask=None):
    from elasticdl_tpu.models.metrics import masked_mean

    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["labels"]
    )
    return masked_mean(ce, mask)


def _metrics(logits, batch, mask=None):
    from elasticdl_tpu.models.metrics import masked_mean

    return {
        "accuracy": masked_mean(jnp.argmax(logits, -1) == batch["labels"], mask),
        "loss": masked_mean(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["labels"]
            ),
            mask,
        ),
    }


def _example_batch(batch_size: int, image_size: int = 32):
    return {
        "images": jnp.zeros(
            (batch_size, image_size, image_size, 3), jnp.float32
        ),
        "labels": jnp.zeros((batch_size,), jnp.int32),
    }


def model_spec(
    learning_rate: float = 0.1,
    compute_dtype: str = "bfloat16",
    depth: int = 50,
    width: int = 64,
    image_size: int = 32,
    num_classes: int = NUM_CLASSES,
    imagenet_stem: bool = False,
) -> ModelSpec:
    """depth=50 -> bottleneck stages (3,4,6,3); depth=14 (tests) -> (1,1,1,1).

    ``image_size=224, num_classes=1000, imagenet_stem=True`` is the
    standard ImageNet ResNet-50 — the configuration MFU benchmarks use;
    the CIFAR default matches
    BASELINE config #2.
    """
    stage_map = {50: (3, 4, 6, 3), 26: (2, 2, 2, 2), 14: (1, 1, 1, 1)}
    if depth not in stage_map:
        raise ValueError(f"unsupported depth {depth}, pick from {sorted(stage_map)}")
    stages = stage_map[depth]
    dtype = jnp.dtype(compute_dtype)
    if image_size != 32 or num_classes != NUM_CLASSES:
        # Non-CIFAR shapes have no dataset codec in the zoo: a job feeding
        # cifar10_feed records into this variant would silently recompile
        # against 32x32/10-class batches and train 990 dead classes.
        # Fail loudly; the MFU bench feeds synthetic batches directly.
        def feed(records):
            raise RuntimeError(
                f"resnet image_size={image_size}/num_classes={num_classes} "
                "has no dataset codec — this variant takes synthetic "
                "batches (tools/bench_all.py) or a custom feed, not "
                "cifar10 records"
            )
    else:
        feed = cifar10_feed
    return ModelSpec(
        name=f"cifar10_resnet{depth}",
        init=functools.partial(
            _init_params, stages=stages, width=width,
            num_classes=num_classes, imagenet_stem=imagenet_stem,
        ),
        apply=functools.partial(
            _apply, stages=stages, compute_dtype=dtype,
            imagenet_stem=imagenet_stem,
        ),
        loss=_loss,
        metrics=_metrics,
        optimizer=optax.sgd(learning_rate, momentum=0.9, nesterov=True),
        feed=feed,
        example_batch=functools.partial(
            _example_batch, image_size=image_size
        ),
    )
