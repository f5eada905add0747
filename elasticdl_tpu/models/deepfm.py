"""DeepFM on Criteo-Kaggle — BASELINE.json config #4, the flagship benchmark
("DeepFM on Criteo-Kaggle, PS embedding + dense AllReduce hybrid").

Reference parity [D: config list; sources unverifiable — mount empty at survey
time]: the reference builds DeepFM from ``elasticdl.layers.Embedding`` (tables
on the gRPC parameter server) plus Keras dense layers synced via Horovod
allreduce.  Here the "hybrid" is just two partition specs inside ONE jitted
step: the fused embedding tables are row-sharded over the mesh (declared via
``embedding_tables``), dense params are replicated with psum'd grads.

Criteo schema: 13 numeric ("I1..I13", log1p-normalized) + 26 categorical
("C1..C26", hashed into a fused table — see models/tabular.py).

Model = first-order linear term + FM second-order pairwise interactions
+ DNN over [embeddings; normalized numerics]; all three heads sum into one
logit.  Compute in bfloat16 (MXU-native), f32 params/loss.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.jax_compat import jit_compiled
from elasticdl_tpu.data.codecs import criteo_feed, criteo_feed_pre
from elasticdl_tpu.models.spec import Adam, EmbeddingTableSpec, HostTableIO, ModelSpec
from elasticdl_tpu.models.tabular import (
    bce_loss,
    binary_metrics,
    fuse_feature_ids,
    fuse_feature_ids_np,
    log_normalize,
)
from elasticdl_tpu.ops.embedding import (
    ParallelContext,
    embedding_lookup,
    normal_packed_table,
)

NUM_DENSE = 13
NUM_CAT = 26


HOST_FM_KEY = "__host__fm_table"


def _init_params(
    rng: jax.Array,
    buckets_per_feature: int,
    embedding_dim: int,
    hidden: tuple,
    host_tier: bool = False,
) -> Dict[str, Any]:
    vocab = NUM_CAT * buckets_per_feature
    ks = jax.random.split(rng, 4 + len(hidden))
    glorot = jax.nn.initializers.glorot_normal()
    # One sharded table (the "parameter server" part) holds BOTH the FM
    # embedding (dims 0..embedding_dim-1, normal init) and the first-order
    # linear weight (last dim, zero init) per id: the per-id scatter/gather
    # cost is per PHYSICAL ROW (128 lanes) regardless of dim, so a separate
    # dim-1 linear table would double the dominant scatter-add for 1/128th
    # of a row's payload (from a per-op device trace; today --profile_dir
    # + benchmark/xplane.py).  Stored
    # lane-packed — see ops/embedding.py: whole-physical-row gathers/
    # scatters are the TPU fast path (flat-slice layout hit a serial
    # per-row loop).
    params: Dict[str, Any] = {
        # Replicated dense params (the "allreduce" part).
        "dense_linear": {
            "w": jnp.zeros((NUM_DENSE, 1), jnp.float32),
            "b": jnp.zeros((1,), jnp.float32),
        },
        "mlp": {},
    }
    if not host_tier:
        # Host-tier mode keeps NO device table: rows live in the native C++
        # store (lazy, per-id) and arrive through the batch.
        params["fm_table"] = normal_packed_table(
            ks[0], vocab, embedding_dim + 1, live_dim=embedding_dim
        )
    in_dim = NUM_CAT * embedding_dim + NUM_DENSE
    for i, width in enumerate(hidden):
        params["mlp"][f"layer{i}"] = {
            "w": glorot(ks[2 + i], (in_dim, width), jnp.float32),
            "b": jnp.zeros((width,), jnp.float32),
        }
        in_dim = width
    params["mlp"]["out"] = {
        "w": glorot(ks[2 + len(hidden)], (in_dim, 1), jnp.float32),
        "b": jnp.zeros((1,), jnp.float32),
    }
    return params


def _apply(
    params,
    batch,
    train: bool = False,
    ctx: ParallelContext = ParallelContext(),
    buckets_per_feature: int = 0,
    embedding_dim: int = 8,
    compute_dtype=jnp.bfloat16,
    **_,
):
    # Pipeline-preprocessed batches (criteo_feed_pre) arrive with the host
    # transforms already applied — float16 dense is log1p'd, uint16 cat ids
    # are hashed bucket ids.  Dtype is static under jit, so this branch
    # costs nothing at runtime.
    d = batch["dense"]
    dense = (
        d.astype(jnp.float32) if d.dtype == jnp.float16 else log_normalize(d)
    )

    if HOST_FM_KEY in batch:
        # Host-tier: vectors were pulled from the C++ store and injected by
        # the trainer; their cotangents flow back out as sparse grads.
        vecs = batch[HOST_FM_KEY]  # [b, 26, dim+1]
    else:
        c = batch["cat"]
        if c.dtype == jnp.uint16:  # pre-hashed: apply the feature offsets only
            ids = c.astype(jnp.int32) + (
                jnp.arange(NUM_CAT, dtype=jnp.int32) * buckets_per_feature
            )
        else:
            ids = fuse_feature_ids(c, buckets_per_feature)  # [b, 26]
        vecs = embedding_lookup(
            params["fm_table"], ids, ctx, dim=embedding_dim + 1
        )
    emb, lin = vecs[..., :embedding_dim], vecs[..., embedding_dim]  # [b,26,d],[b,26]

    emb = emb.astype(compute_dtype)
    dense_c = dense.astype(compute_dtype)

    # First-order: sparse linear + dense linear.
    first = jnp.sum(lin, axis=-1, dtype=jnp.float32)
    dl = params["dense_linear"]
    first = first + (dense @ dl["w"])[:, 0] + dl["b"][0]

    # Second-order FM: 0.5 * sum_d[(sum_f v)^2 - sum_f v^2].
    sum_v = jnp.sum(emb, axis=1)
    sum_v2 = jnp.sum(emb * emb, axis=1)
    fm = 0.5 * jnp.sum(sum_v * sum_v - sum_v2, axis=-1).astype(jnp.float32)

    # Deep head.
    x = jnp.concatenate([emb.reshape(emb.shape[0], -1), dense_c], axis=-1)
    mlp = params["mlp"]
    n_hidden = len(mlp) - 1
    for i in range(n_hidden):
        layer = jax.tree.map(lambda a: a.astype(compute_dtype), mlp[f"layer{i}"])
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    out = jax.tree.map(lambda a: a.astype(compute_dtype), mlp["out"])
    deep = (x @ out["w"] + out["b"])[:, 0].astype(jnp.float32)

    return first + fm + deep


def _predict(params, batch, ctx: ParallelContext = ParallelContext(), **kw):
    """Inference entry (serving tier / predict jobs): click probability in
    [0, 1], not the raw logit — what an online caller actually consumes."""
    return jax.nn.sigmoid(_apply(params, batch, train=False, ctx=ctx, **kw))


def _loss(logits, batch, mask=None):
    return bce_loss(logits, batch["labels"], mask)


def _metrics(logits, batch, mask=None):
    return binary_metrics(logits, batch["labels"], mask)


def _example_batch(batch_size: int, pre: bool = False):
    if pre:
        return {
            "dense": jnp.zeros((batch_size, NUM_DENSE), jnp.float16),
            "cat": jnp.zeros((batch_size, NUM_CAT), jnp.uint16),
            "labels": jnp.zeros((batch_size,), jnp.uint8),
        }
    return {
        "dense": jnp.zeros((batch_size, NUM_DENSE), jnp.float32),
        "cat": jnp.zeros((batch_size, NUM_CAT), jnp.int32),
        "labels": jnp.zeros((batch_size,), jnp.int32),
    }


def model_spec(
    learning_rate: float = 1e-3,
    compute_dtype: str = "bfloat16",
    buckets_per_feature: int = 65536,
    embedding_dim: int = 8,
    hidden: Any = (400, 400),
    host_tier: Any = "auto",
    pipeline_preprocess: Any = "auto",
) -> ModelSpec:
    """``host_tier``: True places the FM table in the native host store
    (ps/host_store) instead of HBM; "auto" promotes it when the padded table
    plus Adam moments would crowd a chip's HBM (ops.embedding guard) — the
    reference's external gRPC-PS tier, for vocabularies beyond mesh memory.

    ``pipeline_preprocess``: run the feature transforms (hash bucketing +
    log1p) in the input pipeline's C++ decoder instead of on device,
    shipping compact dtypes (uint16/float16/uint8 — 79 B/example vs 160 B).
    The reference's preprocessing layers live in the input pipeline the same
    way (SURVEY.md §2 #15).  "auto" enables it for the mesh-tier model
    whenever the bucket count fits uint16; the on-device transform path
    remains for raw batches (numerics pinned equal by tests).
    """
    if isinstance(hidden, (list, tuple)):
        hidden = tuple(int(h) for h in hidden)
    else:  # "400,400" via --model_params
        hidden = tuple(int(h) for h in str(hidden).split(",") if h)
    dtype = jnp.dtype(compute_dtype)
    vocab = NUM_CAT * buckets_per_feature
    dim = embedding_dim
    if host_tier == "auto":
        from elasticdl_tpu.ops.embedding import exceeds_hbm_guard

        host_tier = exceeds_hbm_guard(vocab, dim + 1)
    host_tier = bool(host_tier)
    if pipeline_preprocess == "auto":
        # Host-tier pulls need the RAW ids (fuse_feature_ids_np over the
        # full 32-bit space); uint16 bucket ids only exist for <= 2^16.
        pipeline_preprocess = not host_tier and buckets_per_feature <= 65536
    pipeline_preprocess = bool(pipeline_preprocess)
    if pipeline_preprocess and (host_tier or buckets_per_feature > 65536):
        raise ValueError(
            "pipeline_preprocess requires the mesh-tier model and "
            "buckets_per_feature <= 65536"
        )
    return ModelSpec(
        name="deepfm",
        # Jitted, so the table is one fused pass from counters to packed
        # rows wherever init is called (alone, or inlined in the trainer's
        # sharded init), with the same bits either way.
        init=jit_compiled(
            functools.partial(
                _init_params,
                buckets_per_feature=buckets_per_feature,
                embedding_dim=dim,
                hidden=hidden,
                host_tier=host_tier,
            ),
            name="deepfm.init",
            expected_variants=2,  # typed and raw uint32 keys
        ),
        apply=functools.partial(
            _apply,
            buckets_per_feature=buckets_per_feature,
            embedding_dim=dim,
            compute_dtype=dtype,
        ),
        predict=functools.partial(
            _predict,
            buckets_per_feature=buckets_per_feature,
            embedding_dim=dim,
            compute_dtype=dtype,
        ),
        loss=_loss,
        metrics=_metrics,
        optimizer=Adam(learning_rate),
        embedding_tables=(
            []
            if host_tier
            else [
                EmbeddingTableSpec(
                    path=("fm_table",), vocab_size=vocab, dim=dim + 1
                )
            ]
        ),
        host_io=(
            {
                HOST_FM_KEY: HostTableIO(
                    ids_fn=functools.partial(
                        _host_ids, buckets_per_feature=buckets_per_feature
                    ),
                    dim=dim + 1,
                    optimizer="adagrad",
                    learning_rate=learning_rate * 10,
                    init_scale=0.01,
                )
            }
            if host_tier
            else {}
        ),
        feed=(
            functools.partial(criteo_feed_pre, buckets=buckets_per_feature)
            if pipeline_preprocess
            else criteo_feed
        ),
        example_batch=functools.partial(
            _example_batch, pre=pipeline_preprocess
        ),
    )


def _host_ids(batch, buckets_per_feature: int):
    """Host-side (numpy) fused ids — identical to the on-device hash."""
    return fuse_feature_ids_np(batch["cat"], buckets_per_feature)
