"""Decoder-only transformer LM with ring-attention sequence parallelism.

Long-context is first-class in this rebuild (the reference predates it —
SURVEY.md §2 parallelism table: SP/CP absent upstream; this is a TPU-native
capability extension, not a parity item).  The model declares
``batch_shard_dim=1``: the trainer shards the SEQUENCE dimension over the
mesh axis, each device holds ``[B, S/n]`` of every sequence, and attention
runs blockwise while K/V blocks rotate around the ICI ring
(``ops/ring_attention.py`` — compute overlaps the ppermute transfer, so HBM
per device scales with S/n, enabling sequences that cannot fit one chip).

The label shift never crosses shard boundaries: the codec stores S+1 tokens
per record and the feed emits (tokens[:-1], tokens[1:]) BEFORE sharding.
Dense params are replicated with psum'd grads (the AllReduce strategy), so
SP composes with the existing trainer unchanged; positions are globalized
with the device's axis index.

``model_spec(parallelism="tensor")`` (r20) selects the hybrid-parallel
variant for the 2D ``(dp, tp)`` mesh instead: Megatron column/row-split
projections (``wqkv``/``w1`` column-sharded, ``wo``/``w2`` row-sharded
over ``tp``, declared via ``ModelSpec.tensor_sharding``), batch sharded
over ``dp`` (``batch_shard_dim=0``), ONE ``tp`` all-reduce per residual
branch through ``parallel/collectives``'s custom-VJP pair (``tp_grad_sync``
/ ``tp_all_reduce`` — identity<->psum transposes hand-written because the
shim's check_vma=False shard_map would transpose psum to psum and
over-count replicated cotangents by ``tp``).  The same apply runs dense on
a 1-D mesh (``ctx.tp_axis is None``), which is what a 2D->1D elastic
re-partition degrades to.

Architecture: pre-RMSNorm blocks, causal MHA (ring, or local full under
tensor parallelism), GELU MLP (4x), learned positional embedding,
weight-tied LM head.  bfloat16 compute, f32 params.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import optax
from jax import lax

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.data.codecs import lm_feed
from elasticdl_tpu.models.spec import ModelSpec
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops.ring_attention import (
    PATH_XLA_REFERENCE,
    announce_path,
    attention_reference,
    ring_attention,
)
from elasticdl_tpu.ops.embedding import ParallelContext


def _rms_norm(x, scale, eps=1e-6):
    # Stats and the normalize/affine arithmetic in f32, ONE downcast at the
    # end.  The previous form multiplied the downcast value by the f32
    # ``scale`` param LAST, silently promoting every tensor downstream of
    # the first norm (q/k/v, MLP, residuals) to f32 — the "bfloat16
    # compute" stream was f32 end to end (caught when the flash-attention
    # kernel received f32 operands and blew its VMEM budget at L=8192).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale).astype(x.dtype)


def _init_params(
    rng, vocab: int, dim: int, n_heads: int, n_layers: int, max_seq: int
) -> Dict[str, Any]:
    ks = iter(jax.random.split(rng, 3 + 5 * n_layers))
    scale = dim**-0.5
    params: Dict[str, Any] = {
        "tok_emb": jax.random.normal(next(ks), (vocab, dim)) * scale,
        "pos_emb": jax.random.normal(next(ks), (max_seq, dim)) * 0.01,
        "ln_f": jnp.ones((dim,), jnp.float32),
        "blocks": {},
    }
    for i in range(n_layers):
        params["blocks"][f"b{i}"] = {
            "ln1": jnp.ones((dim,), jnp.float32),
            "wqkv": jax.random.normal(next(ks), (dim, 3 * dim)) * scale,
            "wo": jax.random.normal(next(ks), (dim, dim)) * scale,
            "ln2": jnp.ones((dim,), jnp.float32),
            "w1": jax.random.normal(next(ks), (dim, 4 * dim)) * scale,
            "w2": jax.random.normal(next(ks), (4 * dim, dim)) * (0.5 * scale),
        }
    return params


def _block(x, blk, axis, n_heads, compute_dtype):
    """One pre-norm transformer block (attention + MLP residual)."""
    b, l, dim = x.shape
    head_dim = dim // n_heads
    h = _rms_norm(x, blk["ln1"])
    # q, k, v are three column blocks of ONE weight, multiplied apart: the
    # same columns as ``h @ wqkv`` split in three, but no [B, L, 3*dim]
    # activation is split (100 MB read, three arrays written, a layer) and
    # no gradient concatenated; slicing the 12 MB weight instead is free
    # (it fuses into the cast).  Each product is viewed by heads straight
    # away: [B, L, dim] -> [B, L, H, D] meets the flash kernels' own view
    # back to [B, L, H*D] and the pair cancels (ops/flash_attention.py,
    # "Layouts"; a slice between the two reshapes made XLA keep a 4-D array
    # of 64-wide rows, sequence-minor, and pay a transpose copy per operand:
    # PERF.md, PR 31).
    # Every product here is a save site of the rematerialised block
    # (ops/remat.py), and so are the flash kernel's output and logsumexp
    # (tagged where they are born); norms, gelu and the adds never.
    q, k, v = (
        remat_lib.product(name, h, w).reshape(b, l, n_heads, head_dim)
        for name, w in zip("qkv", jnp.split(blk["wqkv"].astype(compute_dtype), 3, axis=1))
    )
    # Blockwise causal attention; K/V ring over the sequence axis.
    att = ring_attention(q, k, v, axis_name=axis, causal=True)
    x = x + remat_lib.product("attn_proj", att.reshape(b, l, dim), blk["wo"].astype(compute_dtype))
    h = _rms_norm(x, blk["ln2"])
    h = jax.nn.gelu(remat_lib.product("mlp_up", h, blk["w1"].astype(compute_dtype)))
    return x + h @ blk["w2"].astype(compute_dtype)


def _apply(
    params,
    batch,
    train: bool = False,
    ctx: ParallelContext = ParallelContext(),
    n_heads: int = 4,
    compute_dtype=jnp.bfloat16,
    remat: bool = True,
    **_,
):
    tokens = batch["tokens"]  # [B, L_local] (sequence-sharded over the axis)
    l = tokens.shape[1]
    axis = ctx.axis_name
    # Fail loud on over-long sequences: positions past max_seq would silently
    # CLAMP on the pos_emb gather (same stance as the embedding OOV contract).
    n_shards = axis_size(axis) if axis is not None else 1
    if l * n_shards > params["pos_emb"].shape[0]:
        raise ValueError(
            f"global sequence length {l * n_shards} exceeds max_seq "
            f"{params['pos_emb'].shape[0]}; raise max_seq in the model spec"
        )
    # Global positions of this device's sequence chunk.
    offset = lax.axis_index(axis) * l if axis is not None else 0
    pos = offset + jnp.arange(l)

    x = params["tok_emb"][tokens] + params["pos_emb"][pos][None]
    x = x.astype(compute_dtype)
    # Rematerialization per block in TRAINING: activations inside a block
    # are recomputed during the backward instead of living in HBM for the
    # whole forward — peak activation memory drops from
    # O(n_layers * B * S/n * dim * ~10) to ~one block's worth (+ the residual
    # stream), the standard FLOPs-for-HBM trade for long sequences — except
    # the save sites the byte budget the trainer resolved lets a layer keep
    # (ops/remat.py; 0, off the TPU, keeps nothing).  The ring-attention
    # ppermutes replay fine under remat (pure collective).
    # Eval/predict skip it — there is no backward to save memory for.
    block_fn = functools.partial(
        _block, axis=axis, n_heads=n_heads, compute_dtype=compute_dtype
    )
    x = _run_blocks(block_fn, x, params["blocks"], remat and train, ctx)
    x = _rms_norm(x, params["ln_f"])
    # Weight-tied head; logits in f32 for a stable softmax/CE.
    return (x @ params["tok_emb"].T.astype(compute_dtype)).astype(jnp.float32)


def _run_blocks(block_fn, x, blocks, rematerialise: bool, ctx: ParallelContext):
    """``x`` through the blocks in name order, each rematerialised with its
    own keep-set where ``rematerialise``."""
    names = sorted(blocks)
    fns = [block_fn] * len(names)
    if rematerialise:
        fns = remat_lib.plan(fns, [(x, blocks[name]) for name in names], ctx.remat_keep_bytes)
    for name, fn in zip(names, fns):
        x = fn(x, blocks[name])
    return x


def _tp_block(x, blk, tp_axis, n_heads, compute_dtype):
    """One pre-norm block, tensor-parallel (Megatron split).

    This rank holds ``wqkv``/``w1`` column shards and ``wo``/``w2`` row
    shards; ``x`` (the residual stream) and the norm gains are replicated
    across ``tp``.  Each residual branch costs exactly one tp all-reduce
    (the *g* op after its row-split matmul); the matching *f* op sits
    AFTER the norm so the norm gain differentiates against the full,
    already-summed cotangent rather than one rank's partial.  Attention
    runs complete locally over this rank's ``n_heads/tp`` heads — head
    splitting needs no sequence collective at all.

    With ``tp_axis=None`` (1-D mesh, or no mesh) the shards are the full
    matrices and both collectives drop out: the dense path, bit-identical
    in every column-split matmul, which is what the mesh2d parity probe
    leans on.
    """
    # Trace-time import: a module-level one would close the ops ->
    # parallel -> ops import cycle (parallel/__init__ pulls the trainer,
    # which needs ops.embedding mid-initialization).
    from elasticdl_tpu.parallel.collectives import tp_all_reduce, tp_grad_sync

    b, l, dim = x.shape
    tp = axis_size(tp_axis) if tp_axis is not None else 1
    local_heads = n_heads // tp
    head_dim = dim // n_heads
    h = _rms_norm(x, blk["ln1"])
    if tp_axis is not None:
        h = tp_grad_sync(h, tp_axis)
    qkv = h @ blk["wqkv"].astype(compute_dtype)  # [B, L, 3*dim/tp]
    # HEAD-MAJOR column layout ([q_h | k_h | v_h] per head, heads
    # consecutive): a contiguous 1/tp column shard is then exactly this
    # rank's heads with their complete q/k/v — the split the tp sharding
    # plan's wqkv dim-1 entry produces.  (The sequence-parallel _block
    # reads the same random init as [all-q | all-k | all-v]; both are
    # valid labelings of iid columns, but only head-major composes with
    # contiguous sharding.)
    qkv = qkv.reshape(b, l, local_heads, 3, head_dim)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    announce_path(PATH_XLA_REFERENCE, q, True, "tensor-parallel block")
    att = attention_reference(q, k, v, causal=True)
    out = att.reshape(b, l, dim // tp) @ blk["wo"].astype(compute_dtype)
    if tp_axis is not None:
        out = tp_all_reduce(out, tp_axis)
    x = x + out
    h = _rms_norm(x, blk["ln2"])
    if tp_axis is not None:
        h = tp_grad_sync(h, tp_axis)
    h = jax.nn.gelu(h @ blk["w1"].astype(compute_dtype))
    out = h @ blk["w2"].astype(compute_dtype)
    if tp_axis is not None:
        out = tp_all_reduce(out, tp_axis)
    return x + out


def _tp_apply(
    params,
    batch,
    train: bool = False,
    ctx: ParallelContext = ParallelContext(),
    n_heads: int = 4,
    compute_dtype=jnp.bfloat16,
    remat: bool = True,
    **_,
):
    """Hybrid-parallel forward: batch rows sharded over ``dp`` (each
    device sees ``[B/dp, L]`` complete sequences — positions need no
    axis offset), weight shards over ``ctx.tp_axis``."""
    tokens = batch["tokens"]  # [B_local, L] — full sequences
    l = tokens.shape[1]
    tp = axis_size(ctx.tp_axis) if ctx.tp_axis is not None else 1
    if n_heads % tp:
        raise ValueError(
            f"tensor parallelism {tp} does not divide n_heads {n_heads}; "
            f"pick tp from the head count's divisor chain"
        )
    if l > params["pos_emb"].shape[0]:
        raise ValueError(
            f"sequence length {l} exceeds max_seq "
            f"{params['pos_emb'].shape[0]}; raise max_seq in the model spec"
        )
    pos = jnp.arange(l)
    x = params["tok_emb"][tokens] + params["pos_emb"][pos][None]
    x = x.astype(compute_dtype)
    block_fn = functools.partial(
        _tp_block, tp_axis=ctx.tp_axis, n_heads=n_heads,
        compute_dtype=compute_dtype,
    )
    x = _run_blocks(block_fn, x, params["blocks"], remat and train, ctx)
    x = _rms_norm(x, params["ln_f"])
    return (x @ params["tok_emb"].T.astype(compute_dtype)).astype(jnp.float32)


def _tp_dims(params):
    """The ``ModelSpec.tensor_sharding`` plan: which dim of each weight
    shards over ``tp``.  Column splits (``wqkv``, ``w1``) shard dim 1 —
    their outputs are per-rank slices; row splits (``wo``, ``w2``) shard
    dim 0 — their outputs are partial sums the block's ``tp_all_reduce``
    completes.  Everything else (embeddings, norm gains) replicates."""
    return {
        "tok_emb": None,
        "pos_emb": None,
        "ln_f": None,
        "blocks": {
            name: {
                "ln1": None,
                "wqkv": 1,
                "wo": 0,
                "ln2": None,
                "w1": 1,
                "w2": 0,
            }
            for name in params["blocks"]
        },
    }


def _loss(logits, batch, mask=None):
    # Mean CE over this device's tokens (mask: whole padded SEQUENCES carry
    # zero weight); the trainer's count/total weighting makes it the global
    # mean.
    from elasticdl_tpu.models.metrics import masked_mean

    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["labels"]
    )
    return masked_mean(ce, mask)


def _metrics(logits, batch):
    ce = _loss(logits, batch)
    acc = jnp.mean(
        (jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32)
    )
    return {"loss": ce, "accuracy": acc}


def _example_batch(batch_size: int, seq_len: int = 256):
    return {
        "tokens": jnp.zeros((batch_size, seq_len), jnp.int32),
        "labels": jnp.zeros((batch_size, seq_len), jnp.int32),
    }


def model_spec(
    learning_rate: float = 3e-4,
    compute_dtype: str = "bfloat16",
    vocab: int = 8192,
    dim: int = 256,
    n_heads: int = 4,
    n_layers: int = 2,
    max_seq: int = 4096,
    seq_len: int = 256,
    remat: bool = True,
    parallelism: str = "sequence",
) -> ModelSpec:
    """``parallelism`` picks the scale axis: ``"sequence"`` (default,
    ring attention over a 1-D mesh's sequence shards) or ``"tensor"``
    (Megatron weight shards over the 2D mesh's ``tp`` axis, batch over
    ``dp`` — see module docstring)."""
    if parallelism not in ("sequence", "tensor"):
        raise ValueError(
            f"parallelism must be 'sequence' or 'tensor', got {parallelism!r}"
        )
    dtype = jnp.dtype(compute_dtype)
    tensor = parallelism == "tensor"
    apply_fn = _tp_apply if tensor else _apply
    return ModelSpec(
        name="transformer_lm",
        init=functools.partial(
            _init_params,
            vocab=vocab,
            dim=dim,
            n_heads=n_heads,
            n_layers=n_layers,
            max_seq=max_seq,
        ),
        apply=functools.partial(
            apply_fn, n_heads=n_heads, compute_dtype=dtype, remat=remat
        ),
        loss=_loss,
        metrics=_metrics,
        optimizer=optax.adamw(learning_rate),
        feed=lm_feed,
        example_batch=functools.partial(_example_batch, seq_len=seq_len),
        # sequence parallelism shards dim 1 (see module docstring); tensor
        # parallelism keeps sequences whole and shards examples over dp.
        batch_shard_dim=0 if tensor else 1,
        tensor_sharding=_tp_dims if tensor else None,
        rematerialises=bool(remat),
    )
