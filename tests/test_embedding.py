"""Sharded embedding lookup: parity with a plain gather, and gradient
correctness (incl. duplicate-id accumulation) — the TPU-native analogue of the
reference's embedding-layer-vs-fake-PS unit tests (SURVEY.md §4).

Tables are lane-packed [P, pack*dim] (pack = 128//dim logical rows per
physical row — ops/embedding.py module docstring); a plain [V, dim] table is
the pack == 1 case.  Tests cover both, since models use pack > 1 layouts."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.ops.embedding import (
    ParallelContext,
    embedding_lookup,
    gather_rows,
    pack_table,
    pad_vocab,
    row_pack,
    table_shape,
    unpack_table,
)
from elasticdl_tpu.parallel.mesh import create_mesh

from elasticdl_tpu.common.jax_compat import shard_map

VOCAB = 64  # divisible by 8 so a [V, D] table div-shards cleanly
DIM = 16

# Both collective lookup routes.  "ragged_emulated" runs the real ragged
# routing/offset/unsort code with a dense emulation of the ragged-all-to-all
# collective (XLA:CPU has no ragged-all-to-all HLO; on TPU "auto" resolves to
# the real op through the identical code path).
IMPLS = ("dense", "ragged_emulated")

# Table layouts: plain [V, D] (pack=1: dim passed = width) and lane-packed
# [V/pack, pack*D] (pack=8 for DIM=16).  Both must behave identically.
LAYOUTS = ("plain", "packed")


def _table(rng):
    return jax.random.normal(rng, (VOCAB, DIM), jnp.float32)


def _layout(table2d, layout):
    """(table_array, lookup_dim) for a layout.  'packed' packs WITHOUT vocab
    padding (VOCAB already divides the mesh) so shard math stays exact."""
    if layout == "plain":
        return table2d, DIM
    pack = row_pack(DIM)
    return table2d.reshape(table2d.shape[0] // pack, pack * DIM), DIM


def _sharded_fn(mesh, impl="dense"):
    # Layout needs no parameter: embedding_lookup derives pack/stride from
    # the table array's width and dim=DIM, for plain and packed alike.
    axis = mesh.axis_names[0]
    ctx = ParallelContext(
        axis_name=axis, sharded_embeddings=True, embedding_impl=impl
    )
    return shard_map(
        lambda t, i: embedding_lookup(t, i, ctx, dim=DIM),
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )


def test_pad_vocab_and_shapes():
    # dim 128+ -> pack 1, physical rows = padded vocab, multiple of 256.
    assert pad_vocab(1, 128) == 256
    assert pad_vocab(256, 128) == 256
    assert pad_vocab(257, 128) == 512
    # dim 8 -> pack 16 -> vocab pads to 16*256=4096 logical rows.
    assert row_pack(8) == 16
    assert pad_vocab(1, 8) == 4096
    assert table_shape(1, 8) == (256, 128)
    # Criteo fused table: 26*65536 logical rows, dim 8.
    assert table_shape(26 * 65536, 8) == (26 * 65536 // 16, 128)
    # dim 1 -> pack 128.
    assert table_shape(1000, 1) == (256, 128)
    # dim that isn't a power of two: rows pad to the next-pow2 stride so the
    # physical width stays exactly 128 (misaligned widths gather ~3x slower).
    assert row_pack(48) == 2  # stride 64
    assert table_shape(513, 48) == (512, 128)  # 513 logical -> 1024 padded
    assert row_pack(9) == 8  # stride 16 (the DeepFM folded emb+linear table)
    assert table_shape(26 * 65536, 9) == (26 * 65536 // 8, 128)
    # dim > 128 pads to the next multiple of 128, pack 1.
    assert table_shape(300, 200) == (512, 256)


def test_pack_unpack_roundtrip():
    table = _table(jax.random.key(0))
    packed = pack_table(table, DIM)
    assert packed.shape == table_shape(VOCAB, DIM)
    # Rows survive, padding rows are zero.
    logical = unpack_table(packed, DIM)
    np.testing.assert_array_equal(np.asarray(logical[:VOCAB]), np.asarray(table))
    assert not np.asarray(logical[VOCAB:]).any()
    # Flat input packs identically.
    packed_flat = pack_table(table.reshape(-1), DIM)
    np.testing.assert_array_equal(np.asarray(packed_flat), np.asarray(packed))
    with pytest.raises(ValueError, match="multiple"):
        pack_table(jnp.zeros((65,)), DIM)


def test_packed_lookup_matches_plain(devices):
    """Lane-packed storage must agree with the plain [V, D] path, fwd and grad
    (including duplicate-id accumulation)."""
    table = _table(jax.random.key(0))
    packed, _ = _layout(table, "packed")
    ids = jnp.array([[3, 3], [0, 63], [17, 3]], jnp.int32)
    ctx = ParallelContext()
    out_plain = embedding_lookup(table, ids, ctx)
    out_packed = embedding_lookup(packed, ids, ctx, dim=DIM)
    np.testing.assert_allclose(
        np.asarray(out_packed), np.asarray(out_plain), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(gather_rows(packed, ids, DIM)), np.asarray(out_plain),
        rtol=1e-6,
    )

    cot = jax.random.normal(jax.random.key(2), out_plain.shape)
    g_plain = jax.grad(
        lambda t: jnp.sum(embedding_lookup(t, ids, ctx) * cot)
    )(table)
    g_packed = jax.grad(
        lambda t: jnp.sum(embedding_lookup(t, ids, ctx, dim=DIM) * cot)
    )(packed)
    np.testing.assert_allclose(
        np.asarray(g_packed).reshape(-1, DIM),
        np.asarray(g_plain),
        rtol=1e-5,
    )


def test_stride_padded_lookup_matches_plain():
    """Non-power-of-two dim (9, the DeepFM folded table): rows live at
    stride 16 with dead lanes; lookup and grad must match the plain table."""
    dim = 9
    table = jax.random.normal(jax.random.key(0), (40, dim), jnp.float32)
    packed = pack_table(table, dim)
    assert packed.shape == table_shape(40, dim)
    # dup + 2 OOV (3000 is past the PADDED vocab of 2048; 40..2047 are valid
    # zero padding rows by the module contract, not OOV)
    ids = jnp.array([0, 7, 39, 7, 3000, -1], jnp.int32)
    out = np.asarray(gather_rows(packed, ids, dim))
    exp = np.asarray(table)
    for i, idx in enumerate([0, 7, 39, 7]):
        np.testing.assert_allclose(out[i], exp[idx], rtol=1e-6)
    assert np.isnan(out[4]).all() and np.isnan(out[5]).all()

    cot = jax.random.normal(jax.random.key(1), (6, dim))
    g_packed = jax.grad(
        lambda t: jnp.sum(jnp.where(jnp.isnan(gather_rows(t, ids, dim)), 0.0,
                                    gather_rows(t, ids, dim) * cot))
    )(packed)
    good = [0, 7, 39, 7]
    g_exp = jax.grad(
        lambda t: jnp.sum(jnp.take(t, jnp.array(good), axis=0) * cot[:4])
    )(table)
    np.testing.assert_allclose(
        np.asarray(unpack_table(g_packed, dim))[:40], np.asarray(g_exp),
        rtol=1e-5, atol=1e-6,
    )


def test_pad_embedding_tables_undersized_leaf():
    """A user table built for the RAW vocab (fewer rows than the declared
    padded vocab) zero-pads up to the declared shape; an oversized or
    wrong-width leaf raises."""
    from elasticdl_tpu.models.spec import EmbeddingTableSpec
    from elasticdl_tpu.parallel.trainer import pad_embedding_tables

    spec = [EmbeddingTableSpec(path=("t",), vocab_size=5000, dim=16)]
    leaf = jnp.ones((1000, 16), jnp.float32)
    out = pad_embedding_tables({"t": leaf}, spec)["t"]
    assert out.shape == table_shape(5000, 16)
    logical = unpack_table(out, 16)
    np.testing.assert_array_equal(np.asarray(logical[:1000]), np.asarray(leaf))
    assert not np.asarray(logical[1000:]).any()

    with pytest.raises(ValueError, match="incompatible"):
        pad_embedding_tables({"t": jnp.ones((9000, 16))}, spec)


def test_lookup_validation():
    ctx = ParallelContext()
    with pytest.raises(ValueError, match="pack_table"):
        embedding_lookup(jnp.zeros((64,)), jnp.zeros((2,), jnp.int32), ctx)
    with pytest.raises(ValueError, match="stride"):
        embedding_lookup(
            jnp.zeros((64, 6)), jnp.zeros((2,), jnp.int32), ctx, dim=3
        )


def test_oov_is_nan_local():
    """Single-device fail-loud OOV for both layouts, both id signs."""
    table = _table(jax.random.key(0))
    for layout in LAYOUTS:
        arr, dim = _layout(table, layout)
        ids = jnp.array([0, -1, VOCAB - 1, VOCAB, 2**30, -(2**30)], jnp.int32)
        out = np.asarray(gather_rows(arr, ids, dim))
        np.testing.assert_allclose(out[0], np.asarray(table)[0], rtol=1e-6)
        np.testing.assert_allclose(
            out[2], np.asarray(table)[VOCAB - 1], rtol=1e-6
        )
        for bad in (1, 3, 4, 5):
            assert np.isnan(out[bad]).all(), (layout, bad)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("n_dev", [1, 4, 8])
def test_sharded_lookup_matches_gather(devices, n_dev, impl, layout):
    mesh = create_mesh(devices, num_devices=n_dev)
    table = _table(jax.random.key(0))
    arr, dim = _layout(table, layout)
    ids = jax.random.randint(jax.random.key(1), (32,), 0, VOCAB)

    expected = jnp.take(table, ids, axis=0)
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P(mesh.axis_names[0])))
    out = jax.jit(_sharded_fn(mesh, impl))(sh(arr), sh(ids))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-6)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_lookup_skewed_ids(devices, impl, layout):
    """Worst-case routing skew: every device's ids all live on ONE shard (the
    ragged route's send sizes are maximally unbalanced)."""
    mesh = create_mesh(devices)
    table = _table(jax.random.key(0))
    arr, dim = _layout(table, layout)
    rows_per_shard = VOCAB // 8
    # All 32 ids hit shard 5's row range.
    ids = jax.random.randint(
        jax.random.key(3), (32,), 5 * rows_per_shard, 6 * rows_per_shard
    )
    expected = jnp.take(table, ids, axis=0)
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P(mesh.axis_names[0])))
    out = jax.jit(_sharded_fn(mesh, impl))(sh(arr), sh(ids))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-6)


@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_lookup_2d_ids(devices, impl):
    """ids shaped [batch, n_features] — the tabular-model case."""
    mesh = create_mesh(devices)
    table = _table(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (16, 5), 0, VOCAB)

    expected = jnp.take(table, ids, axis=0)
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P(mesh.axis_names[0])))
    out = jax.jit(_sharded_fn(mesh, impl))(sh(table), sh(ids))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=1e-6)


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_lookup_gradient_accumulates_duplicates(devices, impl, layout):
    """d(loss)/d(table) must scatter-ADD cotangents for duplicate ids — the
    reference's IndexedSlices semantics on the PS side."""
    mesh = create_mesh(devices)
    axis = mesh.axis_names[0]
    table = _table(jax.random.key(0))
    arr, dim = _layout(table, layout)
    # Every device looks up id 3 (heavy duplication across the mesh) plus a
    # unique id, so the grad row for 3 accumulates 8 contributions.
    ids = jnp.array([3, 3, 3, 3, 3, 3, 3, 3, 0, 1, 2, 4, 5, 6, 7, 8], jnp.int32)
    cot = jax.random.normal(jax.random.key(2), (ids.shape[0], DIM))

    def ref_loss(t):
        return jnp.sum(jnp.take(t, ids, axis=0) * cot)

    expected_grad = np.asarray(jax.grad(ref_loss)(table))

    ctx = ParallelContext(
        axis_name=axis, sharded_embeddings=True, embedding_impl=impl
    )

    def local_loss(t, i, c):
        # Per-device scalar, NOT psum'd: under AD each device's cotangent is 1,
        # so the collective transposes deliver d(sum_i loss_i)/d(table) into the
        # row shards.  (psum inside the grad would double-count under
        # check_vma=False, whose conservative psum transpose is psum.)
        vec = embedding_lookup(t, i, ctx, dim=DIM)
        return jnp.sum(vec * c)

    mapped = shard_map(
        jax.grad(local_loss),
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P(axis)))
    grad = np.asarray(jax.jit(mapped)(sh(arr), sh(ids), sh(cot)))
    np.testing.assert_allclose(
        grad.reshape(-1, DIM), expected_grad, rtol=1e-5
    )


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_lookup_oov_is_nan(devices, impl, layout):
    """Fail-loud OOV: ids outside the padded global vocab come back as NaN
    rows in SHARDED mode too (VERDICT r1 'loud OOV'), never zeros or a
    silently wrong row; in-range rows are unaffected."""
    mesh = create_mesh(devices)
    table = _table(jax.random.key(0))
    arr, dim = _layout(table, layout)
    ids = np.random.default_rng(0).integers(0, VOCAB, size=(32,)).astype(np.int32)
    bad_slots = [0, 5, 17, 31]
    ids[bad_slots[0]] = VOCAB * 10  # far out of range
    ids[bad_slots[1]] = -3
    ids[bad_slots[2]] = VOCAB  # first row past the end
    ids[bad_slots[3]] = 2**30  # huge junk id
    ids = jnp.asarray(ids)

    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P(mesh.axis_names[0])))
    out = np.asarray(jax.jit(_sharded_fn(mesh, impl))(sh(arr), sh(ids)))
    for i in range(32):
        if i in bad_slots:
            assert np.isnan(out[i]).all(), f"row {i} (junk id) must be NaN"
        else:
            np.testing.assert_allclose(
                out[i], np.asarray(table)[int(ids[i])], rtol=1e-6
            )


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("impl", IMPLS)
def test_sharded_lookup_oov_gradient_dropped(devices, impl, layout):
    """Junk-id cotangents are dropped, not scattered into a wrong row: the
    grad with junk ids present equals the grad with them excluded."""
    mesh = create_mesh(devices)
    axis = mesh.axis_names[0]
    table = _table(jax.random.key(0))
    arr, dim = _layout(table, layout)
    ids = jnp.array(
        [3, -7, 3, VOCAB * 4, 9, 2**30, 1, 0] + list(range(8)), jnp.int32
    )
    cot = jax.random.normal(jax.random.key(2), (ids.shape[0], DIM))

    good = np.asarray(ids) >= 0
    good &= np.asarray(ids) < VOCAB
    expected = np.asarray(
        jax.grad(
            lambda t: jnp.sum(
                jnp.take(t, jnp.asarray(np.asarray(ids)[good]), axis=0)
                * jnp.asarray(np.asarray(cot)[good])
            )
        )(table)
    )

    ctx = ParallelContext(
        axis_name=axis, sharded_embeddings=True, embedding_impl=impl
    )

    def local_loss(t, i, c):
        vec = embedding_lookup(t, i, ctx, dim=DIM)
        return jnp.sum(jnp.where(jnp.isnan(vec), 0.0, vec * c))

    mapped = shard_map(
        jax.grad(local_loss),
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    sh = lambda a: jax.device_put(a, NamedSharding(mesh, P(axis)))
    grad = np.asarray(jax.jit(mapped)(sh(arr), sh(ids), sh(cot)))
    np.testing.assert_allclose(
        grad.reshape(-1, DIM), expected, rtol=1e-5, atol=1e-6
    )


# The ragged route's ids go by ONE all_gather and stay where they land in
# the owner's [n, L] slots (PR 55): the cases its plan has.  Per device 24
# ids into a packed table of 2048 rows of 11 floats (pack 8, as DeepFM's).
ROUTE_VOCAB, ROUTE_DIM, ROUTE_L = 2048, 11, 24


def _route_ids(case: str, n: int) -> np.ndarray:
    """[n * ROUTE_L] int32: device k looks up ids[k * L : (k + 1) * L]."""
    rng = np.random.default_rng(55 + n)
    rows_local = ROUTE_VOCAB // n
    ids = rng.integers(0, ROUTE_VOCAB, size=(n, ROUTE_L))
    if case == "one_owner":  # worst-case skew: the last shard receives n * L
        ids = rng.integers(ROUTE_VOCAB - rows_local, ROUTE_VOCAB, size=(n, ROUTE_L))
    elif case == "a_starved_shard":  # shard 0 receives nothing
        ids = rng.integers(rows_local, ROUTE_VOCAB, size=(n, ROUTE_L))
    elif case == "junk":  # negative and >= n * rows, on every sender
        ids[:, 0], ids[:, 5], ids[:, 11], ids[:, 23] = -7, ROUTE_VOCAB, 2**30, ROUTE_VOCAB * 3
        ids[0, 1] = -(2**30)
    elif case == "duplicates":  # one id from every sender, six times each
        ids[:, ::4] = 3 * rows_local // 2
    else:
        assert case == "uniform", case
    return ids.reshape(-1).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _routes(n_dev: int):
    """``{route: (table, ids, cot) -> (vectors, the table's gradient, [rows received, rows in range] a shard)}`` on a
    mesh of ``n_dev`` devices, each ONE jitted program: the dense route, the emulated ragged one, and the ragged one
    with its rows HANDED over (the carrier's rows added, as the trainer's sweep does, by a scatter-add).  The ids are
    an operand, so the five id cases of a mesh read one compiled triple (each case compiled its own three: PR 66)."""
    from elasticdl_tpu.ops.embedding import route_taps

    mesh = create_mesh(jax.devices(), num_devices=n_dev)
    axis = mesh.axis_names[0]
    P_local, W = ROUTE_VOCAB // 8 // n_dev, 128       # ``pack_table``'s: 8 rows of 11 floats to a physical row of 128

    def run(impl, handed=False):
        ctx = ParallelContext(axis_name=axis, sharded_embeddings=True, embedding_impl=impl)

        def loss(t, i, c, carrier=None):
            hand = ((), None) if carrier is None else ([t], (carrier,))
            with route_taps(*hand) as taps:
                vec = embedding_lookup(t, i, ctx, dim=ROUTE_DIM)
            counts = jnp.stack([
                sum(taps.rows_received) if taps.rows_received else jnp.int32(0),
                sum(rows for rows, _, _ in taps.table_grad),
            ])
            physical = None if carrier is None else taps.handed[0][1]
            return jnp.sum(jnp.where(jnp.isnan(vec), 0.0, vec * c)), (vec, counts, physical)

        def local(t, i, c):
            if not handed:
                (_, (vec, counts, _)), table_bar = jax.value_and_grad(loss, has_aux=True)(t, i, c)
                return vec, table_bar, counts
            carrier = jnp.zeros((n_dev * ROUTE_L, W), t.dtype)
            (_, (vec, counts, physical)), rows_bar = jax.value_and_grad(
                loss, argnums=3, has_aux=True
            )(t, i, c, carrier)
            # what the trainer's sweep does with the pair, by a scatter-add
            table_bar = jnp.zeros((P_local + 1, W), t.dtype).at[physical].add(rows_bar)
            return vec, table_bar[:P_local], counts

        return jax.jit(shard_map(
            local, mesh=mesh, in_specs=(P(axis),) * 3, out_specs=(P(axis),) * 3,
            check_vma=False,
        ))

    return {"dense": run("dense"), "ragged": run("ragged_emulated"), "handed": run("ragged_emulated", handed=True)}


@pytest.mark.parametrize("case", ["uniform", "one_owner", "a_starved_shard", "junk", "duplicates"])
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_ragged_route_is_the_dense_one_and_hands_over_what_it_scatters(devices, n_dev, case):
    """On the emulated route: forward equal to ``_dense_lookup``'s exactly,
    table gradient equal to the dense route's to float32 summation order,
    the rows a shard received and those inside its range what the ids say,
    and the handed carrier's rows, added at the physical rows handed beside
    them, the plain path's table gradient."""
    rng = np.random.default_rng(7)
    table = pack_table(
        jnp.asarray(rng.standard_normal((ROUTE_VOCAB, ROUTE_DIM)), jnp.float32), ROUTE_DIM
    )
    assert table.shape == (ROUTE_VOCAB // 8, 128)
    ids = _route_ids(case, n_dev)
    cot = jnp.asarray(rng.standard_normal((ids.shape[0], ROUTE_DIM)), jnp.float32)
    rows_local = ROUTE_VOCAB // n_dev
    routes = _routes(n_dev)
    run = lambda route: routes[route](table, jnp.asarray(ids), cot)  # noqa: E731

    dense_vec, dense_bar, _ = run("dense")
    vec, bar, counts = run("ragged")
    np.testing.assert_array_equal(np.asarray(vec), np.asarray(dense_vec))
    np.testing.assert_allclose(np.asarray(bar), np.asarray(dense_bar), rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(dense_bar).max()) > 0.5
    good = (ids >= 0) & (ids < ROUTE_VOCAB)
    assert np.isnan(np.asarray(vec)).all(axis=1).tolist() == (~good).tolist()
    # a junk id goes to the clamped owner, which finds it outside its rows
    owner = np.clip(ids // rows_local, 0, n_dev - 1)
    received = np.bincount(owner, minlength=n_dev)
    in_range = np.bincount(owner[good], minlength=n_dev)
    np.testing.assert_array_equal(
        np.asarray(counts).reshape(n_dev, 2), np.stack([received, in_range], axis=1)
    )
    if case == "one_owner":
        assert received[-1] == n_dev * ROUTE_L
    if case == "a_starved_shard":
        assert received[0] == 0

    handed_vec, handed_bar, handed_counts = run("handed")
    np.testing.assert_array_equal(np.asarray(handed_vec), np.asarray(vec))
    np.testing.assert_array_equal(np.asarray(handed_counts), np.asarray(counts))
    np.testing.assert_allclose(np.asarray(handed_bar), np.asarray(bar), rtol=1e-6, atol=1e-6)


def test_resolve_impl_mesh_size_aware():
    """auto at axis_size 1 is a local gather (dense n=1 short-circuit), never
    the ragged machinery — VERDICT r2 Weak #1.  Explicit impls pass through."""
    from elasticdl_tpu.ops.embedding import resolve_impl

    assert resolve_impl("auto", "tpu", axis_size=1) == "dense"
    assert resolve_impl("auto", "tpu", axis_size=8) == "ragged"
    assert resolve_impl("auto", "cpu", axis_size=8) == "dense"
    assert resolve_impl("ragged", "tpu", axis_size=1) == "ragged"
    assert resolve_impl("ragged_emulated", "cpu", axis_size=1) == "ragged_emulated"
    with pytest.raises(ValueError, match="unknown"):
        resolve_impl("bogus")


def test_lookup_impls_match_config():
    """config.py inlines the impl tuple (to stay jax-free); keep in sync."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.ops.embedding import LOOKUP_IMPLS

    for impl in LOOKUP_IMPLS:
        JobConfig(embedding_lookup_impl=impl).validate()
    with pytest.raises(ValueError, match="embedding_lookup_impl"):
        JobConfig(embedding_lookup_impl="bogus").validate()


# ---------------------------------------------------------------------------
# The table cotangent by the sorted merge sweep (ops/table_grad.py), run in
# Pallas interpret mode on the CPU as ops/flash_attention.py's kernels are.
# ---------------------------------------------------------------------------

SWEEP_ROWS = 256  # buffer rows; tiles of 64, chunks of 128 update rows


def _zipf_ids(rng, n, rows):
    p = np.arange(1, rows + 1, dtype=np.float64) ** -1.05
    return rng.permutation(rows)[rng.choice(rows, n, p=p / p.sum())]


# name -> (ids as a function of (rng), bit-equal to the scatter-add?)
SWEEP_CASES = {
    "uniform": (lambda r: r.integers(0, SWEEP_ROWS, 300), False),
    "distinct": (lambda r: r.permutation(SWEEP_ROWS)[:200], True),
    "one_id": (lambda r: np.full(300, 37), False),
    "zipf": (lambda r: _zipf_ids(r, 300, SWEEP_ROWS), False),
    "three_quarters_filler": (
        lambda r: r.permutation(
            np.concatenate([r.permutation(SWEEP_ROWS)[:80], np.full(240, SWEEP_ROWS)])
        ),
        True,
    ),
    "tile_edges": (lambda r: np.array([0, 63, 64, 127, 128, 191, 192, 255]), True),
    "a_tile_with_no_update": (lambda r: r.permutation(128)[:100] + 128, True),
    # 300 rows for one tile of 64: chunks 0, 1 and the loop's 2.
    "a_tile_with_three_chunks": (lambda r: r.integers(64, 128, 300), False),
    "n_not_a_multiple_of_the_chunk": (lambda r: r.permutation(SWEEP_ROWS)[:131], True),
    "rows_not_a_multiple_of_the_tile": (lambda r: r.permutation(200)[:150], True),
}


#: What the sweep can observe of its input (PR 68): kind -> (the rows' dtype, a weight a row?, the sum's dtype, one-hot
#: products a chunk).  Float32 rows are split into three exact bfloat16 pieces; a bfloat16 row IS its one piece, the sum
#: is made in float32 and rounded once; with a weight its three pieces stand in the one-hot and the sum stays float32.
SWEEP_KINDS = {
    "float32": (jnp.float32, False, jnp.float32, 3),
    "bfloat16": (jnp.bfloat16, False, jnp.bfloat16, 1),
    "bfloat16_weighted": (jnp.bfloat16, True, jnp.float32, 3),
    "float32_weighted": (jnp.float32, True, jnp.float32, 3),
}
#: distinct ids, repeated ids, a filler id, a hot tile that enters the double-buffered loop, a padded last chunk
NARROW_CASES = ["distinct", "zipf", "three_quarters_filler", "a_tile_with_three_chunks", "n_not_a_multiple_of_the_chunk"]


@pytest.mark.parametrize("kind,case", [
    *(("float32", case) for case in sorted(SWEEP_CASES)),
    *((kind, case) for kind in ("bfloat16", "bfloat16_weighted") for case in NARROW_CASES),
    ("float32_weighted", "zipf"), ("float32_weighted", "three_quarters_filler"),
])
def test_merge_sweep_builds_the_scatter_adds_buffer(kind, case):
    from elasticdl_tpu.ops.table_grad import sweep_table_grad

    make_ids, exact = SWEEP_CASES[case]
    dtype, weighted, out_dtype, _ = SWEEP_KINDS[kind]
    rng = np.random.default_rng(7)
    ids = jnp.asarray(make_ids(rng), jnp.int32)
    num_rows = 200 if case == "rows_not_a_multiple_of_the_tile" else SWEEP_ROWS
    rows = jnp.asarray(rng.standard_normal((ids.shape[0], 128)), jnp.float32).astype(dtype)
    weights = jnp.asarray(rng.uniform(0.01, 2.0, ids.shape[0]), jnp.float32) if weighted else None
    sweep = jax.jit(lambda i, r, w: sweep_table_grad(i, r, num_rows, w, tile=64, chunk=128))
    got = sweep(ids, rows, weights)
    assert got.dtype == out_dtype
    if kind == "float32_weighted":
        # float32 rows take their weight in XLA, ahead of the sweep: today's ``f32(y) * w`` and sum, to the bit
        np.testing.assert_array_equal(np.asarray(got), np.asarray(sweep(ids, rows * weights[:, None], None)))
        return
    if kind == "bfloat16_weighted":
        # exact products summed in float32 against one float32 rounding a product, summed: float32's rounding
        want = jax.ops.segment_sum(rows.astype(jnp.float32) * weights[:, None], ids, num_rows + 1)[:num_rows]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=300 * 2**-23 * 16)
        return
    expected = jnp.zeros((num_rows, 128), jnp.float32).at[ids].add(rows.astype(jnp.float32), mode="drop")
    if kind == "bfloat16":
        # a float32 sum of the bfloat16 rows, rounded once: where ids are distinct the rows themselves
        want = expected.astype(jnp.bfloat16)
        if exact:
            np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
        else:  # another summation order may round the last bit the other way
            np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2**-7, atol=1e-6)
    elif exact:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))
    else:
        # Same addends, another order: f32 summation error of <= 300 terms.
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(expected), rtol=0, atol=300 * 2**-23 * 8
        )


@pytest.mark.parametrize("kind", sorted(SWEEP_KINDS))
def test_the_sweep_takes_as_many_pieces_as_its_rows_need(kind):
    """Read off the jaxpr: the kernel body makes its one-hot product at three places (a tile's first two chunks and
    the hot tiles' loop), three products each for float32 rows or a weight, ONE for bfloat16 rows; no float32 copy of
    bfloat16 rows is made on the way to the kernel, and the unweighted bfloat16 sum leaves it in bfloat16."""
    from elasticdl_tpu.ops.table_grad import sweep_table_grad

    dtype, weighted, out_dtype, products = SWEEP_KINDS[kind]
    ids, rows = jnp.zeros((300,), jnp.int32), jnp.zeros((300, 128), dtype)
    weights = jnp.ones((300,), jnp.float32) if weighted else None
    text = str(jax.make_jaxpr(lambda i, r, w: sweep_table_grad(i, r, SWEEP_ROWS, w, tile=64, chunk=128))(ids, rows, weights))
    assert text.count("dot_general") == 3 * products
    assert text.count("pallas_call") == 1
    if dtype == jnp.bfloat16:
        assert "f32[384,128]" not in text and "f32[300,128]" not in text
    assert re.search(r"\w+:(\w+)\[256,128\] = pallas_call", text).group(1) == {jnp.bfloat16: "bf16", jnp.float32: "f32"}[out_dtype]


@pytest.fixture
def swept(monkeypatch):
    """The choice the program makes on a TPU for a big table, made here for
    a small one: the platform forced, the threshold lowered.  The kernel
    then runs in the interpreter (the backend is still the CPU)."""
    from elasticdl_tpu.ops import embedding

    monkeypatch.setattr(embedding, "_on_tpu", lambda: True)
    monkeypatch.setattr(embedding, "SWEEP_MIN_ROWS", 8)


def _mosaic_calls(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


@pytest.mark.parametrize("dim", [11, 128])  # pack 8 in a stride of 16; pack 1
def test_swept_gather_rows_gradient_is_the_transposes(monkeypatch, swept, dim):
    from elasticdl_tpu.ops import embedding

    rng = np.random.default_rng(3)
    vocab = 8 * 256
    table = pack_table(jnp.asarray(rng.standard_normal((vocab, dim)), jnp.float32), dim)
    ids = jnp.asarray(
        np.concatenate([rng.integers(0, vocab, 500), [-1, vocab * 9, 5, 5, 5]]), jnp.int32
    )
    cot = jnp.asarray(rng.standard_normal((ids.shape[0], dim)), jnp.float32)

    def loss(t):
        vec = gather_rows(t, ids, dim)
        return jnp.sum(jnp.where(jnp.isnan(vec), 0.0, vec * cot))

    got = jax.jit(jax.grad(loss))(table)
    assert _mosaic_calls(jax.grad(loss), table) == 1
    monkeypatch.setattr(embedding, "_on_tpu", lambda: False)
    expected = jax.jit(jax.grad(loss))(table)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=0, atol=2e-6)
    assert float(jnp.abs(expected).max()) > 0.5


@pytest.mark.parametrize("route", ["local", "dense", "ragged_emulated"])
def test_swept_lookup_gradient_equals_the_transpose_on_every_route(
    devices, monkeypatch, swept, route
):
    """``jax.grad`` through ``embedding_lookup`` with the sweep in the
    backward against the same with the AD transpose, duplicates and an
    out-of-vocabulary id included; four devices on the sharded routes."""
    from elasticdl_tpu.ops import embedding

    n = 1 if route == "local" else 4
    mesh = create_mesh(devices, num_devices=n)
    axis = mesh.axis_names[0]
    dim, vocab = 11, 8 * 64 * n
    rng = np.random.default_rng(5)
    table = pack_table(jnp.asarray(rng.standard_normal((vocab, dim)), jnp.float32), dim)
    ids = jnp.asarray(
        np.concatenate([rng.integers(0, vocab, 120), [3] * 7, [vocab * 3]]), jnp.int32
    )
    cot = jnp.asarray(rng.standard_normal((ids.shape[0], dim)), jnp.float32)
    ctx = ParallelContext(
        axis_name=axis if n > 1 else None, sharded_embeddings=n > 1, embedding_impl=route
        if n > 1 else "auto",
    )

    def local_loss(t, i, c):
        vec = embedding_lookup(t, i, ctx, dim=dim)
        return jnp.sum(jnp.where(jnp.isnan(vec), 0.0, vec * c))

    def grad_fn():
        if n == 1:
            return jax.jit(jax.grad(local_loss))
        return jax.jit(shard_map(
            jax.grad(local_loss), mesh=mesh, in_specs=(P(axis),) * 3,
            out_specs=P(axis), check_vma=False,
        ))

    got = grad_fn()(table, ids, cot)
    assert "pallas_call" in str(jax.make_jaxpr(grad_fn())(table, ids, cot))
    monkeypatch.setattr(embedding, "_on_tpu", lambda: False)
    expected = grad_fn()(table, ids, cot)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=0, atol=2e-6)
    assert float(jnp.abs(expected).max()) > 0.5


# (platform is a TPU, physical rows, width, dtype) -> Mosaic calls in the grad
SELECTION = {
    "big_table_on_a_tpu": (True, 3 << 19, 128, jnp.float32, 1),
    "big_table_on_the_cpu": (False, 3 << 19, 128, jnp.float32, 0),
    "small_table_on_a_tpu": (True, (3 << 19) - 256, 128, jnp.float32, 0),
    "wide_rows_on_a_tpu": (True, 3 << 19, 256, jnp.float32, 0),
    "bfloat16_table_on_a_tpu": (True, 3 << 19, 128, jnp.bfloat16, 0),
}


@pytest.mark.parametrize("case", sorted(SELECTION))
def test_the_sweep_is_chosen_from_platform_and_table_shape(monkeypatch, case):
    """One constant decides, read against the table's shape at trace time;
    nothing is run (the tables are shapes)."""
    from elasticdl_tpu.ops import embedding

    on_tpu, rows, width, dtype, calls = SELECTION[case]
    monkeypatch.setattr(embedding, "_on_tpu", lambda: on_tpu)
    assert embedding.SWEEP_MIN_ROWS == 3 << 19
    table = jax.ShapeDtypeStruct((rows, width), dtype)
    ids = jax.ShapeDtypeStruct((64,), jnp.int32)

    def grad(t, i):
        return jax.grad(lambda t: jnp.sum(gather_rows(t, i, 16).astype(jnp.float32)))(t)

    assert _mosaic_calls(grad, table, ids) == calls


def test_integer_tables_keep_the_plain_gather(monkeypatch, swept):
    table = jnp.arange(64 * 128, dtype=jnp.int32).reshape(64, 128)
    out = gather_rows(table, jnp.array([3, 64, -1]), 128)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(table[3]))
    assert int(jnp.abs(out[1:]).max()) == 0
