"""Data layer: recordio format round-trip, shard range addressing, codec
round-trips, and end-to-end synthetic-file -> feed -> train-step for each
model family (the reference's data-reader unit tests, SURVEY.md §4)."""

import numpy as np
import pytest

from elasticdl_tpu.data import codecs, synthetic
from elasticdl_tpu.data.reader import (
    CSVDataReader,
    RecordIODataReader,
    Shard,
    create_data_reader,
)
from elasticdl_tpu.data.recordio import RecordIOReader, write_records


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "data.rio")
    records = [b"hello", b"", b"x" * 10_000, bytes(range(256))]
    assert write_records(path, records) == 4
    reader = RecordIOReader(path)
    assert len(reader) == 4
    assert list(reader.read_range(0, 4)) == records
    assert list(reader.read_range(1, 3)) == records[1:3]
    assert list(reader.read_range(3, 99)) == records[3:]


def test_recordio_crc_detects_corruption(tmp_path):
    path = str(tmp_path / "data.rio")
    write_records(path, [b"payload-one", b"payload-two"])
    raw = bytearray(open(path, "rb").read())
    raw[-3] ^= 0xFF  # flip a byte inside the last payload
    open(path, "wb").write(bytes(raw))
    reader = RecordIOReader(path)
    with pytest.raises(IOError):
        list(reader.read_range(0, 2))


def test_recordio_reader_shards(tmp_path):
    path = str(tmp_path / "d.rio")
    write_records(path, [b"r%d" % i for i in range(25)])
    reader = RecordIODataReader(path)
    shards = reader.create_shards(records_per_shard=10)
    assert [(s.start, s.end) for s in shards] == [(0, 10), (10, 20), (20, 25)]
    assert list(reader.read_records(shards[2])) == [b"r20", b"r21", b"r22", b"r23", b"r24"]


def test_csv_reader_shards_and_header(tmp_path):
    path = str(tmp_path / "d.csv")
    path2 = str(tmp_path / "e.csv")
    open(path, "w").write("h1,h2\n1,a\n2,b\n3,c\n")
    open(path2, "w").write("h1,h2\n4,d\n")
    reader = CSVDataReader(str(tmp_path), skip_header=True)
    shards = sorted(reader.create_shards(2), key=lambda s: (s.name, s.start))
    assert [(s.start, s.end) for s in shards] == [(0, 2), (2, 3), (0, 1)]
    assert list(reader.read_records(Shard(path, 1, 3))) == [b"2,b", b"3,c"]


def test_format_sniffing(tmp_path):
    rio = str(tmp_path / "a.data")
    write_records(rio, [b"x"])
    csv = str(tmp_path / "b.data")
    open(csv, "w").write("1,2\n")
    assert isinstance(create_data_reader(rio), RecordIODataReader)
    assert isinstance(create_data_reader(csv), CSVDataReader)


def test_criteo_codec_roundtrip():
    rec = codecs.encode_criteo_example(1, list(range(13)), list(range(26)))
    batch = codecs.criteo_feed([rec, rec])
    assert batch["dense"].shape == (2, 13)
    assert batch["cat"].shape == (2, 26)
    np.testing.assert_array_equal(batch["labels"], [1, 1])
    np.testing.assert_array_equal(batch["cat"][0], np.arange(26))


def test_packed_records_sequence_semantics():
    from elasticdl_tpu.data.packed import PackedRecords, as_packed

    records = [b"alpha", b"", b"x" * 100, b"tail"]
    packed = as_packed(records)
    assert len(packed) == 4
    assert list(packed) == records
    assert packed[2] == records[2]
    assert packed[-1] == b"tail"
    view = packed[1:3]
    assert isinstance(view, PackedRecords)
    assert list(view) == records[1:3]
    assert view.tobytes() == b"".join(records[1:3])
    assert as_packed(packed) is packed
    with pytest.raises(ValueError):
        packed[::2]


def test_criteo_native_decode_matches_python():
    """The C++ decoder and the Python loop (the format's source of truth)
    must agree bit-for-bit — including blanks, missing trailing fields,
    negatives, decimals, and full-range hex ids."""
    from elasticdl_tpu.data.packed import as_packed
    from elasticdl_tpu.ps.host_store import native_lib_available

    if not native_lib_available():
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(3)
    records = [
        codecs.encode_criteo_example(
            int(rng.integers(0, 2)),
            [None if rng.random() < 0.2 else int(rng.integers(-50, 1000))
             for _ in range(13)],
            [int(rng.integers(0, 1 << 32)) for _ in range(26)],
        )
        for _ in range(256)
    ]
    records.append(b"1")                      # label only
    records.append(b"0\t\t\t")                # blank dense fields
    records.append(b"1\t3.5\t-2.25\t1e2")     # decimals + exponent
    records.append(b"0" + b"\t7" * 13 + b"\tdeadBEEF")  # mixed-case hex

    def py_feed(recs):
        n = len(recs)
        dense = np.zeros((n, 13), np.float32)
        cat = np.zeros((n, 26), np.int32)
        labels = np.zeros((n,), np.int32)
        for i, rec in enumerate(recs):
            parts = rec.decode().split("\t")
            labels[i] = int(parts[0])
            for j, v in enumerate(parts[1:14]):
                dense[i, j] = float(v) if v else 0.0
            for j, v in enumerate(parts[14:]):
                cat[i, j] = np.int32(np.uint32(int(v, 16))) if v else 0
        return {"dense": dense, "cat": cat, "labels": labels}

    ref = py_feed(records)
    for form in (records, as_packed(records)):
        out = codecs.criteo_feed(form)
        for key in ref:
            np.testing.assert_array_equal(ref[key], out[key], err_msg=key)


def test_criteo_native_decode_rejects_malformed():
    from elasticdl_tpu.ps.host_store import native_lib_available

    if not native_lib_available():
        pytest.skip("native lib unavailable")
    with pytest.raises(ValueError, match="record 1"):
        codecs.criteo_feed([b"1\t2", b"not-a-label\t2"])
    with pytest.raises(ValueError):  # non-hex categorical
        codecs.criteo_feed([b"1" + b"\t1" * 13 + b"\tzzzz"])


def test_recordio_packed_read_and_crc(tmp_path):
    path = str(tmp_path / "data.rio")
    records = [b"hello", b"", b"x" * 10_000, bytes(range(256))]
    write_records(path, records)
    reader = RecordIOReader(path)
    assert list(reader.read_range_packed(0, 4)) == records
    assert list(reader.read_range_packed(1, 3)) == records[1:3]
    assert list(reader.read_range_packed(3, 99)) == records[3:]
    assert len(reader.read_range_packed(2, 2)) == 0
    raw = bytearray(open(path, "rb").read())
    raw[-3] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(IOError):
        RecordIOReader(path).read_range_packed(0, 4)


def test_reader_packed_matches_iter(tmp_path):
    path = str(tmp_path / "d.rio")
    write_records(path, [b"r%d" % i for i in range(25)])
    reader = RecordIODataReader(path)
    shard = Shard(path, 10, 20)
    assert list(reader.read_records_packed(shard)) == list(
        reader.read_records(shard)
    )


def test_prefetch_order_and_errors():
    from elasticdl_tpu.data.prefetch import prefetch

    assert list(prefetch(iter(range(100)), depth=3)) == list(range(100))
    assert list(prefetch(iter(range(5)), depth=0)) == list(range(5))

    def boom():
        yield 1
        yield 2
        raise RuntimeError("decode failed")

    it = prefetch(boom(), depth=2)
    assert next(it) == 1
    assert next(it) == 2
    with pytest.raises(RuntimeError, match="decode failed"):
        next(it)


def test_criteo_feed_pre_matches_device_transforms():
    """The pipeline-preprocessed feed must equal the on-device transforms:
    cat buckets bit-for-bit (models/tabular.py hash), dense within one f16
    ulp of log1p, labels exact — for BOTH the C++ decoder and the numpy
    fallback."""
    from elasticdl_tpu.models.tabular import fuse_feature_ids_np
    from elasticdl_tpu.ps.host_store import native_lib_available

    rng = np.random.default_rng(5)
    records = [
        codecs.encode_criteo_example(
            int(rng.integers(0, 2)),
            [None if rng.random() < 0.2 else int(rng.integers(0, 100000))
             for _ in range(13)],
            [int(rng.integers(0, 1 << 32)) for _ in range(26)],
        )
        for _ in range(512)
    ]
    buckets = 65536
    raw = codecs.criteo_feed(records)
    expect_ids = fuse_feature_ids_np(raw["cat"], buckets)
    offsets = np.arange(26, dtype=np.int64) * buckets
    expect_dense = np.log1p(np.maximum(raw["dense"], 0.0))

    def check(pre):
        np.testing.assert_array_equal(
            pre["cat"].astype(np.int64) + offsets, expect_ids
        )
        np.testing.assert_array_equal(pre["labels"], raw["labels"])
        assert pre["dense"].dtype == np.float16
        np.testing.assert_allclose(
            pre["dense"].astype(np.float32), expect_dense, rtol=1e-3
        )

    if native_lib_available():
        check(codecs.criteo_feed_pre(records, buckets=buckets))
        # Native f16 rounding must match numpy's cast bit-for-bit.
        np.testing.assert_array_equal(
            codecs.criteo_feed_pre(records, buckets=buckets)["dense"].view(
                np.uint16
            ),
            expect_dense.astype(np.float16).view(np.uint16),
        )

    # numpy fallback (force it by importing the fallback branch directly)
    h = raw["cat"].astype(np.uint32) * np.uint32(2654435761)
    h ^= h >> np.uint32(16)
    fallback = {
        "dense": expect_dense.astype(np.float16),
        "cat": (h % np.uint32(buckets)).astype(np.uint16),
        "labels": raw["labels"].astype(np.uint8),
    }
    check(fallback)


def test_deepfm_pipeline_preprocess_matches_device_path(devices):
    """Same records through pipeline_preprocess=True and =False specs give
    the same logits (up to the f16 wire rounding, far below bf16 compute
    noise)."""
    import jax

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    rng = np.random.default_rng(9)
    records = [
        codecs.encode_criteo_example(
            int(rng.integers(0, 2)),
            [int(rng.integers(0, 1000)) for _ in range(13)],
            [int(rng.integers(0, 1 << 32)) for _ in range(26)],
        )
        for _ in range(64)
    ]
    mesh = create_mesh(devices[:4])
    outs = {}
    for pre in (False, True):
        spec = load_model_spec(
            "elasticdl_tpu.models",
            "deepfm.model_spec",
            buckets_per_feature=512,
            embedding_dim=4,
            hidden=(16,),
            compute_dtype="float32",
            host_tier=False,
            pipeline_preprocess=pre,
        )
        batch = spec.feed(records)
        assert batch["cat"].dtype == (np.uint16 if pre else np.int32)
        trainer = Trainer(spec, JobConfig(), mesh)
        state = trainer.init_state(jax.random.key(0))
        outs[pre] = np.asarray(
            trainer.run_predict_step(state, batch)
        )
    np.testing.assert_allclose(outs[True], outs[False], rtol=2e-3, atol=2e-3)


def test_census_native_decode_matches_layers():
    """The C++ census decoder must equal the preprocessing-layer pipeline
    (ToNumber + Hashing crc32) bit-for-bit, including blanks, whitespace,
    decimals, and invalid numerics."""
    from elasticdl_tpu.preprocessing import Hashing, ToNumber
    from elasticdl_tpu.ps.host_store import native_lib_available

    if not native_lib_available():
        pytest.skip("native lib unavailable")
    records = [
        codecs.encode_census_example(0, [39, 13, 0, 0, 40], ["private"] * 9),
        codecs.encode_census_example(1, [17.5, 1, 5000, 0, 12.25], ["a b", ""] + ["x"] * 7),
        b"1, 39 ,13,,40,junk, gov,hs,married,tech,husband,white,male,us,a".replace(b"junk", b"oops"),
        b"0,1e2,2.5,-3,0.0,4,w1,w2,w3,w4,w5,w6,w7,w8,w9",
    ]

    def layer_feed(recs):
        to_number = ToNumber(out_dtype="float32", default=0.0)
        hashing = Hashing(1 << 31)
        n = len(recs)
        dense_raw = np.empty((n, 5), object)
        cat_raw = np.empty((n, 9), object)
        labels = np.zeros((n,), np.int32)
        for i, rec in enumerate(recs):
            parts = rec.decode().split(",")
            labels[i] = int(parts[0])
            dense_raw[i] = parts[1:6]
            cat_raw[i] = [v.strip() for v in parts[6:]]
        return {
            "dense": to_number(dense_raw),
            "cat": hashing(cat_raw).astype(np.int32),
            "labels": labels,
        }

    ref = layer_feed(records)
    out = codecs.census_feed(records)
    for key in ref:
        np.testing.assert_array_equal(ref[key], out[key], err_msg=key)


def test_census_codec_roundtrip():
    rec = codecs.encode_census_example(0, [39, 13, 0, 0, 40], ["private"] * 9)
    batch = codecs.census_feed([rec])
    assert batch["dense"].shape == (1, 5)
    assert batch["cat"].shape == (1, 9)
    assert (batch["cat"] >= 0).all()


@pytest.mark.parametrize(
    "family,model_def,n",
    [
        ("mnist", "mnist.model_spec", 64),
        ("cifar10", "cifar10_resnet.model_spec", 32),
        ("criteo", "deepfm.model_spec", 64),
        ("census", "wide_deep.model_spec", 64),
    ],
)
def test_synthetic_to_train_step(tmp_path, devices, family, model_def, n):
    """File on disk -> reader shard -> feed -> one mesh train step."""
    import jax

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    path = str(tmp_path / f"{family}.data")
    synthetic.generate(family, path, n)
    reader = create_data_reader(path)
    shards = reader.create_shards(n)
    assert sum(s.size for s in shards) == n

    tiny = {
        "deepfm.model_spec": dict(buckets_per_feature=64, hidden=(16,)),
        "wide_deep.model_spec": dict(buckets=32, hidden=(16,)),
        "cifar10_resnet.model_spec": dict(depth=14, width=8),
    }.get(model_def, {})
    spec = load_model_spec(
        "elasticdl_tpu.models", model_def, compute_dtype="float32", **tiny
    )
    batch = spec.feed(list(reader.read_records(shards[0])))
    trainer = Trainer(spec, JobConfig(), create_mesh(devices))
    state = trainer.init_state(jax.random.key(0))
    state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    assert np.isfinite(float(metrics["loss"]))


def test_csv_packed_matches_iter(tmp_path):
    path = str(tmp_path / "d.csv")
    open(path, "wb").write(b"h1,h2\n1,a\r\n2,b\n3,c\n4,d")  # mixed EOLs, no final NL
    reader = CSVDataReader(path, skip_header=True)
    for shard in (Shard(path, 0, 4), Shard(path, 1, 3), Shard(path, 2, 99)):
        assert list(reader.read_records_packed(shard)) == list(
            reader.read_records(shard)
        )


def test_prefetch_cancellation_releases_producer():
    """An abandoned consumer must cancel the producer thread (pre-r4-review
    it parked on the bounded queue forever, pinning decoded batches)."""
    import threading
    import time

    from elasticdl_tpu.data.prefetch import prefetch

    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield i

    before = threading.active_count()
    it = prefetch(gen(), depth=2)
    assert next(it) == 0
    it.close()  # abandon mid-iteration -> cancel event fires
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, "producer thread leaked"
    assert len(produced) < 1000  # producer stopped early, not drained


def test_prefetch_abandoned_before_first_pull_starts_no_thread():
    """A generator abandoned before its first next() never runs its body, so
    its finally can't cancel anything — the producer must therefore start
    lazily on the first pull (ADVICE r4 #1), or it would spin forever."""
    import gc
    import threading

    from elasticdl_tpu.data.prefetch import prefetch

    produced = []

    def gen():
        for i in range(1000):
            produced.append(i)
            yield i

    before = threading.active_count()
    it = prefetch(gen(), depth=2)
    del it  # abandoned: no next() ever happens
    gc.collect()
    assert threading.active_count() <= before, "producer started eagerly"
    assert produced == []


# ---------------- parallel ingest (r9, data/ingest_pool.py) ----------------


def test_plan_chunks_alignment_and_cover():
    from elasticdl_tpu.data.ingest_pool import plan_chunks

    # Interior boundaries minibatch-aligned, range covered exactly, tail on
    # the last chunk, chunk count bounded by threads.
    for start, end, mb, threads in (
        (0, 100, 16, 4), (32, 131, 16, 4), (0, 5, 16, 4), (0, 64, 16, 3),
        (7, 7, 16, 4), (0, 1000, 1, 8), (0, 33, 16, 2),
    ):
        chunks = plan_chunks(start, end, mb, threads)
        assert chunks[0][0] == start and chunks[-1][1] == max(start, end)
        for (a, b), (c, d) in zip(chunks, chunks[1:]):
            assert b == c, chunks
            assert (b - start) % mb == 0, chunks  # interior cut is aligned
        assert len(chunks) <= max(1, threads)
        # only the last chunk may hold a non-multiple of mb
        for a, b in chunks[:-1]:
            assert (b - a) % mb == 0
    # nothing to split: single chunk back
    assert plan_chunks(0, 31, 16, 4) == [(0, 31)]  # 1 full mb + tail
    assert plan_chunks(0, 100, 16, 1) == [(0, 100)]


def test_ingest_pool_map_ordered_preserves_order_and_raises():
    from elasticdl_tpu.data.ingest_pool import IngestPool

    pool = IngestPool(4)
    assert pool.parallel and pool.threads == 4
    try:
        out = pool.map_ordered(lambda x: x * x, list(range(37)))
        assert out == [x * x for x in range(37)]

        def boom(x):
            if x == 5:
                raise ValueError("chunk failure")
            return x

        with pytest.raises(ValueError, match="chunk failure"):
            pool.map_ordered(boom, list(range(8)))
    finally:
        pool.shutdown()
    # serial degradation: no pool at all, same results
    serial = IngestPool(1)
    assert not serial.parallel
    assert serial.map_ordered(lambda x: -x, [3, 1, 2]) == [-3, -1, -2]


def test_parallel_chunk_decode_bit_identical(tmp_path):
    """The r9 contract: chunked read+decode reassembled in chunk order is
    byte-for-byte the serial path's output — record order preserved across
    an mb-unaligned shard with a ragged tail."""
    from elasticdl_tpu.data.ingest_pool import IngestPool, plan_chunks

    path = str(tmp_path / "c.rio")
    n, mb = 1000, 64  # 15 full minibatches + 40-record tail
    synthetic.synthetic_criteo(path, n, seed=3, container="recordio")
    reader = create_data_reader(path)
    assert reader.thread_safe_ranges
    shard = Shard(path, 0, n)

    serial = codecs.criteo_feed_pre(reader.read_records_packed(shard), 4096)

    pool = IngestPool(4)
    try:
        chunks = plan_chunks(shard.start, shard.end, mb, pool.threads)
        assert len(chunks) == 4
        parts = pool.map_ordered(
            lambda span: codecs.criteo_feed_pre(
                reader.read_records_packed(Shard(path, span[0], span[1])),
                4096,
            ),
            chunks,
        )
    finally:
        pool.shutdown()
    merged = {
        k: np.concatenate([p[k] for p in parts], axis=0) for k in serial
    }
    assert set(merged) == set(serial)
    for k in serial:
        assert merged[k].dtype == serial[k].dtype
        np.testing.assert_array_equal(merged[k], serial[k])


def test_recordio_offsets_cache_shared_across_readers(tmp_path):
    """The process-level (path, mtime, size) offsets cache: a second reader
    instance of the same unchanged file reuses the first's index (no
    re-scan), while a rewritten file gets a fresh scan."""
    from elasticdl_tpu.data import recordio as rio

    path = str(tmp_path / "cache.rio")
    write_records(path, [b"a" * 10, b"b" * 20, b"c" * 5])
    r1 = RecordIOReader(path)
    idx1 = r1.index()
    r2 = RecordIOReader(path)
    assert r2.index() is idx1  # shared list object: served from the cache

    # Rewrite with different content: the key (mtime_ns, size) changes, so
    # the stale index must not be reused.
    import os as _os
    write_records(path, [b"x" * 7, b"y" * 300])
    _os.utime(path, ns=(1, 1))  # force a distinct mtime even on coarse fs
    r3 = RecordIOReader(path)
    idx3 = r3.index()
    assert idx3 is not idx1 and len(idx3) == 2
    assert list(r3.read_range(0, 2)) == [b"x" * 7, b"y" * 300]
    # bounded: the cache never grows past its cap
    assert len(rio._INDEX_CACHE) <= rio._INDEX_CACHE_MAX


def test_prefetch_thread_name_attributes_task():
    """The producer thread carries the caller's name (prefetch:<task_id>)
    so thread dumps attribute ingest threads."""
    import threading
    from elasticdl_tpu.data.prefetch import prefetch

    names = []

    def gen():
        names.append(threading.current_thread().name)
        yield 1

    assert list(prefetch(gen(), 2, name="prefetch:42")) == [1]
    assert names == ["prefetch:42"]


# -- the record index: one vectorised scan a file a process (PR 36) --

_LINE_FILES = {
    "trailing_newline": b"1,a\n2,b\n3,c\n",
    "no_trailing_newline": b"1,a\n2,b\n3,c",
    "crlf": b"1,a\r\n2,b\r\n3,c\r\n",
    "blank_lines_middle_and_end": b"1,a\n\n\n2,b\n\n",
    "only_newlines": b"\n\n\n",
    "empty": b"",
    "one_line": b"1,a",
    "one_line_newline": b"1,a\n",
}


def _loop_line_offsets(path):
    """The plain scan the vectorised one replaced, kept as its reference."""
    offsets = []
    with open(path, "rb") as f:
        pos = f.tell()
        for line in f:
            offsets.append(pos)
            pos += len(line)
    return offsets


# Chunks of 1 to 5 bytes put a boundary on, before and after a newline of
# every file above ("1,a\n": 4 bytes a line); None is the real chunk size.
@pytest.mark.parametrize("chunk_bytes", [1, 2, 3, 4, 5, None])
@pytest.mark.parametrize("name", sorted(_LINE_FILES))
def test_vectorised_line_scan_equals_the_line_loop(tmp_path, name, chunk_bytes):
    from elasticdl_tpu.data.reader import scan_line_offsets

    path = str(tmp_path / "lines.txt")
    open(path, "wb").write(_LINE_FILES[name])
    got = (
        scan_line_offsets(path) if chunk_bytes is None
        else scan_line_offsets(path, chunk_bytes)
    )
    assert got.dtype == np.int64 and got.ndim == 1
    assert got.tolist() == _loop_line_offsets(path)


@pytest.mark.parametrize("name", sorted(_LINE_FILES))
def test_skip_header_slices_the_shared_line_index(tmp_path, name):
    path = str(tmp_path / "lines.txt")
    open(path, "wb").write(_LINE_FILES[name])
    lines = [l.rstrip(b"\r\n") for l in _LINE_FILES[name].split(b"\n")]
    lines = lines[: len(_loop_line_offsets(path))]
    whole = Shard(path, 0, 99)
    for skip in (False, True):
        reader = CSVDataReader(path, skip_header=skip)
        assert reader._offsets(path).tolist() == _loop_line_offsets(path)[skip:]
        assert list(reader.read_records(whole)) == lines[skip:]
        assert list(reader.read_records_packed(whole)) == lines[skip:]
        assert sum(s.size for s in reader.create_shards(2)) == len(lines[skip:])


def _index_counts(container):
    """(builds, waits) of the process's registry for one container."""
    from elasticdl_tpu.common import gauge

    return tuple(
        gauge.default().counter(name, labels={"container": container}).value()
        for name in ("edl_reader_index_builds_total", "edl_reader_index_waits_total")
    )


def _slow_scan(monkeypatch, module, name, calls, seconds=0.3):
    """Make a module's scan last long enough that threads released together
    all arrive while it runs, and count its calls."""
    import time

    real = getattr(module, name)

    def scan(path):
        calls.append(path)
        time.sleep(seconds)
        return real(path)

    monkeypatch.setattr(module, name, scan)


@pytest.mark.parametrize("container", ["text", "recordio"])
def test_cold_index_is_built_once_for_ten_threads(tmp_path, monkeypatch, container):
    """Ten threads released together on a cold reader, each reading its own
    range of ONE file (a worker's first task): the scan runs once, the
    others wait for it, every thread gets its own range's records."""
    import sys
    import threading
    from elasticdl_tpu.data import reader as reader_mod
    from elasticdl_tpu.data import recordio as rio

    records = [b"%d,r" % i for i in range(200)]
    calls = []
    if container == "text":
        path = str(tmp_path / "d.csv")
        open(path, "wb").write(b"\n".join(records) + b"\n")
        _slow_scan(monkeypatch, reader_mod, "scan_line_offsets", calls)
        reader = CSVDataReader(path)
    else:
        path = str(tmp_path / "d.rio")
        write_records(path, records)
        _slow_scan(monkeypatch, rio, "_scan_record_offsets", calls)
        reader = RecordIODataReader(path)
    builds0, waits0 = _index_counts(container)
    n = 10
    barrier = threading.Barrier(n)
    got, errors = [None] * n, []

    def read(i):
        try:
            barrier.wait(timeout=10)
            got[i] = list(reader.read_records_packed(Shard(path, 20 * i, 20 * i + 20)))
        except BaseException as e:  # surfaced below: a thread must not die silently
            errors.append(e)

    threads = [threading.Thread(target=read, args=(i,), daemon=True) for i in range(n)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    assert calls == [path]  # ONE scan
    builds, waits = _index_counts(container)
    assert builds - builds0 == 1
    assert 1 <= waits - waits0 <= n - 1
    for i in range(n):
        assert got[i] == records[20 * i : 20 * i + 20]


def test_two_files_index_concurrently(tmp_path, monkeypatch):
    """The cache-wide lock is not held during a scan: while one file's scan
    is in flight another file is indexed to the end, and neither waits on
    the other's key."""
    import threading
    from elasticdl_tpu.data import reader as reader_mod

    slow, fast = str(tmp_path / "slow.csv"), str(tmp_path / "fast.csv")
    open(slow, "wb").write(b"1\n2\n3\n")
    open(fast, "wb").write(b"4\n5\n")
    real = reader_mod.scan_line_offsets
    started, fast_done = threading.Event(), threading.Event()
    overlapped = []

    def scan(path):
        if path == slow:
            started.set()
            overlapped.append(fast_done.wait(timeout=10))
        return real(path)

    monkeypatch.setattr(reader_mod, "scan_line_offsets", scan)
    builds0, waits0 = _index_counts("text")
    out = {}
    t = threading.Thread(
        target=lambda: out.update(slow=list(CSVDataReader(slow).read_records(Shard(slow, 0, 9)))),
        daemon=True,
    )
    t.start()
    assert started.wait(timeout=10)
    assert list(CSVDataReader(fast).read_records(Shard(fast, 0, 9))) == [b"4", b"5"]
    fast_done.set()
    t.join(timeout=10)
    assert not t.is_alive() and out["slow"] == [b"1", b"2", b"3"]
    assert overlapped == [True]  # the other file was indexed inside this scan
    builds, waits = _index_counts("text")
    assert (builds - builds0, waits - waits0) == (2, 0)


@pytest.mark.parametrize("change", ["size", "mtime"])
def test_rewritten_text_file_is_indexed_again(tmp_path, change):
    """A second reader instance of an UNCHANGED file shares the first's
    scan; one of a file rewritten since (new size, or the same size at a new
    mtime) scans again and serves the new lines."""
    import os

    path = str(tmp_path / "d.csv")
    open(path, "wb").write(b"1,a\n2,b\n3,c\n")
    builds0, _ = _index_counts("text")
    first = CSVDataReader(path)
    assert list(first.read_records(Shard(path, 0, 9))) == [b"1,a", b"2,b", b"3,c"]
    assert np.shares_memory(CSVDataReader(path)._offsets(path), first._offsets(path))
    assert _index_counts("text")[0] - builds0 == 1
    before = os.stat(path)
    new = b"1,a\n2,b\n3,c\n4,d\n" if change == "size" else b"1\n2\n3\n4\n5\n6\n"
    open(path, "wb").write(new)
    if change == "mtime":
        assert os.stat(path).st_size == before.st_size
        os.utime(path, ns=(before.st_atime_ns, before.st_mtime_ns + 1_000_000_000))
    again = CSVDataReader(path)
    assert list(again.read_records(Shard(path, 0, 9))) == new.split(b"\n")[:-1]
    assert _index_counts("text")[0] - builds0 == 2
