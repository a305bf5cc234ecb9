"""Record-shard format + feed tests: the pre-decoded shard format's
write/read round trip and typed corruption, the converter, streaming
ingestion through a VerifyingStore, the tiered ShardCache (RAM + disk
spill), records_feed bit-parity against the serial LMDB decode path
(clean AND under corrupt_record faults), its in-place assembly (the
stream against the shards themselves and against ``workers=0``, a held
batch never rewritten, the copying default counted, the next batch's
reads in flight at a yield), thread-safe LocalStore ranged reads under a
concurrent pool, and device-vs-host augmentation bit-identity at a
shared RNG seed."""

import itertools
import os
import threading
import time

import numpy as np
import pytest

from sparknet_tpu.data import PartitionedDataset
from sparknet_tpu.data.db import array_to_datum, db_feed
from sparknet_tpu.data.integrity import (
    DataCorruptionError, Quarantine, QuarantinePolicy,
)
from sparknet_tpu.data.lmdb_io import write_lmdb
from sparknet_tpu.data.objectstore import LocalStore, VerifyingStore
from sparknet_tpu.data.pipeline import FeedStats, ShardCache
from sparknet_tpu.data.records import (
    RecordShard, ShardSet, ShardWriter, convert_to_shards,
    is_records_source, records_feed, write_shard,
)
from sparknet_tpu.models.dsl import layer
from sparknet_tpu.proto.caffe_pb import Phase
from sparknet_tpu.utils import faults


def _records(n, c=3, h=8, w=8, seed=0):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 256, size=(c, h, w)).astype(np.uint8),
             int(rng.integers(0, 10))) for i in range(n)]


def _write_lmdb_of(path, recs):
    write_lmdb(path, [(b"%08d" % i, array_to_datum(img, label))
                      for i, (img, label) in enumerate(recs)])


def _data_layer(source, batch, backend):
    return layer("d", "Data", [], ["data", "label"],
                 data_param={"source": source, "batch_size": batch,
                             "backend": backend},
                 transform_param={"scale": 0.5, "mean_value": [16.0]})


# ---------------------------------------------------------------------------
# Shard format round trip + typed corruption
# ---------------------------------------------------------------------------

def test_shard_roundtrip_bit_exact(tmp_path):
    recs = _records(7)
    path = str(tmp_path / "a.rec")
    assert write_shard(path, recs) == 7
    shard = RecordShard.open(path)
    assert shard.count == 7 and len(shard) == 7
    assert (shard.c, shard.h, shard.w) == (3, 8, 8)
    for i, (img, label) in enumerate(recs):
        got, glabel = shard.read(i)
        assert got.dtype == np.uint8
        assert np.array_equal(img, got)
        assert label == glabel
    # the lazy-partition surface: slicing and iteration
    assert len(shard[2:5]) == 3
    assert np.array_equal(shard[3][0], recs[3][0])
    assert sum(1 for _ in shard) == 7


def test_shard_flipped_byte_is_typed_corruption_with_attribution(tmp_path):
    recs = _records(5)
    path = str(tmp_path / "a.rec")
    write_shard(path, recs)
    shard = RecordShard.open(path)
    pos = shard.offset(3) + 5
    with open(path, "r+b") as f:
        f.seek(pos)
        orig = f.read(1)[0]
        f.seek(pos)
        f.write(bytes([orig ^ 0xFF]))
    shard = RecordShard.open(path)
    with pytest.raises(DataCorruptionError) as ei:
        shard.read(3)
    assert ei.value.key == 3
    assert ei.value.offset == shard.offset(3)
    # neighbours still read clean — corruption is per-record, not per-shard
    assert np.array_equal(shard.read(2)[0], recs[2][0])


def test_shard_writer_rejects_non_uint8(tmp_path):
    w = ShardWriter(str(tmp_path / "a.rec"), 1, 2, 2)
    with pytest.raises(DataCorruptionError):
        w.add(np.full((1, 2, 2), 0.5, np.float32), 0)
    w.add(np.zeros((1, 2, 2), np.uint8), 1)
    assert w.close() == 1


def test_garbage_file_is_typed_corruption(tmp_path):
    path = str(tmp_path / "junk.rec")
    with open(path, "wb") as f:
        f.write(b"not a shard at all, far too short?" * 3)
    with pytest.raises(DataCorruptionError):
        RecordShard.open(path)


# ---------------------------------------------------------------------------
# Converter + ShardSet
# ---------------------------------------------------------------------------

def test_convert_rolls_shards_and_shardset_replays_in_order(tmp_path):
    recs = _records(10, c=2, h=4, w=4)
    stride = 2 * 4 * 4 + 8
    out = convert_to_shards(iter(recs), str(tmp_path / "s"),
                            shard_bytes=3 * stride)
    assert out["records"] == 10 and len(out["shards"]) > 1
    assert out["geometry"] == (2, 4, 4)
    ss = ShardSet.open(str(tmp_path / "s"))
    assert ss.count == 10
    for i, (img, label) in enumerate(recs):
        shard, j = ss.locate(i)
        got, glabel = shard.read(j)
        assert np.array_equal(img, got) and label == glabel
    assert is_records_source(str(tmp_path / "s"))
    assert not is_records_source(str(tmp_path))
    ss.close()


def test_convert_quarantines_bad_records(tmp_path):
    def stream():
        yield np.zeros((1, 2, 2), np.uint8), 0
        yield np.full((1, 2, 2), 0.5, np.float32), 1   # not representable
        yield np.ones((1, 2, 2), np.uint8), 2

    q = Quarantine(QuarantinePolicy(max_fraction=0.5), epoch_size=3)
    out = convert_to_shards(stream(), str(tmp_path / "s"), quarantine=q)
    assert out["records"] == 2
    assert q.report()["total_bad"] == 1


def test_shardset_verifying_store_reads_bit_exact(tmp_path):
    recs = _records(9)
    convert_to_shards(iter(recs), str(tmp_path / "s"),
                      shard_bytes=4 * (3 * 8 * 8 + 8))
    ss = ShardSet.open(str(tmp_path / "s"), verify=True)
    assert all(isinstance(s.store, VerifyingStore) for s in ss.shards)
    for i, (img, label) in enumerate(recs):
        shard, j = ss.locate(i)
        got, glabel = shard.read(j)
        assert np.array_equal(img, got) and label == glabel
    ss.close()


def test_partitioned_dataset_from_records(tmp_path):
    recs = _records(8)
    convert_to_shards(iter(recs), str(tmp_path / "s"),
                      shard_bytes=3 * (3 * 8 * 8 + 8))
    ds = PartitionedDataset.from_records(str(tmp_path / "s"))
    assert sum(len(p) for p in ds.partitions) == 8
    flat = [r for part in ds.partitions for r in part]
    for (img, label), (gimg, glabel) in zip(recs, flat):
        assert np.array_equal(img, gimg) and label == glabel


# ---------------------------------------------------------------------------
# LocalStore under a concurrent ranged-read pool
# ---------------------------------------------------------------------------

def test_local_store_concurrent_ranged_reads():
    import tempfile
    with tempfile.TemporaryDirectory() as root:
        blobs = {}
        for k in range(3):
            payload = bytes((k * 17 + i) % 256 for i in range(4096))
            with open(os.path.join(root, f"b{k}"), "wb") as f:
                f.write(payload)
            blobs[f"b{k}"] = payload
        store = LocalStore(root)
        errs = []

        def reader(tid):
            rng = np.random.default_rng(tid)
            try:
                for _ in range(300):
                    key = f"b{int(rng.integers(3))}"
                    off = int(rng.integers(0, 4000))
                    ln = int(rng.integers(1, 96))
                    got = store.open_range(key, off, ln)
                    if got != blobs[key][off:off + ln]:
                        errs.append((tid, key, off, ln))
            except Exception as e:  # noqa: BLE001 — collected for assert
                errs.append((tid, repr(e)))

        threads = [threading.Thread(target=reader, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        store.close()


# ---------------------------------------------------------------------------
# Tiered ShardCache
# ---------------------------------------------------------------------------

def test_shard_cache_tiers_spill_and_promote(tmp_path):
    stats = FeedStats()
    cache = ShardCache(max_shards=2, stats=stats,
                       spill_dir=str(tmp_path / "spill"), max_spill=8)
    payloads = {k: bytes([k]) * 64 for k in range(4)}
    for k in range(4):   # k=2,3 evict k=0,1 to disk
        assert cache.get(k, lambda k=k: payloads[k]) == payloads[k]
    tiers = cache.tier_counts()
    assert tiers["ram_shards"] == 2 and tiers["disk_shards"] == 2
    # RAM hit
    assert cache.get(3, lambda: b"wrong") == payloads[3]
    # disk hit promotes back to RAM (and evicts another to disk)
    assert cache.get(0, lambda: b"wrong") == payloads[0]
    snap = stats.snapshot()
    assert snap["cache_hits"] == 1
    assert snap["cache_disk_hits"] == 1
    assert snap["cache_misses"] == 4
    assert cache.tier_counts()["spills"] >= 3


def test_shard_cache_spill_bound_deletes_oldest(tmp_path):
    cache = ShardCache(max_shards=1, spill_dir=str(tmp_path / "spill"),
                       max_spill=2)
    for k in range(5):
        cache.get(k, lambda k=k: bytes([k]))
    assert cache.tier_counts()["disk_shards"] <= 2
    spilled = os.listdir(str(tmp_path / "spill"))
    assert len(spilled) <= 2


def test_shard_cache_without_spill_dir_just_evicts():
    cache = ShardCache(max_shards=1, spill_dir="")
    cache.get("a", lambda: b"a")
    cache.get("b", lambda: b"b")
    assert cache.tier_counts()["disk_shards"] == 0
    # "a" was dropped, not spilled: re-materializes
    assert cache.get("a", lambda: b"a2") == b"a2"


# ---------------------------------------------------------------------------
# records_feed bit-parity vs the serial LMDB decode reference
# ---------------------------------------------------------------------------

def _pull_batches(feed, n):
    out = []
    for _ in range(n):
        b = next(feed)
        out.append({k: np.array(v) for k, v in b.items()})
    feed.close()
    return out


def _norm_quarantine(rep):
    rep = dict(rep)
    rep.pop("examples", None)
    rep.pop("by_source", None)   # source names differ across backends
    return rep


@pytest.mark.parametrize("corrupt", [False, True])
def test_records_feed_bit_identical_to_serial_lmdb(tmp_path, monkeypatch,
                                                   corrupt):
    if corrupt:
        monkeypatch.setenv("SPARKNET_FAULT", "corrupt_record:0.1")
        monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    n, batch, batches = 48, 8, 13   # 13*8 > 2 epochs: epoch rolls covered
    recs = _records(n, seed=7)
    db = str(tmp_path / "lmdb")
    _write_lmdb_of(db, recs)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards,
                      shard_bytes=20 * (3 * 8 * 8 + 8))

    faults.reset_injector()
    qa = Quarantine(QuarantinePolicy(max_fraction=0.5), epoch_size=n)
    ref = _pull_batches(db_feed(_data_layer(db, batch, "LMDB"),
                                Phase.TRAIN, seed=0, quarantine=qa,
                                workers=0), batches)

    faults.reset_injector()
    qb = Quarantine(QuarantinePolicy(max_fraction=0.5), epoch_size=n)
    stats = FeedStats()
    got = _pull_batches(records_feed(_data_layer(shards, batch, "RECORDS"),
                                     Phase.TRAIN, seed=0, quarantine=qb,
                                     workers=4, stats=stats), batches)

    for a, b in zip(ref, got):
        assert np.array_equal(a["data"], b["data"])
        assert np.array_equal(a["label"], b["label"])
    assert _norm_quarantine(qa.report()) == _norm_quarantine(qb.report())
    if corrupt:
        assert qb.report()["total_bad"] > 0
        assert any(shards in s for s in qb.report()["by_source"])
    snap = stats.snapshot()
    assert snap["read_s"] > 0     # the IO stage books under "read"
    assert snap["decode_s"] >= 0 and snap["batches"] == batches


def test_db_feed_dispatches_records_backend(tmp_path):
    """A Data layer whose source holds ``*.rec`` flows through db_feed
    unchanged — the dispatch point every prototxt already uses."""
    recs = _records(16, seed=2)
    shards = str(tmp_path / "s")
    convert_to_shards(iter(recs), shards)
    faults.reset_injector()
    feed = db_feed(_data_layer(shards, 4, "RECORDS"), Phase.TRAIN, seed=0)
    a = _pull_batches(feed, 2)
    faults.reset_injector()
    b = _pull_batches(records_feed(_data_layer(shards, 4, "RECORDS"),
                                   Phase.TRAIN, seed=0), 2)
    for x, y in zip(a, b):
        assert np.array_equal(x["data"], y["data"])


@pytest.mark.parametrize("path", ["verify", "cache", "verify+cache"])
def test_records_feed_from_verifying_store_with_tiered_cache(tmp_path, path):
    """No ``read_into`` of the store's own on these paths (a
    ``VerifyingStore`` reads a record at a time through its checksum and
    retry; a ``ShardCache`` serves slices of a blob): the same bytes
    arrive, and the feed books every record as copied."""
    recs = _records(24, seed=5)
    shards = str(tmp_path / "s")
    convert_to_shards(iter(recs), shards, shard_bytes=8 * (3 * 8 * 8 + 8))
    faults.reset_injector()
    ref_stats = FeedStats()
    ref = _pull_batches(records_feed(_data_layer(shards, 8, "RECORDS"),
                                     Phase.TRAIN, seed=0, workers=0,
                                     stats=ref_stats), 6)
    assert ref_stats.snapshot()["read_in_place"] == 6 * 8
    assert ref_stats.snapshot()["read_copied"] == 0
    stats = FeedStats()
    cache = ShardCache(max_shards=1, stats=stats,
                       spill_dir=str(tmp_path / "spill"),
                       max_spill=8) if "cache" in path else None
    faults.reset_injector()
    got = _pull_batches(records_feed(_data_layer(shards, 8, "RECORDS"),
                                     Phase.TRAIN, seed=0, workers=2,
                                     verify="verify" in path, cache=cache,
                                     stats=stats), 6)
    for a, b in zip(ref, got):
        assert np.array_equal(a["data"], b["data"])
        assert np.array_equal(a["label"], b["label"])
    snap = stats.snapshot()
    assert snap["read_in_place"] == 0 and snap["read_copied"] == 6 * 8
    if cache is not None:
        assert snap["cache_misses"] >= 3          # one cold miss per shard
        assert snap["cache_hits"] > 0             # within-shard locality
        assert snap["cache_disk_hits"] > 0        # epoch 2 rereads spilled


# ---------------------------------------------------------------------------
# In-place assembly
# ---------------------------------------------------------------------------

def _raw_layer(source, batch):
    return layer("d", "Data", [], ["data", "label"],
                 data_param={"source": source, "batch_size": batch,
                             "backend": "RECORDS"})


def _expected_stream(recs, batch, batches):
    """The stream from the records themselves: a batch is the next
    ``batch`` records of the cyclic order whose fault coin stays down."""
    injector = faults.get_injector()
    good = (seq for seq in itertools.count()
            if not injector.corrupt_record(seq))
    out = []
    for _ in range(batches):
        picked = [recs[seq % len(recs)]
                  for seq in itertools.islice(good, batch)]
        out.append({"data": np.stack([img for img, _ in picked]),
                    "label": np.asarray([lab for _, lab in picked],
                                        np.float32)})
    return out


@pytest.mark.parametrize("workers", [1, 3, 16])
@pytest.mark.parametrize("corrupt", [False, True])
def test_in_place_stream_is_the_serial_one(tmp_path, monkeypatch, corrupt,
                                           workers):
    """Batch edges inside a shard (20 records a shard, 16 a batch) and
    across an epoch's end (50 records): the raw in-place stream equals
    the shards' own records in pull order and the ``workers=0`` stream
    bit for bit, with equal quarantine reports, and the counters say how
    each record's bytes arrived."""
    if corrupt:
        monkeypatch.setenv("SPARKNET_FAULT", "corrupt_record:0.1")
        monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    n, batch, batches = 50, 16, 8
    recs = _records(n, seed=3)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards,
                      shard_bytes=20 * (3 * 8 * 8 + 8))
    assert len(os.listdir(shards)) == 3

    def stream(w):
        faults.reset_injector()
        q = Quarantine(QuarantinePolicy(max_fraction=0.5), epoch_size=n)
        stats = FeedStats()
        got = _pull_batches(records_feed(
            _raw_layer(shards, batch), Phase.TRAIN, raw=True, quarantine=q,
            workers=w, stats=stats), batches)
        return got, q.report(), stats.snapshot()

    ref, ref_report, _ = stream(0)
    got, report, snap = stream(workers)
    want = _expected_stream(recs, batch, batches)
    for a, b, c in zip(want, ref, got):
        assert c["data"].dtype == np.uint8 and c["label"].dtype == np.float32
        for key in ("data", "label"):
            assert np.array_equal(a[key], b[key])
            assert np.array_equal(a[key], c[key])
    assert report == ref_report
    assert snap["batches"] == batches and snap["read_s"] > 0
    consumed = batches * batch + report["total_bad"]
    if corrupt:
        assert report["total_bad"] > 0 and report["epochs_completed"] >= 2
        # an injected fault rots a copy of the record's bytes
        assert snap["read_copied"] == report["total_bad"]
    assert snap["read_in_place"] + snap["read_copied"] == consumed
    assert snap["read_in_place"] == consumed - report["total_bad"]


@pytest.mark.parametrize("corrupt", [False, True])
def test_a_held_batch_is_never_written_again(tmp_path, monkeypatch, corrupt):
    """``buffers=0``: a consumer that keeps every batch it was handed
    finds each as it was yielded, whatever the feed read since, and no
    two share memory."""
    if corrupt:
        monkeypatch.setenv("SPARKNET_FAULT", "corrupt_record:0.1")
        monkeypatch.setenv("SPARKNET_FAULT_ATTEMPT", "0")
    recs = _records(40, seed=9)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards, shard_bytes=16 * (3 * 8 * 8 + 8))
    faults.reset_injector()
    feed = records_feed(_raw_layer(shards, 8), Phase.TRAIN, raw=True,
                        workers=4,
                        quarantine=Quarantine(
                            QuarantinePolicy(max_fraction=0.5),
                            epoch_size=40))
    held = list(itertools.islice(feed, 4))
    for _ in range(3):          # the feed reads on, two batches ahead
        next(feed)
    feed.close()
    for want, got in zip(_expected_stream(recs, 8, 4), held):
        assert np.array_equal(want["data"], got["data"])
        assert np.array_equal(want["label"], got["label"])
    for i, a in enumerate(held):
        for b in held[i + 1:]:
            assert not np.shares_memory(a["data"], b["data"])


def test_only_arrays_nobody_holds_are_read_into_again(tmp_path):
    """``buffers=0``: the feed reads into one of its earlier arrays once
    every reference to it is gone (its pages are mapped; a fresh array's
    are not), and into no array that anything still holds: a name, a
    view, a dict."""
    from sparknet_tpu.data.records import _unheld
    a = np.zeros(4, np.uint8)
    spare = [a]
    assert _unheld(spare) is None           # ``a`` holds it
    view = a[1:]
    del a
    assert _unheld(spare) is None           # a view holds it
    del view
    assert _unheld(spare) is not None and spare == []

    recs = _records(40, seed=10)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards, shard_bytes=16 * (3 * 8 * 8 + 8))
    faults.reset_injector()
    feed = records_feed(_raw_layer(shards, 8), Phase.TRAIN, raw=True,
                        workers=2)
    want = _expected_stream(recs, 8, 14)

    def address(arr):
        return arr.__array_interface__["data"][0]

    kept = next(feed)                       # held to the end
    row = next(feed)["data"][3]             # a view of the second, held
    seen = []
    for k in range(2, 14):
        b = next(feed)
        assert np.array_equal(want[k]["data"], b["data"])
        assert np.array_equal(want[k]["label"], b["label"])
        seen.append(address(b["data"]))
        del b
    feed.close()
    assert len(set(seen)) < len(seen)       # memory came round again
    assert address(kept["data"]) not in seen
    assert address(row.base) not in seen
    assert np.array_equal(kept["data"], want[0]["data"])
    assert np.array_equal(row, want[1]["data"][3])


def test_device_batches_held_stay_as_delivered(tmp_path):
    """On the CPU backend ``device_put`` may alias the host array: the
    alias is a holder like any other, so device batches a consumer
    keeps stay as delivered while the feed reads on behind them."""
    from sparknet_tpu.data import device_feed
    recs = _records(40, seed=14)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards, shard_bytes=16 * (3 * 8 * 8 + 8))
    faults.reset_injector()
    want = _expected_stream(recs, 8, 6)
    with device_feed(records_feed(_raw_layer(shards, 8), Phase.TRAIN,
                                  raw=True, workers=2), depth=2) as feed:
        held = [next(feed) for _ in range(6)]
        for _ in range(10):
            next(feed)
        for a, b in zip(want, held):
            assert np.array_equal(a["data"], np.asarray(b["data"]))
            assert np.array_equal(a["label"], np.asarray(b["label"]))


def test_raw_buffers_rotate_under_the_rings_contract(tmp_path):
    """``buffers=N`` on the raw path: the yielded array is the ring's,
    right when it is yielded, and handed out again N batches later —
    the aliasing the parameter documents, no more and no less."""
    recs = _records(40, seed=8)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards, shard_bytes=16 * (3 * 8 * 8 + 8))
    faults.reset_injector()
    feed = records_feed(_raw_layer(shards, 8), Phase.TRAIN, raw=True,
                        workers=2, buffers=4)
    held = []
    for want in _expected_stream(recs, 8, 6):
        got = next(feed)
        assert np.array_equal(want["data"], got["data"])
        assert np.array_equal(want["label"], got["label"])
        held.append(got["data"])
    feed.close()
    assert np.shares_memory(held[0], held[4])
    assert np.shares_memory(held[1], held[5])
    assert not any(np.shares_memory(held[0], h) for h in held[1:4])


@pytest.mark.parametrize("workers,toolchain", [(0, True), (2, True),
                                               (2, False)])
def test_rot_on_the_medium_is_caught_in_the_row(tmp_path, monkeypatch,
                                                workers, toolchain):
    """A flipped byte and a shard cut short inside its last record: the
    in-place crc check files both with the record's key and offset, the
    stream goes on with the records that are whole, and nothing of the
    bad ones is delivered — by the native check of a run, and by the
    ``zlib.crc32`` loop that stands in where nothing compiles."""
    from sparknet_tpu import native
    if toolchain:
        assert native.available()
    else:
        monkeypatch.setattr(native, "get_lib", lambda: None)
    recs = _records(12, seed=6)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards)
    (name,) = os.listdir(shards)
    path = os.path.join(shards, name)
    shard = RecordShard.open(path)
    with open(path, "r+b") as f:
        f.seek(shard.offset(3) + 5)
        orig = f.read(1)[0]
        f.seek(shard.offset(3) + 5)
        f.write(bytes([orig ^ 0xFF]))
        f.truncate(shard.offset(12) - 10)
    faults.reset_injector()
    q = Quarantine(QuarantinePolicy(max_fraction=0.5), epoch_size=12)
    got = _pull_batches(records_feed(_raw_layer(shards, 4), Phase.TRAIN,
                                     raw=True, quarantine=q,
                                     workers=workers), 5)
    whole = [r for i, r in enumerate(recs) if i not in (3, 11)]
    for k, b in enumerate(got):
        want = [whole[(4 * k + j) % 10] for j in range(4)]
        assert np.array_equal(b["data"], np.stack([i for i, _ in want]))
        assert np.array_equal(b["label"],
                              np.asarray([l for _, l in want], np.float32))
    report = q.report()
    assert report["total_bad"] == 3     # 3, 11, and 3 again an epoch on
    assert [(e["key"], e["offset"]) for e in report["examples"][:2]] == [
        ("3", shard.offset(3)), ("11", shard.offset(11))]
    assert all(path in e["source"] or shards in e["source"]
               for e in report["examples"])


def test_closing_the_device_feed_stops_the_readers(tmp_path):
    """The feed reads a batch ahead of the one it yielded, so its readers
    are busy whenever a consumer stops: ``DeviceFeed.close`` closes the
    generator too, and no reader thread is left for the interpreter's
    shutdown to find."""
    from sparknet_tpu.data import device_feed
    recs = _records(64, seed=12)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards)
    faults.reset_injector()

    def readers():
        return [t for t in threading.enumerate()
                if t.name.startswith(f"records:{shards}")]

    feed = device_feed(records_feed(_raw_layer(shards, 8), Phase.TRAIN,
                                    raw=True, workers=3), depth=2)
    first = next(feed)
    assert np.array_equal(np.asarray(first["data"]),
                          np.stack([img for img, _ in recs[:8]]))
    assert len(readers()) == 3
    feed.close()
    assert readers() == []


def test_a_feed_collected_at_shutdown_waits_for_no_lock(tmp_path,
                                                        monkeypatch):
    """A consumer that never closed its feed leaves the generator to a
    finalizing interpreter, whose daemon readers stopped wherever they
    stood, reading ahead: with the store's lock held as such a reader
    would hold it, the generator's ``finally`` still returns."""
    import sys
    recs = _records(32, seed=13)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards)
    faults.reset_injector()
    feed = records_feed(_raw_layer(shards, 8), Phase.TRAIN, raw=True,
                        workers=2)
    next(feed)
    frame = feed.gi_frame.f_locals
    pool, store = frame["pool"], frame["shards"].shards[0].store
    monkeypatch.setattr(sys, "is_finalizing", lambda: True)
    assert store._lock.acquire(timeout=5)
    try:
        closer = threading.Thread(target=feed.close)
        closer.start()
        closer.join(timeout=10)
        assert not closer.is_alive()
    finally:
        store._lock.release()
        monkeypatch.undo()
        pool.close()
        store.close()


def test_next_batch_reads_are_in_flight_at_a_yield(tmp_path, monkeypatch):
    """Through a store whose reads of the second batch block on an
    event: the first batch is yielded all the same, and while the
    generator is suspended there (so nothing can be submitted any more)
    the readers are found inside reads of the second batch."""
    from sparknet_tpu.data import records as records_mod

    recs = _records(32, seed=4)
    shards = str(tmp_path / "shards")
    convert_to_shards(iter(recs), shards)
    (name,) = os.listdir(shards)
    batch, stride = 8, 3 * 8 * 8 + 8
    second = RecordShard.open(os.path.join(shards, name)).offset(batch)
    release, started = threading.Event(), []

    class GatedStore(LocalStore):
        def read_into(self, key, offset, buffers):
            if offset >= second:
                started.append(offset)
                assert release.wait(timeout=30)
            return super().read_into(key, offset, buffers)

    monkeypatch.setattr(records_mod, "get_store",
                        lambda url: (GatedStore(url), ""))
    faults.reset_injector()
    feed = records_feed(_raw_layer(shards, batch), Phase.TRAIN, raw=True,
                        workers=2)
    try:
        first = next(feed)
        assert np.array_equal(first["data"],
                              np.stack([img for img, _ in recs[:batch]]))
        for _ in range(100):            # the readers pick their runs up
            if len(started) == 2:
                break
            time.sleep(0.05)
        # both readers sit in runs of the second batch, and nothing of
        # the third has been read into: its pulls come at the next turn
        assert sorted(started) == [second, second + 4 * stride]
        release.set()
        nxt = next(feed)
        assert np.array_equal(
            nxt["data"], np.stack([img for img, _ in recs[batch:2 * batch]]))
    finally:
        release.set()
        feed.close()


# ---------------------------------------------------------------------------
# Device-side augmentation bit-parity
# ---------------------------------------------------------------------------

def test_device_and_host_augment_arrays_bit_identical():
    import jax

    from sparknet_tpu.ops.augment import AugmentSpec, augment_batch
    from sparknet_tpu.data.transforms import augment_batch_host
    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, size=(6, 3, 12, 12)).astype(np.uint8)
    spec = AugmentSpec(crop=8, mirror=True, mean=16.0, scale=0.25,
                       train=True)
    key = jax.random.PRNGKey(123)
    dev = np.asarray(augment_batch(imgs, key, spec))
    host = augment_batch_host(imgs, key, spec)
    assert dev.shape == (6, 3, 8, 8)
    assert np.array_equal(dev, host)          # bit-identical, not close
    # test phase: deterministic center crop, no mirror
    tspec = spec._replace(train=False)
    dev_t = np.asarray(augment_batch(imgs, key, tspec))
    host_t = augment_batch_host(imgs, key, tspec)
    assert np.array_equal(dev_t, host_t)


_MEANS = {
    "none": lambda c, h, w, rng: None,
    "channel": lambda c, h, w, rng: np.asarray(
        [104.0, 117.0, 123.0][:c], np.float32).reshape(c, 1, 1),
    "planes": lambda c, h, w, rng: np.ascontiguousarray(np.broadcast_to(
        np.asarray([104.0, 117.0, 123.0][:c], np.float32).reshape(c, 1, 1),
        (c, h, w))),
    "image": lambda c, h, w, rng: rng.normal(
        110.0, 30.0, size=(c, h, w)).astype(np.float32),
}


@pytest.mark.parametrize("mean", sorted(_MEANS))
@pytest.mark.parametrize("offsets,flips", [
    ("drawn", "drawn"), ("zero", "all"), ("far", "none"), ("far", "all")])
@pytest.mark.parametrize("shape,crop", [
    ((2, 3, 256, 256), 227), ((2, 3, 256, 256), 224), ((5, 1, 32, 32), 28),
    ((3, 3, 12, 12), 0)])
@pytest.mark.parametrize("dtype", ["uint8", "float32"])
def test_crop_and_mirror_are_the_host_oracle_bit_for_bit(
        monkeypatch, dtype, shape, crop, offsets, flips, mean):
    """The selection (a uint8 batch: two one-hot products in bfloat16)
    and the gather (a float32 batch) against the numpy oracle,
    ``array_equal``: ImageNet's shapes and a one-channel 32 -> 28, the
    offsets forced to 0 and to ``h - crop``, every sample mirrored and
    none, no mean / per-channel values / the same broadcast to an image /
    a mean image, at scale 1 and 1/255."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.data.transforms import augment_batch_host
    from sparknet_tpu.ops import augment
    n, c, h, w = shape
    rng = np.random.default_rng(3)
    imgs = rng.integers(0, 256, size=shape).astype(np.uint8).astype(dtype)
    drawn = augment.draw_offsets

    def forced(key, n, h, w, spec):
        ys, xs, fl = drawn(key, n, h, w, spec)
        if offsets != "drawn":
            at = 0 if offsets == "zero" or not spec.crop else h - spec.crop
            ys = xs = jnp.full((n,), at, jnp.int32)
        if flips != "drawn":
            fl = jnp.full((n,), int(flips == "all"), jnp.int32)
        return ys, xs, fl

    monkeypatch.setattr(augment, "draw_offsets", forced)
    key = jax.random.PRNGKey(11)
    for scale in (1.0, 1.0 / 255):
        spec = augment.AugmentSpec(crop=crop, mirror=True,
                                   mean=_MEANS[mean](c, h, w, rng),
                                   scale=scale, train=True)
        dev = np.asarray(augment.augment_batch(imgs, key, spec))
        host = augment_batch_host(imgs, key, spec)
        assert dev.dtype == np.float32
        assert dev.shape == augment.out_shape(shape, spec)
        assert np.array_equal(dev, host)


@pytest.mark.parametrize("dtype,path", [("uint8", "select"),
                                        ("int8", "select"),
                                        ("float32", "gather"),
                                        ("int32", "gather")])
def test_the_lowering_follows_the_batch_and_is_counted(monkeypatch, dtype,
                                                       path):
    """Integers of at most 8 bits take the selection, anything else the
    gather; ``augment_lowering_total`` counts the choice once a trace."""
    import jax

    from sparknet_tpu.ops import augment
    from sparknet_tpu.utils import telemetry
    for k in ("SPARKNET_TELEMETRY", "SPARKNET_TRACE_DIR",
              "SPARKNET_METRICS_SNAP"):
        monkeypatch.delenv(k, raising=False)
    telemetry.reset()
    try:
        spec = augment.AugmentSpec(crop=8, mirror=True, mean=[16.0])
        imgs = np.random.default_rng(0).integers(
            0, 100, size=(4, 1, 12, 12)).astype(dtype)
        step = jax.jit(lambda i, k: augment.augment_batch(i, k, spec))
        text = step.lower(imgs, jax.random.PRNGKey(0)).as_text()
        assert ("dot_general" in text) == (path == "select")
        for seed in range(3):               # three calls of that trace
            step(imgs, jax.random.PRNGKey(seed))
        fam = telemetry.get_registry().snapshot()["augment_lowering_total"]
        assert fam["kind"] == "counter"
        assert {s["labels"]["path"]: s["value"]
                for s in fam["samples"]} == {path: 1.0}
    finally:
        telemetry.reset()


def test_solver_device_augment_losses_bit_identical():
    """set_augment(device=True) — augmentation traced into the jitted
    step — must reproduce the host-numpy path's losses bit for bit at
    the same seed (tame LR so losses stay finite and comparable)."""
    import itertools as it

    from sparknet_tpu.models import lenet
    from sparknet_tpu.ops.augment import AugmentSpec
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.solvers import Solver

    txt = ("base_lr: 0.0005\nmomentum: 0.9\nweight_decay: 0.004\n"
           "lr_policy: \"fixed\"\n")
    spec = AugmentSpec(crop=28, mirror=True, mean=[16.0], scale=1.0 / 255,
                       train=True)
    rng = np.random.default_rng(0)
    host = [{"data": rng.integers(0, 256, size=(8, 1, 32, 32)
                                  ).astype(np.uint8),
             "label": rng.integers(0, 10, size=8).astype(np.float32)}
            for _ in range(4)]

    def run(device):
        sp = load_solver_prototxt_with_net(txt, lenet(16, 16))
        solver = Solver(sp, seed=0)
        solver.set_augment(spec, device=device)
        solver.set_train_data(it.cycle(host))
        return [float(solver.step(1)) for _ in range(5)]

    a, b = run(True), run(False)
    assert all(np.isfinite(a)), a
    assert a == b                              # bit-identical losses


def test_augment_spec_from_transform_param():
    from sparknet_tpu.ops.augment import AugmentSpec, out_shape
    spec = AugmentSpec.from_transform_param(
        {"crop_size": 24, "mirror": True, "mean_value": [10.0, 20.0, 30.0],
         "scale": 0.5}, Phase.TRAIN)
    assert spec.crop == 24 and spec.mirror and spec.train
    assert spec.scale == 0.5
    assert np.asarray(spec.mean).shape == (3, 1, 1)
    assert out_shape((4, 3, 32, 32), spec) == (4, 3, 24, 24)


# ---------------------------------------------------------------------------
# Converter CLI
# ---------------------------------------------------------------------------

def test_convert_cli_lmdb_roundtrip(tmp_path, capsys):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import convert as convert_cli
    recs = _records(12, seed=11)
    db = str(tmp_path / "lmdb")
    _write_lmdb_of(db, recs)
    out_dir = str(tmp_path / "shards")
    assert convert_cli.main(["--source", db, "--out", out_dir]) == 0
    ss = ShardSet.open(out_dir)
    assert ss.count == 12
    for i, (img, label) in enumerate(recs):
        shard, j = ss.locate(i)
        got, glabel = shard.read(j)
        assert np.array_equal(img, got) and label == glabel
    ss.close()
