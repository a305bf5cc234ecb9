"""Pre-decoded record shards: the feed-at-device-speed storage format.

BENCH_r05 measured the input leg at 70.7 img/s against 18,149 img/s of
bf16 compute — and the remaining host cost after the PR-4 pipeline is
*re-decoding the same bytes every epoch*.  Caffe's answer was the same
(arXiv 1408.5093: convert_imageset writes decoded LMDB once), and Caffe
con Troll (arXiv 1504.04343) showed end-to-end throughput is set by the
data path's memory traffic, not kernels.  This module is the convert-
once half of that lesson:

- **Shard format v1** — a versioned container of uint8, crop-ready
  (C,H,W) pixel blocks + i64 labels at a FIXED stride, so record ``i``
  lives at a computable offset and any record is exactly ONE ranged
  read (``ObjectStore.open_range``) — no index lookup, no decode.  A
  per-record crc32 table sits between the header and the records; it is
  small enough to read whole at open time and doubles as the checksum
  registry for ``objectstore.VerifyingStore``.

  ::

      [ 64 B header | count × u32 crc table | count × stride records ]
      header: magic "SPRKREC\\x01", version, count, (c, h, w),
              label bytes, stride, crc(table), crc(header)
      record: c*h*w uint8 pixels ++ i64-LE label   (stride bytes)

- :class:`ShardWriter` / :func:`write_shard` — streaming writer
  (placeholder header + table, patched on close), used by
  ``tools/convert.py`` to convert LMDB/LevelDB/HDF5/tar sources once.
- :class:`RecordShard` — reader over any :class:`ObjectStore` (local
  disk, S3/GS, or a :class:`VerifyingStore` wrap).  Satisfies the
  ``__len__``/``__getitem__`` lazy-partition contract, so a shard IS a
  ``PartitionedDataset`` partition and composes with the tiered
  ``pipeline.ShardCache`` (RAM → local-disk spill → origin store).
- :func:`records_feed` — the ``db_feed``-shaped batch stream that skips
  decode entirely and assembles each batch IN PLACE: serial pulls keep
  the fault-injection coin flips and quarantine epoch accounting
  bit-identical to the LMDB path; the batch's uint8 array exists before
  its reads are submitted, and runs of consecutive records fan out over
  a bounded ``DecodePool`` (order-preserving, typed errors) whose
  readers land each record's pixels straight in its row
  (``ObjectStore.read_into``: one copy, page cache to batch) and check
  its crc there (``native.crc32_rows``: a run a call, off the
  interpreter lock); the next batch's reads are in flight while this
  one is finished.  ``raw=True`` ships the array untransformed for the
  device-side augmentation path (``ops.augment``).

Knobs: ``SPARKNET_RECORD_READERS`` (reader threads, each reading runs of
records into their rows; default ``SPARKNET_FEED_WORKERS``),
``SPARKNET_RECORD_SHARD_MB`` (converter shard size target).
"""

from __future__ import annotations

import collections
import itertools
import os
import struct
import sys
import time
import zlib
from typing import Any, Iterable, Iterator

import numpy as np

from .. import native
from ..utils import faults, knobs, telemetry
from .integrity import DataCorruptionError, Quarantine, QuarantinePolicy, crc32
from .objectstore import ObjectStore, VerifyingStore, get_store

MAGIC = b"SPRKREC\x01"
VERSION = 1
HEADER_SIZE = 64
LABEL_BYTES = 8
SHARD_SUFFIX = ".rec"

# magic(8s) version(u32) flags(u32) count(u64) c(u32) h(u32) w(u32)
# label_bytes(u32) stride(u64) table_crc(u32) — header_crc(u32) follows,
# covering everything before it; the tail pads to HEADER_SIZE
_HEADER = struct.Struct("<8sIIQIIIIQI")
_LABEL = struct.Struct("<q")
_LABEL_DTYPE = np.dtype("<i8")      # a batch's label column, as stored

# Records one reader takes at a time: a run of consecutive records of one
# shard, bound for consecutive rows of one batch, is one ``read_into``
# (2 buffers a record) and one hand-over through the pool.
MAX_RUN = 32


def record_readers(default: int | None = None) -> int:
    """Reader threads of ``records_feed``: ``SPARKNET_RECORD_READERS``,
    else the decode-pool default (``SPARKNET_FEED_WORKERS``).  0 = serial:
    the same reads into the same rows, on the feed's own thread."""
    raw = knobs.raw("SPARKNET_RECORD_READERS", "")
    if not raw:
        from .pipeline import feed_workers
        return feed_workers(default)
    n = int(raw)
    if n < 0:
        raise ValueError(f"SPARKNET_RECORD_READERS must be >= 0, got {n}")
    return n


def shard_bytes_target() -> int:
    """Converter shard-size target in bytes (``SPARKNET_RECORD_SHARD_MB``,
    default 64 MB) — big enough that sequential streaming amortizes the
    per-object open, small enough that one shard is a cache unit."""
    mb = knobs.get_int("SPARKNET_RECORD_SHARD_MB", 64)
    if mb < 1:
        raise ValueError(f"SPARKNET_RECORD_SHARD_MB must be >= 1, got {mb}")
    return mb * (1 << 20)


# ---------------------------------------------------------------------------
# Writer
# ---------------------------------------------------------------------------

class ShardWriter:
    """Streaming shard writer: records append sequentially, the header
    and crc table are patched on :meth:`close` (one seek-back — the file
    is invalid until closed, by construction, so a torn write can never
    parse as a short-but-valid shard)."""

    def __init__(self, path: str, c: int, h: int, w: int,
                 capacity: int | None = None):
        if min(c, h, w) <= 0:
            raise ValueError(f"impossible geometry ({c}, {h}, {w})")
        self.path = path
        self.c, self.h, self.w = int(c), int(h), int(w)
        self.stride = self.c * self.h * self.w + LABEL_BYTES
        self.capacity = capacity
        self._crcs: list[int] = []
        self._f = open(path, "wb")
        self._f.write(b"\0" * HEADER_SIZE)      # patched on close
        if capacity:                            # table placeholder
            self._f.write(b"\0" * (4 * capacity))
        self._closed = False

    @property
    def count(self) -> int:
        return len(self._crcs)

    @property
    def nbytes(self) -> int:
        """Record bytes written so far (the converter's roll trigger)."""
        return self.count * self.stride

    def add(self, img: np.ndarray, label: int) -> None:
        """Append one (C,H,W) uint8 image + label.  Float inputs that
        hold exact uint8 values (the decode path's 0–255 f32) are cast
        losslessly; anything else is a typed error — the format stores
        pre-decoded uint8 pixels, nothing lossier."""
        if self._closed:
            raise RuntimeError(f"{self.path}: writer is closed")
        if self.capacity is not None and self.count >= self.capacity:
            raise RuntimeError(
                f"{self.path}: shard capacity {self.capacity} exceeded")
        img = np.asarray(img)
        if img.shape != (self.c, self.h, self.w):
            raise DataCorruptionError(
                f"record shape {img.shape} != shard geometry "
                f"({self.c}, {self.h}, {self.w})", source=self.path)
        if img.dtype != np.uint8:
            as_u8 = img.astype(np.uint8)
            if not np.array_equal(as_u8.astype(img.dtype), img):
                raise DataCorruptionError(
                    "record is not uint8-representable (float pixels "
                    "outside exact 0..255) — shard format v1 stores "
                    "pre-decoded uint8", source=self.path)
            img = as_u8
        block = (np.ascontiguousarray(img).tobytes()
                 + _LABEL.pack(int(label)))
        self._crcs.append(crc32(block))
        self._f.write(block)

    def close(self) -> int:
        """Finalize: write the crc table and the validated header;
        returns the record count."""
        if self._closed:
            return self.count
        self._closed = True
        try:
            if self.capacity is not None and self.count > self.capacity:
                raise RuntimeError("capacity bookkeeping corrupted")
            table = np.asarray(self._crcs, "<u4").tobytes()
            if self.capacity is None:
                # table goes where the placeholder wasn't: rewrite the
                # records after it (small shards; the converter passes
                # capacity for the streaming path)
                self._f.flush()
                with open(self.path, "rb") as rf:
                    rf.seek(HEADER_SIZE)
                    body = rf.read()
                self._f.seek(HEADER_SIZE)
                self._f.write(table)
                self._f.write(body)
            else:
                pad = b"\0" * (4 * (self.capacity - self.count))
                self._f.seek(HEADER_SIZE)
                self._f.write(table + pad)
                table = table + pad
            head = _HEADER.pack(MAGIC, VERSION, 0, self.count,
                                self.c, self.h, self.w, LABEL_BYTES,
                                self.stride, crc32(table))
            head += struct.pack("<I", crc32(head))
            self._f.seek(0)
            self._f.write(head.ljust(HEADER_SIZE, b"\0"))
        finally:
            self._f.close()
        return self.count

    def __enter__(self) -> "ShardWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def write_shard(path: str, records: Iterable[tuple[np.ndarray, int]]) -> int:
    """Write an iterable of (img, label) as one shard; geometry comes
    from the first record.  Returns the record count."""
    it = iter(records)
    try:
        img, label = next(it)
    except StopIteration:
        raise ValueError(f"{path}: cannot write an empty shard") from None
    w = ShardWriter(path, *np.asarray(img).shape)
    with w:
        w.add(img, label)
        for img, label in it:
            w.add(img, label)
    return w.count


# ---------------------------------------------------------------------------
# Reader
# ---------------------------------------------------------------------------

def _parse_header(raw: bytes, source: str) -> tuple:
    if len(raw) < HEADER_SIZE:
        raise DataCorruptionError(
            f"shard header truncated ({len(raw)} < {HEADER_SIZE} bytes)",
            source=source, offset=0)
    (magic, version, _flags, count, c, h, w, label_bytes, stride,
     table_crc) = _HEADER.unpack_from(raw, 0)
    if magic != MAGIC:
        raise DataCorruptionError(
            f"bad shard magic {magic!r}", source=source, offset=0)
    (header_crc,) = struct.unpack_from("<I", raw, _HEADER.size)
    if crc32(raw[:_HEADER.size]) != header_crc:
        raise DataCorruptionError(
            "shard header checksum mismatch", source=source, offset=0)
    if version != VERSION:
        raise DataCorruptionError(
            f"unsupported shard version {version}", source=source, offset=0)
    if (label_bytes != LABEL_BYTES or min(c, h, w) <= 0
            or stride != c * h * w + LABEL_BYTES):
        raise DataCorruptionError(
            f"inconsistent shard geometry c={c} h={h} w={w} "
            f"stride={stride}", source=source, offset=0)
    return count, c, h, w, stride, table_crc


class RecordShard:
    """Reader over one shard in an :class:`ObjectStore`.

    The header and crc table are read once at construction (two small
    ranged reads); after that ``read(i)`` is exactly one ranged read of
    ``stride`` bytes, crc-validated against the table, and
    ``read_rows``/``check_rows`` do the same for a run of records
    straight into rows of a caller's batch.  Thread-safe for concurrent
    readers (the parallel ranged-read pool) as long as the backing
    store's ``open_range``/``read_into`` are — ``LocalStore`` uses
    positioned reads on a refcounted fd pool for exactly this.

    Satisfies ``__len__``/``__getitem__``, so a shard can stand directly
    as a ``PartitionedDataset`` partition (decode-free lazy records) and
    compose with ``PartitionedDataset.cached()``.

    ``attach_cache``: an optional tiered ``pipeline.ShardCache`` holding
    whole-shard pixel blobs — a cold shard streams from the store in ONE
    big ranged read (wire speed, not one blocking read per record), a
    warm one serves every record from host RAM, and RAM evictions spill
    to local-disk files instead of falling back to the origin store.
    """

    def __init__(self, store: ObjectStore, key: str,
                 source: str | None = None):
        self.store = store
        self.key = key
        self.source = source or key
        head = store.open_range(key, 0, HEADER_SIZE)
        (self.count, self.c, self.h, self.w, self.stride,
         table_crc) = _parse_header(head, self.source)
        table = store.open_range(key, HEADER_SIZE, 4 * self.count)
        if len(table) != 4 * self.count or crc32(table) != table_crc:
            raise DataCorruptionError(
                f"shard crc table corrupt ({len(table)} bytes)",
                source=self.source, offset=HEADER_SIZE)
        self.crcs = np.frombuffer(table, "<u4").copy()
        self.data_off = HEADER_SIZE + 4 * self.count
        self._cache = None
        self._cache_key: Any = None

    @classmethod
    def open(cls, path: str) -> "RecordShard":
        """Open a local shard file (a LocalStore rooted at its dir)."""
        from .objectstore import LocalStore
        root, name = os.path.split(os.path.abspath(path))
        return cls(LocalStore(root), name, source=path)

    # -- integrity plumbing ----------------------------------------------
    def register_checksums(self, vstore: VerifyingStore,
                           key: str | None = None) -> int:
        """Register every record block's crc32 with a VerifyingStore so
        its ranged reads become self-verifying (torn-read retry + typed
        corruption with byte-offset attribution).  Returns the count."""
        key = key or self.key
        for i in range(self.count):
            vstore.add_checksum(key, self.offset(i), int(self.crcs[i]))
        return self.count

    def attach_cache(self, cache, key: Any = None) -> None:
        """Serve ``read_raw`` through a tiered ``ShardCache`` of
        whole-shard pixel blobs (see class docstring)."""
        self._cache = cache
        self._cache_key = key if key is not None else self.source

    # -- record access ----------------------------------------------------
    def offset(self, i: int) -> int:
        return self.data_off + i * self.stride

    def _load_blob(self) -> bytes:
        # The whole-region read skips the store's range-checksum tier:
        # that registry is keyed per record block, and a blob read at
        # data_off would collide with record 0's entry.  Integrity is
        # not weakened — unpack() crc-validates every slice of the blob
        # against the in-shard table.
        store = self.store
        if isinstance(store, VerifyingStore):
            from ..utils.retry import io_retry
            return io_retry(store.inner.open_range, self.key,
                            self.data_off, self.count * self.stride,
                            describe=f"shard blob {self.key}")
        return store.open_range(self.key, self.data_off,
                                self.count * self.stride)

    def _corrupt(self, i: int, nbytes: int) -> DataCorruptionError:
        return DataCorruptionError(
            f"record block checksum mismatch "
            f"({nbytes}/{self.stride} bytes)",
            source=self.source, key=i, offset=self.offset(i))

    @property
    def max_run(self) -> int:
        """Records one :meth:`read_rows` may cover: a run where the bytes
        come from the cached blob or the store reads in place; one
        through any other store, whose ranged read (a GET, a
        ``VerifyingStore``'s checksum and torn-read retry) is a
        record's."""
        if self._cache is not None or self.store.reads_in_place:
            return MAX_RUN
        return 1

    def read_rows(self, i: int, imgs: np.ndarray,
                  labels: np.ndarray) -> tuple[int, bool]:
        """Records ``i .. i+len(imgs)-1`` (at most :attr:`max_run`) into
        the rows of ``imgs`` (uint8 ``[m, c, h, w]``) and ``labels``
        (``<i8 [m]``), both contiguous — the feed's read: no ``bytes``
        a record.  NOT yet crc-validated; pair with :meth:`check_rows`.
        Returns (records whose bytes arrived whole, whether they were
        read in place): the store's ``read_into`` lands them there; the
        cached blob's slice, and a store with no override, copy."""
        m = len(imgs)
        if not 0 <= i <= self.count - m:
            raise IndexError(
                f"records [{i}, {i + m}) out of range [0, {self.count})")
        if self._cache is not None:
            blob = self._cache.get(self._cache_key, self._load_blob)
            got = max(0, min(m, len(blob) // self.stride - i))
            if got:
                rec = np.frombuffer(
                    blob, np.uint8, got * self.stride,
                    i * self.stride).reshape(got, self.stride)
                imgs[:got].reshape(got, -1)[...] = rec[:, :-LABEL_BYTES]
                labels[:got].view(np.uint8).reshape(
                    got, LABEL_BYTES)[...] = rec[:, -LABEL_BYTES:]
            return got, False
        got = self.store.read_into(
            self.key, self.offset(i),
            [b for r in range(m) for b in (imgs[r], labels[r:r + 1])])
        return got // self.stride, self.store.reads_in_place

    def check_rows(self, i: int, imgs: np.ndarray, labels: np.ndarray,
                   arrived: int) -> list[tuple[int, DataCorruptionError]]:
        """Validate rows filled by :meth:`read_rows` where they lie: each
        record's crc (the label's chained over the pixels') against the
        table.  Returns ``(row, error)`` for every record that fails or
        did not arrive whole: the typed error :meth:`unpack` raises.
        Thread-safe, and meant for several pool threads at once."""
        m = len(imgs)
        want = self.crcs[i:i + m]
        # one native call a run, the interpreter lock released once;
        # with no toolchain, zlib.crc32 a record, which takes it back a
        # record (8 threads then check no faster than 4)
        failed = native.crc32_rows(imgs, labels, want)
        if failed is None:
            failed = [zlib.crc32(labels[r:r + 1], zlib.crc32(imgs[r]))
                      & 0xFFFFFFFF != want[r] for r in range(m)]
        return [(r, self._corrupt(i + r, self.stride if r < arrived else 0))
                for r in range(m) if r >= arrived or failed[r]]

    def read_raw(self, i: int) -> bytes:
        """Record ``i``'s block as fresh ``bytes`` — one ranged read (or
        a slice of the cached whole-shard blob), NOT yet crc-validated;
        pair with :meth:`unpack`.  The one-record path (``read``, lazy
        partitions, an injected fault); the feed reads through
        :meth:`read_rows`."""
        if not 0 <= i < self.count:
            raise IndexError(f"record {i} out of range [0, {self.count})")
        if self._cache is not None:
            blob = self._cache.get(self._cache_key, self._load_blob)
            off = i * self.stride
            return bytes(blob[off:off + self.stride])
        return self.store.open_range(self.key, self.offset(i), self.stride)

    def unpack(self, raw: bytes, i: int) -> tuple[np.ndarray, int]:
        """Validate + unpack one record block: crc against the table,
        then a zero-decode frombuffer VIEW of ``raw`` (no copy).
        Corruption raises :class:`DataCorruptionError` with
        source/key/offset attribution (the quarantine layer's admission
        unit)."""
        if len(raw) != self.stride or crc32(raw) != int(self.crcs[i]):
            raise self._corrupt(i, len(raw))
        img = np.frombuffer(raw, np.uint8,
                            count=self.stride - LABEL_BYTES).reshape(
                                self.c, self.h, self.w)
        (label,) = _LABEL.unpack_from(raw, self.stride - LABEL_BYTES)
        return img, label

    def read(self, i: int) -> tuple[np.ndarray, int]:
        return self.unpack(self.read_raw(i), i)

    # -- lazy-partition contract -----------------------------------------
    def __len__(self) -> int:
        return self.count

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return [self.read(i) for i in range(*idx.indices(self.count))]
        return self.read(int(idx))

    def __iter__(self):
        return (self.read(i) for i in range(self.count))


class ShardSet:
    """An ordered set of shards behind one feed: cumulative indexing
    (``locate`` maps a dataset ordinal to (shard, local index)), one
    shared store, optional VerifyingStore wrap with every shard's crc
    table pre-registered."""

    def __init__(self, shards: list[RecordShard], source: str):
        if not shards:
            raise ValueError(f"{source}: no record shards found")
        self.shards = shards
        self.source = source
        geo = {(s.c, s.h, s.w) for s in shards}
        if len(geo) > 1:
            raise DataCorruptionError(
                f"shards disagree on geometry: {sorted(geo)}",
                source=source)
        self.c, self.h, self.w = shards[0].c, shards[0].h, shards[0].w
        self._starts: list[int] = []
        at = 0
        for s in shards:
            self._starts.append(at)
            at += s.count
        self.count = at

    @classmethod
    def open(cls, source: str, verify: bool = False) -> "ShardSet":
        """Open every ``*.rec`` under ``source`` — a local file, a local
        directory, or an object-store URL (``s3://``, ``gs://``,
        ``file://``) — in sorted key order.  ``verify=True`` wraps the
        store in a :class:`VerifyingStore` carrying every record's crc,
        so each ranged read is independently verified with the one-
        fresh-re-read torn-vs-rot distinction."""
        path = source[7:] if source.startswith("file://") else source
        if "://" not in source and os.path.isfile(path):
            from .objectstore import LocalStore
            root, name = os.path.split(os.path.abspath(path))
            store: ObjectStore = LocalStore(root)
            keys = [name]
        else:
            store, prefix = get_store(source)
            keys = [k for k in store.list_keys(prefix)
                    if k.endswith(SHARD_SUFFIX)]
        shards = [RecordShard(store, k, source=f"{source}:{k}")
                  for k in keys]
        if verify:
            vstore = VerifyingStore(store)
            for s in shards:
                s.register_checksums(vstore)
                s.store = vstore
        return cls(shards, source)

    def attach_cache(self, cache) -> None:
        for i, s in enumerate(self.shards):
            s.attach_cache(cache, key=(self.source, i))

    def locate(self, ordinal: int) -> tuple[RecordShard, int]:
        i = ordinal % self.count
        import bisect
        si = bisect.bisect_right(self._starts, i) - 1
        return self.shards[si], i - self._starts[si]

    def partitions(self) -> list[RecordShard]:
        return list(self.shards)

    def close(self) -> None:
        for s in self.shards:
            s.store.close()


# ---------------------------------------------------------------------------
# Feed
# ---------------------------------------------------------------------------

def is_records_source(source: str) -> bool:
    """True when ``source`` names shard records: a ``*.rec`` file/key or
    a directory (or store prefix) holding at least one."""
    if source.endswith(SHARD_SUFFIX):
        return True
    path = source[7:] if source.startswith("file://") else source
    if "://" in source or not os.path.isdir(path):
        return False
    try:
        return any(n.endswith(SHARD_SUFFIX) for n in os.listdir(path))
    except OSError:
        return False


# Arrays a feed keeps an eye on for reuse (``_unheld``): more than any
# pipeline below it holds at once, so none is forgotten before it is let go.
_SPARE_MAX = 64


def _unheld(spare: list) -> np.ndarray | None:
    """Take from ``spare`` an array that nothing but ``spare`` refers to
    any more: every consumer, queue, view and ``device_put`` that aliases
    it has let it go, so writing it again can be seen by no one.  (A
    reference count of 2: the list's, and the call's own argument.)"""
    for i in range(len(spare)):
        if sys.getrefcount(spare[i]) == 2:
            return spare.pop(i)
    return None


class _Batch:
    """One batch under assembly: its arrays, the rows no pull has been
    given yet, and the rows that hold good records, in pull order."""

    __slots__ = ("imgs", "labels", "free", "rows")

    def __init__(self, imgs: np.ndarray):
        self.imgs = imgs
        self.labels = np.empty(len(imgs), _LABEL_DTYPE)
        self.free = collections.deque(range(len(imgs)))
        self.rows: list[int] = []


def records_feed(lp, phase, tops: list[str] | None = None, seed: int = 0,
                 quarantine: Quarantine | None = None,
                 workers: int | None = None, stats=None, buffers: int = 0,
                 raw: bool = False, verify: bool | None = None,
                 cache=None) -> Iterator[dict[str, np.ndarray]]:
    """Batch stream for a records-backed ``Data`` layer — ``db_feed``'s
    contract without the decode stage, each batch assembled in place.

    Determinism mirrors ``db_feed`` exactly: records are PULLED serially
    on the consumer thread (sequential ordinal, the fault injector's
    per-seq ``corrupt_record`` coin, quarantine epoch accounting) and a
    batch is the next ``batch_size`` GOOD records of that order, while
    the ranged READS fan out over an order-preserving ``DecodePool`` —
    so for a fixed seed the parallel records stream is bit-identical to
    the serial one AND to the serial LMDB decode path the shards were
    converted from (same pixels, same labels, same quarantine
    admissions, same replacement pulls).

    In place: a batch's ``uint8 [n, c, h, w]`` array and its label
    column exist before its reads are submitted, a pull is given the
    batch's next row, and a reader lands its run of records in their
    rows (``RecordShard.read_rows``: ``os.preadv`` through a
    ``LocalStore``, a slice of the cached blob with a ``ShardCache``, a
    copy of ``open_range``'s bytes through any other store) and checks
    each crc there — every record, before its batch is yielded.  The
    pulls of batch k+1 are submitted before batch k is collected, so the
    readers never drain at a batch's edge, also while this generator is
    suspended in ``yield``; their epoch rolls wait until the thread
    turns to that batch, so a quarantine admission falls in the budget
    it always did.  A corrupt record is rare and its path may be slow:
    its row is given to a later pull, what batch k lacks is copied out
    of batch k+1's rows (they are next in pull order), and a batch
    whose rows are out of order is gathered once when it is emitted.
    Read seconds book to the feed's ``read`` stage and the crc to
    ``decode``, summed over a batch's records and noted once a batch —
    perfwatch can tell a slow store from a slow host; ``read_in_place``
    / ``read_copied`` count the records by how their bytes arrived.

    ``raw=True`` skips the host transform and ships the uint8 array
    untouched (plus f32 labels) — the device-side augmentation path:
    pair with ``Solver.set_augment`` so crop/mirror/mean/scale run
    inside the compiled step.  A yielded batch is never written again
    while anything refers to it: its array is a fresh one, or one this
    feed made earlier that every holder has since let go (the consumer,
    a queue, a view, a ``device_put`` that aliases it: ``_unheld``), so
    steady state reads into memory whose pages are mapped already.
    ``buffers=N`` instead rotates the yielded array (the raw one, or the
    transform's output) through a ``BufferRing`` under that parameter's
    aliasing contract, the feed's own batch ahead counted in.  ``verify=True`` (or data_param
    ``verify``) routes reads through a :class:`VerifyingStore`.
    ``cache``: a tiered ``pipeline.ShardCache`` for whole-shard blobs
    (cold = one streaming read, warm = host RAM, evicted = local-disk
    spill)."""
    from .db import DataTransformer
    from .pipeline import BufferRing, DecodePool
    p = lp.sub("data_param")
    source = str(p.get("source"))
    batch = int(p.get("batch_size", 1))
    if verify is None:
        verify = bool(p.get("verify", False))
    shards = ShardSet.open(source, verify=verify)
    if cache is not None:
        shards.attach_cache(cache)
    c, h, w = shards.c, shards.h, shards.w
    tf = None if raw else DataTransformer(lp.sub("transform_param"),
                                          phase, seed)
    tops = tops or list(lp.top) or ["data", "label"]
    epoch_size = shards.count
    if quarantine is None:
        quarantine = Quarantine(QuarantinePolicy.from_env(),
                                epoch_size=epoch_size, source=source)
    injector = faults.get_injector()
    ring = BufferRing(buffers) if buffers else None
    if workers is None:
        workers = record_readers()
    run_len = max(1, min(MAX_RUN, batch // max(workers, 1)))
    in_order = list(range(batch))

    seq = 0                 # records pulled
    ahead = 0               # ... and not yet consumed
    rolls: set[int] = set()     # pulled seqs whose epoch roll is still due
    runs: collections.deque = collections.deque()   # submitted, in order
    ready: collections.deque = collections.deque()  # arrived, a record each
    tally = {"read": 0.0, "decode": 0.0, "in_place": 0, "copied": 0}

    def fetch_run(run) -> tuple[list, bool, float, float]:
        """Read a run into its rows and check each crc there (runs on
        pool workers).  A corrupt record is a value here, not a raise:
        its typed error reaches the quarantine at its place in the pull
        order, on the feed's thread.  The injected fault corrupts the
        payload AFTER the read — rotting bytes on the medium — so that
        one record goes through ``read_raw``'s copy."""
        shard, local, m, b, row, rseq, inject = run
        imgs, labels = b.imgs[row:row + m], b.labels[row:row + m]
        t0 = time.perf_counter()
        try:
            if inject:
                block = faults.corrupt_bytes(shard.read_raw(local), rseq)
                t1 = time.perf_counter()
                imgs[0], labels[0] = shard.unpack(block, local)
                return [], False, t1 - t0, time.perf_counter() - t1
            arrived, in_place = shard.read_rows(local, imgs, labels)
        except DataCorruptionError as e:
            # unpack's verdict, or a VerifyingStore's on the one record
            # it reads at a time (RecordShard.max_run)
            return [(0, e)], False, time.perf_counter() - t0, 0.0
        t1 = time.perf_counter()
        bad = shard.check_rows(local, imgs, labels, arrived)
        return bad, in_place, t1 - t0, time.perf_counter() - t1

    pool = DecodePool(fetch_run, workers=workers,
                      name=f"records:{source}", window=2 * batch + 2)

    spare: list[np.ndarray] = []    # arrays handed out, oldest first

    def new_batch() -> _Batch:
        """An array no one else holds: the ring's next under ``buffers``,
        else one of this feed's own that every holder has let go (its
        pages are mapped: a fresh 201 MB array is touched for the first
        time at a ninth of the speed), else a fresh one."""
        shape = (batch, c, h, w)
        if ring and tf is None:
            return _Batch(ring.take(shape, np.uint8))
        imgs = _unheld(spare)
        return _Batch(np.empty(shape, np.uint8) if imgs is None else imgs)

    def submit_pulls(b: _Batch, count: int) -> None:
        """``count`` serial pulls bound for ``b``'s next free rows:
        ordinal advance and fault coin happen here, on the consumer
        thread, in pull order — exactly where ``db_feed`` flips them.
        Consecutive records of one shard bound for consecutive rows go
        to the pool as one run."""
        nonlocal seq, ahead

        def send(run: list) -> None:
            runs.append(tuple(run))
            pool.submit(runs[-1])

        run = None
        for _ in range(count):
            shard, local = shards.locate(seq)
            inject = injector.corrupt_record(seq)
            row = b.free.popleft()
            if seq and seq % epoch_size == 0:
                rolls.add(seq)
            if (run is not None and run[0] is shard and run[2] < limit
                    and not inject and local == run[1] + run[2]
                    and row == run[4] + run[2]):
                run[2] += 1
            else:
                if run is not None:
                    send(run)
                run = [shard, local, 1, b, row, seq, inject]
                limit = 1 if inject else min(run_len, shard.max_run)
            seq += 1
        if run is not None:
            send(run)
        ahead += count

    def arrive() -> None:
        """The next run's records, in pull order, onto ``ready``."""
        bad, in_place, read_s, crc_s = pool.result()
        _, _, m, b, row, rseq, _ = runs.popleft()
        tally["read"] += read_s
        tally["decode"] += crc_s
        tally["in_place" if in_place else "copied"] += m
        bad = dict(bad)
        ready.extend((b, row + r, rseq + r, bad.get(r)) for r in range(m))

    def collect(cur: _Batch) -> None:
        """Consume records in pull order until ``cur`` holds ``batch``
        good ones."""
        nonlocal ahead
        while len(cur.rows) < batch:
            if not ahead:           # nothing pulled ahead is left to take
                submit_pulls(cur, batch - len(cur.rows))
            if not ready:
                arrive()
            b, row, rseq, err = ready.popleft()
            ahead -= 1
            if rolls and rseq in rolls:     # pulled ahead, consumed as a
                rolls.remove(rseq)          # replacement: rolls where the
                quarantine.start_epoch()    # serial pull did, before it
            if err is not None:
                b.free.append(row)
                quarantine.admit(err)   # raises QuarantineExceeded past budget
                continue
            if b is not cur:        # read into the next batch's row
                to = cur.free.popleft()
                cur.imgs[to], cur.labels[to] = b.imgs[row], b.labels[row]
                b.free.append(row)
                row = to
            cur.rows.append(row)

    def emit(b: _Batch, ordinal: int) -> dict[str, np.ndarray]:
        with telemetry.span("feed.stack", cat="feed", batch=ordinal):
            imgs, labels = b.imgs, b.labels
            if b.rows != in_order:      # a hole was filled out of order
                imgs, labels = imgs[b.rows], labels[b.rows]
            labels = labels.astype(np.float32)
        if not (ring and tf is None):
            spare.append(b.imgs)
            del spare[:-_SPARE_MAX]
        data = imgs
        if tf is not None:
            t0 = time.perf_counter()
            shape = ((batch, c, tf.crop, tf.crop) if tf.crop
                     else (batch, c, h, w))
            data = tf.batch(imgs, out=ring.take(shape) if ring else None)
            if stats is not None:
                stats.note("transform", time.perf_counter() - t0)
        if stats is not None:
            stats.note("read", tally["read"])
            stats.note("decode", tally["decode"])
            stats.count_reads(tally["in_place"], tally["copied"])
            tally.update(read=0.0, decode=0.0, in_place=0, copied=0)
            stats.count_batch(batch)
        out = {tops[0]: data}
        if len(tops) > 1:
            out[tops[1]] = labels
        return out

    # one span a host batch, closed before the yield: what this thread
    # spends between two of them is back-pressure from the queue below
    nxt = new_batch()
    try:
        for ordinal in itertools.count():
            with telemetry.span("feed.assemble", cat="feed", batch=ordinal):
                with telemetry.span("feed.submit", cat="feed",
                                    batch=ordinal):
                    cur = nxt
                    # what the batch before took of this one's pulls
                    submit_pulls(cur, batch - ahead)
                    # these are this batch's first pulls: their epoch
                    # rolls fall before any of its admissions
                    for _ in rolls:
                        quarantine.start_epoch()
                    rolls.clear()
                    nxt = new_batch()
                    submit_pulls(nxt, batch)
                with telemetry.span("feed.collect", cat="feed",
                                    batch=ordinal):
                    collect(cur)
                out = emit(cur, ordinal)
            yield out
    finally:
        # Collected by a finalizing interpreter (a consumer that never
        # closed it), the readers are daemon threads already stopped
        # wherever they stood, reading ahead, and one may stand inside
        # the store's or the pool's lock: waiting for it would never end.
        if not sys.is_finalizing():
            pool.close()
            shards.close()


# ---------------------------------------------------------------------------
# Conversion (the library half of tools/convert.py)
# ---------------------------------------------------------------------------

def convert_to_shards(records: Iterable[tuple[np.ndarray, int]],
                      out_dir: str, *, quarantine: Quarantine | None = None,
                      shard_bytes: int | None = None,
                      prefix: str = "shard") -> dict[str, Any]:
    """Write an (img, label) stream as a directory of shards, rolling a
    new shard every ``shard_bytes`` (default ``SPARKNET_RECORD_SHARD_MB``).

    A record that raises :class:`DataCorruptionError` while being pulled
    from the source iterator — or that is not uint8-representable — goes
    through ``quarantine`` (the PR-3 path: skipped, counted per source,
    bounded budget) instead of poisoning the shard.  Returns a summary
    dict: shard paths, record count, quarantine report."""
    os.makedirs(out_dir, exist_ok=True)
    if shard_bytes is None:
        shard_bytes = shard_bytes_target()
    if quarantine is None:
        quarantine = Quarantine(QuarantinePolicy.from_env(),
                                source=out_dir)
    paths: list[str] = []
    writer: ShardWriter | None = None
    total = 0
    geometry: tuple[int, int, int] | None = None
    it = iter(records)
    while True:
        try:
            img, label = next(it)
        except StopIteration:
            break
        except DataCorruptionError as e:
            quarantine.admit(e)
            continue
        img = np.asarray(img)
        if writer is not None and writer.nbytes >= shard_bytes:
            writer.close()
            writer = None
        if writer is None:
            path = os.path.join(
                out_dir, f"{prefix}-{len(paths):05d}{SHARD_SUFFIX}")
            writer = ShardWriter(path, *img.shape)
            geometry = (writer.c, writer.h, writer.w)
            paths.append(path)
        try:
            writer.add(img, label)
        except DataCorruptionError as e:
            quarantine.admit(e)
            continue
        total += 1
    if writer is not None:
        writer.close()
    if not paths:
        raise ValueError(f"{out_dir}: source yielded no writable records")
    return {"shards": paths, "records": total, "geometry": geometry,
            "quarantine": quarantine.report()}
