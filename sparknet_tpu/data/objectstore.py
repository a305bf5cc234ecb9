"""Object-store abstraction for dataset ingestion.

The reference's workers stream training tars directly from S3
(reference: src/main/scala/loaders/ImageNetLoader.scala:25-38 list
objects, :56-86 stream-untar via AmazonS3Client + TarArchiveInputStream).
This module gives the loader chain the same shape — list keys under a
prefix, open a key as a byte stream — over URL-dispatched backends:

- ``file://`` (or a bare path): local filesystem, fully functional.
- ``s3://bucket/prefix``: via boto3 *when installed*; this build has no
  egress and no boto3, so construction raises a clear error telling the
  operator to install boto3 or stage locally (the reference's ec2/ tier
  likewise assumed AWS tooling existed on workers).
- ``gs://bucket/prefix``: same, via google-cloud-storage.

Every store yields file-like objects, so tarfile can stream without
loading archives whole — the property the bounded-RSS ingestion tier
(imagenet.py) relies on.
"""

from __future__ import annotations

import io
import os
import threading
from typing import BinaryIO, Iterator

from ..utils.retry import io_retry


class ObjectStore:
    """list/open interface over a keyed byte store."""

    def list_keys(self, prefix: str = "") -> list[str]:
        raise NotImplementedError

    def open(self, key: str) -> BinaryIO:
        raise NotImplementedError

    def size(self, key: str) -> int:
        raise NotImplementedError

    def open_range(self, key: str, offset: int, length: int) -> bytes:
        """Random-access read (tar-index lazy decode).  Default: seek."""
        with self.open(key) as f:
            f.seek(offset)
            return f.read(length)

    #: True where ``read_into`` lands the bytes in the caller's buffers
    #: with no copy between (the record feed counts its reads by this).
    reads_in_place = False

    def read_into(self, key: str, offset: int, buffers) -> int:
        """Ranged read INTO the caller's writable, contiguous buffers,
        filled in order from ``offset``; returns the bytes delivered
        (fewer than the buffers hold only where the object ends).
        Default: ``open_range`` and a copy, so a store with no override
        keeps everything its ``open_range`` does (a ranged GET, the
        ``VerifyingStore``'s retry and checksum)."""
        views = [memoryview(b).cast("B") for b in buffers]
        raw = self.open_range(key, offset, sum(len(v) for v in views))
        at = 0
        for v in views:
            part = raw[at:at + len(v)]
            v[:len(part)] = part
            at += len(part)
        return at

    def close(self) -> None:
        """Release any pooled resources (no-op by default)."""


class _PooledFd:
    """One pooled descriptor: refcounted so eviction under concurrent
    readers defers the close to the last reader out."""

    __slots__ = ("fd", "refs", "evicted")

    def __init__(self, fd: int):
        self.fd = fd
        self.refs = 0
        self.evicted = False


class LocalStore(ObjectStore):
    """Filesystem-backed store; keys are paths relative to ``root``.

    ``open_range`` (the lazy-partition path, one call per record) and
    ``read_into`` (the record feed's: one ``os.preadv`` lands a run of
    records in their rows of the batch, no ``bytes`` between) are fully
    thread-safe: both are positioned reads (no shared seek cursor to race
    on) against a small LRU pool of raw descriptors.  Only pool
    bookkeeping happens under the lock; the actual IO runs outside it,
    so N pool workers genuinely read in parallel.  A descriptor evicted (or ``close()``d) while readers are
    mid-``pread`` stays open until the last of them releases it —
    eviction can never invalidate a concurrent read."""

    _MAX_HANDLES = 8
    reads_in_place = True

    def __init__(self, root: str):
        self.root = root
        self._fds: "dict[str, _PooledFd]" = {}
        self._lock = threading.Lock()

    def _acquire(self, key: str) -> _PooledFd:
        with self._lock:
            h = self._fds.get(key)
            if h is not None:
                # re-insert: plain dicts preserve insertion order, so
                # pop+set keeps the dict LRU-first for eviction
                del self._fds[key]
                self._fds[key] = h
                h.refs += 1
                return h
        # open outside the lock (disk metadata IO must not serialize the
        # pool), then publish — racing openers of the same key keep the
        # first published fd and retire their own
        fd = os.open(os.path.join(self.root, key), os.O_RDONLY)
        with self._lock:
            h = self._fds.get(key)
            if h is not None:
                os.close(fd)
                del self._fds[key]
                self._fds[key] = h
                h.refs += 1
                return h
            h = _PooledFd(fd)
            h.refs = 1
            self._fds[key] = h
            while len(self._fds) > self._MAX_HANDLES:
                oldest = next(iter(self._fds))
                self._evict_locked(oldest)
            return h

    def _evict_locked(self, key: str) -> None:
        h = self._fds.pop(key)
        h.evicted = True
        if h.refs == 0:
            os.close(h.fd)

    def _release(self, h: _PooledFd) -> None:
        with self._lock:
            h.refs -= 1
            if h.evicted and h.refs == 0:
                os.close(h.fd)

    def open_range(self, key: str, offset: int, length: int) -> bytes:
        h = self._acquire(key)
        try:
            return os.pread(h.fd, length, offset)
        finally:
            self._release(h)

    def read_into(self, key: str, offset: int, buffers) -> int:
        h = self._acquire(key)
        try:
            return os.preadv(h.fd, buffers, offset)
        finally:
            self._release(h)

    def close(self) -> None:
        with self._lock:
            for key in list(self._fds):
                self._evict_locked(key)

    def __del__(self):  # best-effort fd release
        try:
            self.close()
        except Exception:
            pass

    def list_keys(self, prefix: str = "") -> list[str]:
        out = []
        for dirpath, _, files in os.walk(self.root):
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), self.root)
                if rel.startswith(prefix):
                    out.append(rel)
        return sorted(out)

    def open(self, key: str) -> BinaryIO:
        return open(os.path.join(self.root, key), "rb")

    def size(self, key: str) -> int:
        return os.path.getsize(os.path.join(self.root, key))


class S3Store(ObjectStore):
    """S3-backed store (ImageNetLoader.scala's AmazonS3Client role).
    Requires boto3; reads stream via GetObject (ranged for open_range)."""

    def __init__(self, bucket: str, region: str | None = None):
        try:
            import boto3  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "s3:// sources need boto3, which is not in this build — "
                "stage the tars locally (file://) or install boto3 on the "
                "ingest hosts") from e
        import boto3
        self.bucket = bucket
        self._s3 = boto3.client("s3", region_name=region)

    def list_keys(self, prefix: str = "") -> list[str]:
        keys = []
        paginator = self._s3.get_paginator("list_objects_v2")
        for page in paginator.paginate(Bucket=self.bucket, Prefix=prefix):
            keys.extend(o["Key"] for o in page.get("Contents", []))
        return sorted(keys)

    def open(self, key: str) -> BinaryIO:
        body = self._s3.get_object(Bucket=self.bucket, Key=key)["Body"]
        return io.BufferedReader(body)  # type: ignore[arg-type]

    def size(self, key: str) -> int:
        return self._s3.head_object(Bucket=self.bucket,
                                    Key=key)["ContentLength"]

    def open_range(self, key: str, offset: int, length: int) -> bytes:
        rng = f"bytes={offset}-{offset + length - 1}"
        return self._s3.get_object(Bucket=self.bucket, Key=key,
                                   Range=rng)["Body"].read()


class GCSStore(ObjectStore):
    """GCS-backed store; requires google-cloud-storage."""

    def __init__(self, bucket: str):
        try:
            from google.cloud import storage  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "gs:// sources need google-cloud-storage, which is not in "
                "this build — stage the tars locally (file://) or install "
                "it on the ingest hosts") from e
        from google.cloud import storage
        try:
            self._bucket = storage.Client().bucket(bucket)
        except Exception as e:  # no ADC credentials on this host
            raise RuntimeError(
                f"gs://{bucket} is unreachable from this host ({e}); "
                "stage the tars locally (file://) or configure GCP "
                "credentials on the ingest hosts") from e

    def list_keys(self, prefix: str = "") -> list[str]:
        return sorted(b.name for b in self._bucket.list_blobs(prefix=prefix))

    def open(self, key: str) -> BinaryIO:
        return self._bucket.blob(key).open("rb")

    def size(self, key: str) -> int:
        blob = self._bucket.get_blob(key)
        return blob.size if blob else 0

    def open_range(self, key: str, offset: int, length: int) -> bytes:
        return self._bucket.blob(key).download_as_bytes(
            start=offset, end=offset + length - 1)


class VerifyingStore(ObjectStore):
    """Per-record integrity tier over any store: ``open_range`` reads go
    through bounded transient-I/O retry (``utils.retry.io_retry``) and,
    when a checksum is registered for the (key, offset) range, the
    payload's crc32 is verified — with ONE fresh re-read before declaring
    corruption, so a torn read is distinguished from rot on the medium.
    A durable mismatch raises ``DataCorruptionError`` carrying the key
    and byte offset (the quarantine layer's attribution unit).

    This is the checksum the reference never had: its workers stream-
    untar straight from S3 (ImageNetLoader.scala:56-86) and a flipped
    byte in a JPEG payload is silently decoded or silently dropped.
    Build the checksum index at ingest time (``add_checksum`` per record
    while writing the tar index) and every later read is self-verifying.
    """

    def __init__(self, inner: ObjectStore,
                 checksums: dict[tuple[str, int], int] | None = None):
        self.inner = inner
        self.checksums = dict(checksums or {})

    def add_checksum(self, key: str, offset: int, crc: int) -> None:
        self.checksums[(key, offset)] = crc & 0xFFFFFFFF

    def checksum_range(self, key: str, offset: int, length: int) -> int:
        """Read + register a range's crc32 (the ingest-time half)."""
        from .integrity import crc32
        raw = io_retry(self.inner.open_range, key, offset, length,
                       describe=f"open_range {key}@{offset}")
        crc = crc32(raw)
        self.add_checksum(key, offset, crc)
        return crc

    def open_range(self, key: str, offset: int, length: int) -> bytes:
        from .integrity import DataCorruptionError, crc32
        raw = io_retry(self.inner.open_range, key, offset, length,
                       describe=f"open_range {key}@{offset}")
        expect = self.checksums.get((key, offset))
        if expect is None or crc32(raw) == expect:
            return raw
        # one fresh read: a transient torn read heals, real rot does not
        raw = io_retry(self.inner.open_range, key, offset, length,
                       describe=f"re-read {key}@{offset}")
        got = crc32(raw)
        if got != expect:
            raise DataCorruptionError(
                f"record checksum mismatch: crc32 {got:#010x} != "
                f"expected {expect:#010x} ({length} bytes)",
                source=key, key=key, offset=offset)
        return raw

    def list_keys(self, prefix: str = "") -> list[str]:
        return self.inner.list_keys(prefix)

    def open(self, key: str):
        return self.inner.open(key)

    def size(self, key: str) -> int:
        return self.inner.size(key)

    def close(self) -> None:
        self.inner.close()


def get_store(url: str) -> tuple[ObjectStore, str]:
    """URL -> (store, key prefix).  Bare paths and file:// map to
    LocalStore; s3://bucket/p and gs://bucket/p to their clients."""
    if url.startswith("s3://"):
        bucket, _, prefix = url[5:].partition("/")
        return S3Store(bucket), prefix
    if url.startswith("gs://"):
        bucket, _, prefix = url[5:].partition("/")
        return GCSStore(bucket), prefix
    path = url[7:] if url.startswith("file://") else url
    return LocalStore(path), ""
