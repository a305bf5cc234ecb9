"""Parallel, vectorized feed pipeline: decode pool, buffers, shard cache.

BENCH_r05 measured the device compute path at 18,149 img/s while the
end-to-end feed-in-loop leg delivered 70.7 img/s — a 32× host-side gap
(``feed_compute_ratio: 32.2``).  That is the same wall the reference hit:
its per-minibatch JVM callback fed Caffe one image at a time through JNA
(reference: caffe/src/caffe/layers/java_data_layer.cpp:36-44, the measured
hot spot of CallbackBenchmarkSpec), and both Caffe (arXiv 1408.5093) and
Caffe con Troll (arXiv 1504.04343) showed that batched, parallelized
host-side decode/transform is where shallow engineering buys an order of
magnitude.  This module is that engineering, as four composable pieces:

- :class:`DecodePool` — an ORDER-PRESERVING thread pool: work items go in
  serially (so stateful pulls — DB cursors, fault-injection coin flips,
  quarantine epoch accounting — stay deterministic), results come out in
  submission order, and exceptions raised by the work function surface at
  the failing item's ordinal position exactly as a serial loop would see
  them.  ``workers=0`` is the serial reference path: identical ordering,
  identical error positions, zero threads — the parity oracle the tests
  and ``tools/feedbench.py`` compare against.  A worker thread that DIES
  (not raises — dies) surfaces as a typed :class:`DecodeWorkerError` on
  the consumer, never a hang.
- :class:`FeedStats` — per-stage wall-time accounting (read / decode /
  transform / device_put) so the bench's ``feed_in_loop`` JSON can say
  WHERE feed time goes instead of one opaque number — ``read`` is the
  object-store/disk IO stage the records path books its ranged reads
  to, so a slow store is attributable separately from a slow host.
- :class:`BufferRing` — preallocated rotating output buffers for
  batch-level transforms.  Opt-in: the caller owns the aliasing contract
  (a buffer is reused after ``size`` further batches, so the ring must be
  deeper than every downstream queue that holds batches concurrently).
- :class:`ShardCache` — a bounded LRU over materialized (decoded)
  partitions so multi-epoch training pays decode once per shard, not once
  per epoch (used via ``PartitionedDataset.cached``).

Knobs (shared by ``db_feed``, ``device_feed``, the launcher, and bench):

- ``SPARKNET_FEED_WORKERS`` — decode pool width (default: cpu count,
  capped at 8; 0 = serial reference path).
- ``SPARKNET_FEED_DEPTH``   — prefetch depth for ``device_feed`` (default
  4: deep double-buffering so decode, transform, and host→HBM transfer
  all hide under device steps).
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Iterator, Sequence

import numpy as np

from ..utils import knobs, telemetry


def _env_int(name: str, default: int) -> int:
    raw = knobs.raw(name, "")
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {raw!r}") from None


def feed_workers(default: int | None = None) -> int:
    """Decode-pool width: ``SPARKNET_FEED_WORKERS``, else ``default``,
    else cpu count capped at 8.  0 means the serial reference path."""
    if default is None:
        default = min(os.cpu_count() or 1, 8)
    n = _env_int("SPARKNET_FEED_WORKERS", default)
    if n < 0:
        raise ValueError(f"SPARKNET_FEED_WORKERS must be >= 0, got {n}")
    return n


def feed_depth(default: int = 4) -> int:
    """Prefetch depth: ``SPARKNET_FEED_DEPTH``, else ``default``."""
    n = _env_int("SPARKNET_FEED_DEPTH", default)
    if n < 1:
        raise ValueError(f"SPARKNET_FEED_DEPTH must be >= 1, got {n}")
    return n


class FeedStats:
    """Thread-safe per-stage time/count accounting for one feed.

    Stage seconds are summed across whichever threads ran the stage, so
    with a parallel pool ``decode_s`` is cpu-seconds (it can exceed wall
    time — that is the point of the pool).  ``wait_s`` is the consumer's
    side: the seconds ``DeviceFeed.__next__`` was blocked for a staged
    batch.  A stage notes as often as suits it: ``records_feed`` sums
    its readers' ``read`` and ``decode`` seconds over a batch's records
    and notes each once a batch (same totals, two locked calls a batch
    in place of two a record), and counts there how the records' bytes
    arrived (``read_in_place``, ``read_copied``).  ``snapshot()``
    returns totals; ``per_batch()`` divides by delivered batches for
    the bench JSON."""

    STAGES = ("read", "decode", "transform", "device_put", "wait")

    def __init__(self):
        self._lock = threading.Lock()
        self._s = {k: 0.0 for k in self.STAGES}
        self._delivery = "host"
        self.batches = 0
        self.records = 0
        self.read_in_place = 0     # records read straight into their row
        self.read_copied = 0       # ... and through a copy of their bytes
        self.cache_hits = 0        # RAM-tier hits (back-compat meaning)
        self.cache_disk_hits = 0   # served from the local-disk spill tier
        self.cache_misses = 0      # every tier missed: origin materialize

    def note(self, stage: str, seconds: float, records: int = 0) -> None:
        with self._lock:
            self._s[stage] = self._s.get(stage, 0.0) + seconds
            self.records += records
        # telemetry plane: the stage timing the pipeline already measured
        # becomes a histogram sample — DecodePool / transforms /
        # DeviceFeed all route through here, so one hook covers every
        # feed stage (the spans are per batch, where the work happens)
        telemetry.get_registry().histogram(
            "feed_stage_seconds",
            "host feed pipeline stage latency").observe(seconds,
                                                        stage=stage)

    def delivered_by(self, stage: str) -> None:
        """Name the stage whose batches reach the consumer.  ``batches``
        counts delivered batches: the host stage's (``records_feed``,
        ``db_feed``) while it is the only one, the ``DeviceFeed``'s
        (``"device"``) once one is built over this object, so that two
        stages sharing one ``FeedStats`` count each batch once."""
        with self._lock:
            self._delivery = stage

    def count_batch(self, records: int = 0, stage: str = "host") -> None:
        with self._lock:
            self.records += records
            if stage != self._delivery:
                return
            self.batches += 1
        telemetry.get_registry().counter(
            "feed_batches_total", "batches delivered to the consumer"
        ).inc()

    def count_reads(self, in_place: int, copied: int) -> None:
        """How a batch's records reached their rows (``records_feed``):
        read in place by the store, or copied out of ``bytes`` (the
        cached blob, a store with no ``read_into`` of its own, an
        injected fault)."""
        with self._lock:
            self.read_in_place += in_place
            self.read_copied += copied

    def note_cache(self, hit: bool, tier: str = "ram") -> None:
        """Record one shard-cache lookup outcome.  ``tier`` labels WHICH
        tier served a hit (``ram`` or ``disk``); a miss means every tier
        missed.  ``cache_hits`` keeps its pre-tier meaning (RAM hits) so
        existing consumers and the bench JSON stay comparable."""
        with self._lock:
            if hit and tier == "disk":
                self.cache_disk_hits += 1
            elif hit:
                self.cache_hits += 1
            else:
                self.cache_misses += 1
        telemetry.get_registry().counter(
            "feed_cache_total", "shard cache lookups by outcome and tier"
        ).inc(result="hit" if hit else "miss",
              tier=tier if hit else "none")

    class _Timer:
        __slots__ = ("_stats", "_stage", "_records", "_t0")

        def __init__(self, stats, stage, records):
            self._stats, self._stage, self._records = stats, stage, records

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._stats.note(self._stage,
                             time.perf_counter() - self._t0, self._records)

    def timed(self, stage: str, records: int = 0) -> "FeedStats._Timer":
        return self._Timer(self, stage, records)

    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            out = {f"{k}_s": round(v, 6) for k, v in self._s.items()}
            out.update(batches=self.batches, records=self.records,
                       read_in_place=self.read_in_place,
                       read_copied=self.read_copied,
                       cache_hits=self.cache_hits,
                       cache_disk_hits=self.cache_disk_hits,
                       cache_misses=self.cache_misses)
            return out

    def per_batch(self) -> dict[str, float]:
        """Average stage seconds per delivered batch (0.0 before the
        first batch)."""
        with self._lock:
            n = max(self.batches, 1)
            return {f"{k}_s": round(v / n, 6) for k, v in self._s.items()}


class DecodeWorkerError(RuntimeError):
    """A pipeline worker thread died without producing its result (thread
    death, not a work-function exception — those propagate as themselves
    at their ordinal position).  Carries the pool name and the ordinal of
    the orphaned item so the failure is attributable, never a hang."""

    def __init__(self, name: str, ticket: int, detail: str = ""):
        self.pool = name
        self.ticket = ticket
        suffix = f": {detail}" if detail else ""
        super().__init__(
            f"{name} pipeline worker died before producing item "
            f"#{ticket}{suffix}")


_STOP = object()


class DecodePool:
    """Order-preserving parallel map with a bounded in-flight window.

    Items are submitted serially (``submit``) and consumed serially
    (``result``), in the same order; only the work function ``fn`` runs
    on the pool threads.  That split is what keeps a stateful producer
    deterministic: DB cursor advance, fault-injection coin flips, and
    quarantine epoch accounting all happen on the caller's thread in the
    exact sequence the serial path would produce, while the pure decode
    work fans out.

    Exception contract: an exception raised BY ``fn`` is re-raised from
    ``result()`` at that item's position (so ``DataCorruptionError``
    reaches the quarantine in serial order); a worker thread that dies
    without recording a result raises :class:`DecodeWorkerError` from
    ``result()`` within ~``2 × _POLL_S`` — a crashed pipeline is a typed
    error, never a hang.

    ``workers=0`` runs ``fn`` lazily on the consumer thread at
    ``result()`` time — the serial reference path with identical
    ordering, used for parity tests and as the no-thread fallback.
    """

    _POLL_S = 0.1

    def __init__(self, fn: Callable[[Any], Any], workers: int | None = None,
                 window: int | None = None, name: str = "decode",
                 stats: FeedStats | None = None, stage: str = "decode"):
        self.fn = fn
        self.name = name
        self.workers = feed_workers() if workers is None else int(workers)
        if self.workers < 0:
            raise ValueError(f"workers must be >= 0, got {self.workers}")
        self._window = int(window) if window else max(2, 2 * self.workers)
        self._stats = stats
        self._stage = stage
        self._closed = False
        self._next_submit = 0
        self._next_consume = 0
        if self.workers == 0:
            self._pending: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
            self._threads: list[threading.Thread] = []
            return
        self._in: "queue.Queue[Any]" = queue.Queue()
        self._cond = threading.Condition()
        self._results: dict[int, tuple[bool, Any]] = {}
        self._threads = [
            threading.Thread(target=self._run, name=f"{name}-{i}",
                             daemon=True)
            for i in range(self.workers)]
        for t in self._threads:
            t.start()

    # -- worker side ------------------------------------------------------
    def _run(self) -> None:
        while True:
            item = self._in.get()
            if item is _STOP:
                return
            ticket, payload = item
            t0 = time.perf_counter()
            try:
                value, ok = self.fn(payload), True
            except BaseException as e:  # re-raised at the item's ordinal
                value, ok = e, False
            if self._stats is not None:
                self._stats.note(self._stage, time.perf_counter() - t0)
            with self._cond:
                self._results[ticket] = (ok, value)
                self._cond.notify_all()

    # -- consumer side ----------------------------------------------------
    @property
    def in_flight(self) -> int:
        return self._next_submit - self._next_consume

    def submit(self, item: Any) -> int:
        """Enqueue one work item; blocks while the in-flight window is
        full (backpressure), returns the item's ticket."""
        if self._closed:
            raise RuntimeError(f"{self.name} pool is closed")
        ticket = self._next_submit
        self._next_submit += 1
        if self.workers == 0:
            self._pending.put(item)
            return ticket
        with self._cond:
            while (self._next_submit - self._next_consume > self._window
                   and not self._closed):
                self._check_workers(ticket)
                self._cond.wait(self._POLL_S)
        self._in.put((ticket, item))
        return ticket

    def _check_workers(self, ticket: int) -> None:
        if not any(t.is_alive() for t in self._threads):
            raise DecodeWorkerError(
                self.name, ticket, "no live workers left in the pool")

    def result(self) -> Any:
        """The next result in submission order; re-raises the work
        function's exception for that item, or DecodeWorkerError if the
        pool died under it."""
        if self._next_consume >= self._next_submit:
            raise RuntimeError(
                f"{self.name} pool: result() with nothing in flight")
        ticket = self._next_consume
        if self.workers == 0:
            item = self._pending.get_nowait()
            self._next_consume += 1
            t0 = time.perf_counter()
            try:
                return self.fn(item)
            finally:
                if self._stats is not None:
                    self._stats.note(self._stage, time.perf_counter() - t0)
        with self._cond:
            while ticket not in self._results:
                # the wait is a short poll that re-checks pool liveness:
                # a dead pool is a typed error on the consumer, not a hang
                self._check_workers(ticket)
                self._cond.wait(self._POLL_S)
            ok, value = self._results.pop(ticket)
            self._next_consume += 1
            self._cond.notify_all()
        if ok:
            return value
        raise value

    def imap(self, it) -> Iterator[Any]:
        """Order-preserving parallel map over an iterator.  A background
        pump thread advances the source and submits under the window's
        backpressure; results are yielded in source order.  An exception
        raised by the SOURCE is re-raised after every already-submitted
        item has been yielded (drain-then-fail, matching
        ``PrefetchIterator`` semantics)."""
        if self.workers == 0:
            for item in it:
                self.submit(item)
                yield self.result()
            return
        src_err: list[BaseException] = []
        src_done = threading.Event()

        def pump() -> None:
            try:
                for item in it:
                    if self._closed:
                        return
                    self.submit(item)
            except BaseException as e:
                src_err.append(e)
            finally:
                src_done.set()
                with self._cond:
                    self._cond.notify_all()

        t = threading.Thread(target=pump, name=f"{self.name}-pump",
                             daemon=True)
        t.start()
        while True:
            with self._cond:
                while (self._next_consume >= self._next_submit
                       and not src_done.is_set()):
                    self._cond.wait(self._POLL_S)
            if self._next_consume < self._next_submit:
                yield self.result()
                continue
            if src_err:
                raise src_err[0]
            return

    def close(self) -> None:
        """Stop the workers and drop queued work.  In-flight results are
        discarded; safe to call more than once."""
        self._closed = True
        if self.workers == 0:
            return
        while True:  # drop queued-but-unstarted work
            try:
                self._in.get_nowait()
            except queue.Empty:
                break
        for _ in self._threads:
            self._in.put(_STOP)
        with self._cond:
            self._cond.notify_all()
        for t in self._threads:
            t.join(timeout=5.0)

    def __enter__(self) -> "DecodePool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class BufferRing:
    """A rotation of ``size`` preallocated output buffers for batch-level
    transforms — the allocation-free hot path.

    Aliasing contract (the caller's to uphold): buffer k is handed out
    again after ``size`` further ``take()`` calls, so every downstream
    stage that holds batches concurrently (prefetch queue depth + staging
    window + the consumer's working batch) must together hold FEWER than
    ``size`` — size it ``depth + window + 2``, and one more under a raw
    ``records_feed``, which takes the next batch's buffer while this
    batch is still being read.  ``db_feed`` only rotates buffers when
    explicitly asked (``buffers=N``)."""

    def __init__(self, size: int):
        if size < 2:
            raise ValueError(f"BufferRing needs size >= 2, got {size}")
        self.size = size
        self._bufs: list[np.ndarray] = []
        self._i = 0
        self._shape: tuple | None = None
        self._dtype = None

    def take(self, shape: tuple, dtype=np.float32) -> np.ndarray:
        """The next buffer in rotation (contents undefined).  A shape or
        dtype change drops the old rotation and starts a new one."""
        if self._shape != shape or self._dtype != dtype:
            self._bufs = []
            self._shape, self._dtype = shape, dtype
            self._i = 0
        if len(self._bufs) < self.size:
            self._bufs.append(np.empty(shape, dtype))
            return self._bufs[-1]
        buf = self._bufs[self._i % self.size]
        self._i += 1
        return buf


def cache_shards(default: int = 4) -> int:
    """RAM-tier capacity: ``SPARKNET_CACHE_SHARDS``, else ``default``."""
    n = _env_int("SPARKNET_CACHE_SHARDS", default)
    if n < 1:
        raise ValueError(f"SPARKNET_CACHE_SHARDS must be >= 1, got {n}")
    return n


def cache_spill_dir() -> str | None:
    """Disk spill tier directory: ``SPARKNET_CACHE_SPILL_DIR`` (empty =
    spill disabled, the pre-tier behavior)."""
    return knobs.get_str("SPARKNET_CACHE_SPILL_DIR", "") or None


def cache_spill_shards(default: int = 16) -> int:
    """Disk-tier capacity: ``SPARKNET_CACHE_SPILL_SHARDS``."""
    n = _env_int("SPARKNET_CACHE_SPILL_SHARDS", default)
    if n < 1:
        raise ValueError(
            f"SPARKNET_CACHE_SPILL_SHARDS must be >= 1, got {n}")
    return n


class ShardCache:
    """Tiered bounded cache of materialized shards: host RAM LRU, with
    RAM evictions spilled to local-disk files instead of discarded.

    Multi-epoch training re-reads every shard once per epoch; for lazy
    partitions (``imagenet.LazyTarPartition`` decodes on slice access)
    that means paying the full decode each time, and for record shards
    streamed from an object store it means re-paying the wire.  The RAM
    tier keeps up to ``max_shards`` materialized values; when ``spill_dir``
    is set (default: the ``SPARKNET_CACHE_SPILL_DIR`` knob), up to
    ``max_spill`` RAM-evicted shards land as pickle files on local disk,
    so the fallback on a RAM miss is a local read, not the origin store.
    Lookup order: RAM → disk (hit promotes back to RAM) → materialize.

    Values may be any picklable materialization — decoded record lists
    (``CachedPartition``) or whole-shard ``bytes`` blobs
    (``records.RecordShard.attach_cache``); the cache stores whatever
    ``materialize()`` returns, uncoerced.

    Per-tier outcomes land in ``FeedStats`` (``cache_hits`` = RAM,
    ``cache_disk_hits``, ``cache_misses``) and the ``feed_cache_total``
    counter's ``tier`` label, so perfwatch can attribute a feed breach
    to the tier that missed.  Thread-safe; one cache is shared across
    all partitions of a ``PartitionedDataset.cached()`` view."""

    def __init__(self, max_shards: int = 4,
                 stats: FeedStats | None = None,
                 spill_dir: str | None = None,
                 max_spill: int | None = None):
        if max_shards < 1:
            raise ValueError(f"max_shards must be >= 1, got {max_shards}")
        self.max_shards = max_shards
        self._lock = threading.Lock()
        self._cache: "OrderedDict[Any, Any]" = OrderedDict()
        self._stats = stats
        self.spill_dir = cache_spill_dir() if spill_dir is None else (
            spill_dir or None)
        self.max_spill = (cache_spill_shards() if max_spill is None
                          else int(max_spill))
        self._spilled: "OrderedDict[Any, str]" = OrderedDict()  # key->path
        self.hits = 0
        self.disk_hits = 0
        self.misses = 0
        self.spills = 0

    # -- disk tier --------------------------------------------------------
    def _spill_path(self, key: Any) -> str:
        import zlib
        tag = zlib.crc32(repr(key).encode()) & 0xFFFFFFFF
        return os.path.join(self.spill_dir, f"shard-{tag:08x}.pkl")

    def _spill(self, key: Any, value: Any) -> None:
        """Write one RAM-evicted shard to the disk tier (atomic tmp +
        rename; a torn spill file can never be loaded).  Called under
        the lock — spills are rare (one per RAM eviction) and keeping
        them ordered keeps the disk-tier LRU exact."""
        import pickle
        os.makedirs(self.spill_dir, exist_ok=True)
        path = self._spill_path(key)
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                pickle.dump((key, value), f, protocol=4)
            os.replace(tmp, path)
        except OSError:
            # a full/unwritable spill disk degrades to no-spill, it
            # must not kill the feed
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return
        self._spilled.pop(key, None)
        self._spilled[key] = path
        self.spills += 1
        while len(self._spilled) > self.max_spill:
            _, old = self._spilled.popitem(last=False)
            try:
                os.unlink(old)
            except OSError:
                pass

    def _load_spilled(self, key: Any) -> Any | None:
        """Try the disk tier; verifies the stored key (crc32 tags can
        collide) and treats any unreadable file as a miss."""
        import pickle
        path = self._spilled.get(key)
        if path is None:
            return None
        try:
            with open(path, "rb") as f:
                stored_key, value = pickle.load(f)
        except (OSError, pickle.UnpicklingError, EOFError, ValueError):
            self._spilled.pop(key, None)
            return None
        if stored_key != key:
            return None
        return value

    def _insert(self, key: Any, value: Any) -> None:
        """RAM-tier insert + LRU eviction (under the lock); evictees go
        to the disk tier when one is configured."""
        self._cache[key] = value
        self._cache.move_to_end(key)
        while len(self._cache) > self.max_shards:
            old_key, old_value = self._cache.popitem(last=False)
            if self.spill_dir:
                self._spill(old_key, old_value)

    def get(self, key: Any, materialize: Callable[[], Any]) -> Any:
        with self._lock:
            if key in self._cache:
                self._cache.move_to_end(key)
                self.hits += 1
                if self._stats is not None:
                    self._stats.note_cache(True)
                return self._cache[key]
            if self.spill_dir:
                value = self._load_spilled(key)
                if value is not None:
                    self.disk_hits += 1
                    if self._stats is not None:
                        self._stats.note_cache(True, tier="disk")
                    self._insert(key, value)   # promote back to RAM
                    return value
        # materialize OUTSIDE the lock: decode of shard A must not block
        # a cache hit on shard B
        value = materialize()
        with self._lock:
            self.misses += 1
            if self._stats is not None:
                self._stats.note_cache(False)
            self._insert(key, value)
            return value

    def tier_counts(self) -> dict[str, int]:
        with self._lock:
            return {"ram_hits": self.hits, "disk_hits": self.disk_hits,
                    "misses": self.misses, "spills": self.spills,
                    "ram_shards": len(self._cache),
                    "disk_shards": len(self._spilled)}

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)


class CachedPartition:
    """A partition view that materializes its backing partition through a
    shared :class:`ShardCache` on first access.  Satisfies the
    ``__len__``/``__getitem__`` contract ``PartitionedDataset`` keeps for
    lazy partitions."""

    def __init__(self, base: Sequence, key: Any, cache: ShardCache):
        self._base = base
        self._key = key
        self._cache = cache

    def _records(self) -> Sequence:
        return self._cache.get(self._key, lambda: self._base[:])

    def __len__(self) -> int:
        return len(self._base)

    def __getitem__(self, idx):
        return self._records()[idx]

    def __iter__(self):
        return iter(self._records())
