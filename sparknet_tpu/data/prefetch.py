"""Background prefetch + async device transfer, with a feeder watchdog.

The reference's JavaData feed path is fully synchronous — every minibatch
blocks the solver on a C→JVM callback, a CPU float copy, and a lazy CPU→GPU
transfer (reference: caffe/src/caffe/layers/java_data_layer.cpp:36-44; hot
spot measured in src/test/scala/apps/CallbackBenchmarkSpec.scala:1-17).
Caffe's own prefetching pipeline (double-buffered background thread,
reference: caffe/include/caffe/data_layers.hpp:63-117 +
util/blocking_queue.cpp) is bypassed by that path.

Here we implement the double-buffering the reference lost: a daemon thread
runs the host preprocessing and starts the host→HBM ``device_put`` ahead of
time, so the TPU step overlaps with the feed — `device_feed` is the
JavaDataLayer replacement.

Watchdog: Caffe's InternalThread has the same blind spot Spark's stage
supervision has — a prefetch thread that dies silently (or blocks forever
in a read) leaves the solver waiting on an empty BlockingQueue until some
outer timeout kills the whole job as a "straggler".  Here the consumer
never blocks unboundedly: every wait is a short poll that checks feeder
liveness (thread death AND, with ``stall_timeout``, hang), a failed feeder
is restarted once (it re-attaches to the same source iterator — fault
hooks and real pre-pull failures lose no records), and a feed that is
still dead after the restart raises :class:`FeedStalled` AFTER publishing
a ``feed_stalled`` heartbeat — so the supervisor's straggler monitor sees
a live rank whose *feed* is the culprit, not a silent rank to kill."""

from __future__ import annotations

import os
import queue
import sys
import threading
import time
from typing import Any, Callable, Iterator, Mapping

import jax

from ..utils import faults, knobs, telemetry


class FeedStalled(RuntimeError):
    """The prefetch feeder stopped producing (thread death or a stall past
    the timeout) and the one-shot restart did not bring it back.  By the
    time this raises, a ``feed_stalled`` heartbeat has been published (if
    the health plane is on), attributing the stall to the feed."""


class PrefetchIterator:
    """Wrap an iterator; a background thread keeps `depth` items ready.

    ``close()`` stops the producer thread and drops staged items — required
    for endless sources (``RoundFeed.rounds()``), where the producer would
    otherwise stay blocked on the full queue holding device memory for the
    rest of the process (the explicit lifecycle Caffe's InternalThread
    gives its prefetch thread; reference: internal_thread.hpp:29-42).
    Usable as a context manager.

    Watchdog knobs:

    - ``stall_timeout`` — seconds the consumer will wait for a batch
      before declaring the feeder hung (None: no hang deadline, but a
      *dead* feeder thread is still detected by the liveness poll).
      Defaults from ``SPARKNET_FEED_STALL_S`` when unset.  Set it above
      the worst healthy batch latency.
    - ``restarts`` — how many times a dead/hung feeder is restarted
      before :class:`FeedStalled` (default 1: the one-shot restart).
      A restarted feeder re-attaches to the same source iterator under a
      lock, and a superseded feeder never touches the source again — a
      hang between pulls therefore loses no records.
    """

    # _err is the park-then-reraise handoff: the feeder writes it once
    # and then only the consumer reads/raises it; attribute stores are
    # atomic under the GIL, so the watchdog's overwrite needs no lock
    _unguarded_ok = frozenset({"_err"})

    _SENTINEL = object()

    def __init__(self, it: Iterator[Any], depth: int = 2,
                 transform: Callable[[Any], Any] | None = None,
                 stall_timeout: float | None = None, restarts: int = 1):
        self._source = iter(it)
        self._transform = transform
        self._q: queue.Queue[Any] = queue.Queue(maxsize=depth)
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._done = False
        # _gen_lock guards the generation counter and every source pull:
        # only the CURRENT generation's feeder may advance the iterator,
        # so an abandoned (hung) feeder that wakes up late exits without
        # consuming — the restart is lossless
        self._gen_lock = threading.Lock()
        self._generation = 0
        self._restarts_left = int(restarts)
        self._produced = 0    # records pulled from the source (feeder side)
        self._delivered = 0   # batches handed to the consumer
        if stall_timeout is None:
            env = knobs.raw("SPARKNET_FEED_STALL_S", "")
            stall_timeout = float(env) if env else None
        self._stall_timeout = stall_timeout
        # chaos hook: SPARKNET_FAULT=slow_feed:<dur> models a degraded
        # input pipeline by delaying every produced batch (utils.faults)
        self._feed_delay = faults.get_injector().feed_delay()
        self._threads: list[threading.Thread] = []
        self._spawn()

    # -- feeder side ------------------------------------------------------
    def _current(self, gen: int) -> bool:
        return not self._stop.is_set() and gen == self._generation

    def _spawn(self) -> None:
        gen = self._generation
        t = threading.Thread(target=self._run, args=(gen,), daemon=True)
        self._thread = t              # the live feeder (tests poke this)
        self._threads.append(t)
        t.start()

    def _put(self, item: Any, gen: int) -> bool:
        while self._current(gen):
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, gen: int) -> None:
        injector = faults.get_injector()
        try:
            while self._current(gen):
                # chaos hooks fire BEFORE the pull, so neither a die nor
                # a hang ever strands a pulled-but-unqueued record
                ev = injector.feeder_event(self._produced)
                if ev is not None:
                    kind, dur = ev
                    if kind == "die":
                        return      # silent thread death: no sentinel
                    time.sleep(dur)  # hang; loop re-checks the generation
                    continue
                with self._gen_lock:
                    if not self._current(gen):
                        return
                    try:
                        item = next(self._source)
                        self._produced += 1
                    except StopIteration:
                        item = self._SENTINEL
                if item is self._SENTINEL:
                    self._put(item, gen)
                    return
                if self._feed_delay:
                    time.sleep(self._feed_delay)
                out = self._transform(item) if self._transform else item
                if not self._put(out, gen):
                    return
        except BaseException as e:  # surfaced on next()
            self._err = e
            self._put(self._SENTINEL, gen)

    # -- watchdog ---------------------------------------------------------
    def _revive(self, reason: str) -> None:
        """Restart the feeder, or raise FeedStalled once the budget is
        spent.  The generation bump invalidates the old feeder either
        way — it can never race the replacement on the source."""
        with self._gen_lock:
            self._generation += 1
            spent = self._restarts_left <= 0
            if not spent:
                self._restarts_left -= 1
        if spent:
            self._done = True
            self._err = FeedStalled(
                f"prefetch feed stalled after {self._delivered} delivered "
                f"batches: {reason} (restart budget spent)")
            rec = telemetry.get_recorder()
            rec.record("feed_stalled", delivered=self._delivered,
                       reason=reason)
            rec.dump("feed_stalled")
            # attribution on the health plane: the consumer is ALIVE and
            # names the feed as the culprit — the straggler monitor must
            # not read this rank's silence as a hung worker
            from ..parallel import health
            health.maybe_beat(self._delivered, "feed_stalled")
            raise self._err
        telemetry.get_recorder().record(
            "feed_restart", delivered=self._delivered, reason=reason,
            restarts_left=self._restarts_left)
        telemetry.get_registry().counter(
            "feed_restarts_total", "prefetch feeder watchdog restarts"
        ).inc()
        print(f"prefetch: {reason}; restarting feeder "
              f"({self._restarts_left} restarts left)",
              file=sys.stderr, flush=True)
        self._spawn()

    # -- consumer side ----------------------------------------------------
    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self) -> Any:
        if self._done:
            if self._err is not None:
                raise self._err
            raise StopIteration
        deadline = (time.monotonic() + self._stall_timeout
                    if self._stall_timeout is not None else None)
        while True:
            try:
                item = self._q.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    raise StopIteration
                if not self._thread.is_alive() and self._q.empty():
                    if self._err is not None:
                        # feeder errored but its sentinel was lost
                        self._done = True
                        raise self._err
                    self._revive("feeder thread died without finishing "
                                 "its source")
                    deadline = (time.monotonic() + self._stall_timeout
                                if self._stall_timeout is not None else None)
                elif deadline is not None and time.monotonic() > deadline:
                    self._revive(f"no batch within the "
                                 f"{self._stall_timeout:g}s stall timeout")
                    deadline = time.monotonic() + self._stall_timeout
                continue
            if item is self._SENTINEL:
                self._done = True
                if self._err is not None:
                    raise self._err
                raise StopIteration
            self._delivered += 1
            return item

    def close(self) -> None:
        """Stop the producer (every generation of it), release staged
        items, and close the source where it can be closed (a generator:
        ``records_feed`` keeps a batch's reads in flight ahead of the one
        it yielded, and its ``finally`` is what stops those readers —
        now, not whenever the interpreter collects it).  Safe to call
        concurrently with a watchdog restart: the stop event gates both
        the old and the freshly-spawned feeder."""
        self._stop.set()
        self._done = True
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        for t in self._threads:
            t.join(timeout=5.0)
        close_source = getattr(self._source, "close", None)
        # a feeder that outlived its join is still inside the source
        if close_source is not None and not any(
                t.is_alive() for t in self._threads):
            close_source()

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class DeviceFeed:
    """Deep double-buffered host→HBM feed: a :class:`PrefetchIterator`
    keeps ``depth`` HOST batches staged ahead of consumption, and a small
    order-preserving ``device_put`` pool (``putters`` threads) keeps up to
    ``putters + 1`` batches in flight to HBM — so decode/transform
    (upstream), transfer (here), and compute (the consumer's step) all
    overlap.  Device-resident staging stays bounded by the put window,
    independent of the host depth, so a deep host prefetch does not
    multiply HBM pressure.

    ``device_cast`` maps batch keys to a device-side dtype: the host array
    ships in its NARROW dtype (e.g. uint8 pixels — 4× less host→HBM
    traffic than f32) and a one-op cast runs on device after the transfer.

    Iteration semantics match the old transform-in-feeder device_feed:
    items in order, source errors surface after staged items drain, and
    the watchdog (``stall_timeout``/``restarts``) runs in the prefetch
    tier.  ``close()`` (or the context manager) releases both tiers."""

    def __init__(self, batches: Iterator[Mapping[str, Any]],
                 depth: int | None = None, sharding: Any | None = None,
                 stall_timeout: float | None = None, restarts: int = 1,
                 putters: int | None = None,
                 device_cast: Mapping[str, Any] | None = None,
                 stats: Any | None = None):
        from .pipeline import DecodePool, feed_depth
        depth = feed_depth() if depth is None else int(depth)
        # two staging threads by default; HBM staging stays bounded at
        # putters + 1 batches
        if putters is None:
            putters = max(1, knobs.get_int("SPARKNET_FEED_PUTTERS", 2))
        self.stats = stats
        if stats is not None:
            # this stage hands the batches to the consumer: where a
            # FeedStats is shared with the host stage, they count here
            stats.delivered_by("device")
        self._delivered = 0
        self._sharding = sharding
        self._cast = dict(device_cast) if device_cast else None
        self._pf = PrefetchIterator(batches, depth=depth,
                                    stall_timeout=stall_timeout,
                                    restarts=restarts)
        self._pool = DecodePool(self._put_one, workers=putters,
                                window=putters + 1, name="device_put",
                                stats=stats, stage="device_put")
        self._it = self._pool.imap(enumerate(self._pf))

    def _put_one(self, item: tuple[int, Mapping[str, Any]]
                 ) -> dict[str, jax.Array]:
        ordinal, batch = item
        with telemetry.span("feed.device_put", cat="feed", batch=ordinal):
            return self._put(batch)

    def _put(self, batch: Mapping[str, Any]) -> dict[str, jax.Array]:
        out: dict[str, jax.Array] = {}
        for k, v in batch.items():
            if self._sharding is None:
                a = jax.device_put(v)
            else:
                from ..parallel.mesh import stage_local
                a = stage_local(v, self._sharding)
            want = self._cast.get(k) if self._cast else None
            if want is not None and a.dtype != want:
                a = a.astype(want)   # one fused device op, post-transfer
            out[k] = a
        # settle the transfer on the putter thread, not in the consumer's
        # step — staged batches are fully HBM-resident when yielded (and
        # the span and the stats' device_put_s measure the real transfer,
        # not the async dispatch)
        if out:
            jax.block_until_ready(list(out.values()))
        return out

    def __iter__(self) -> "DeviceFeed":
        return self

    def __next__(self) -> dict[str, jax.Array]:
        t0 = time.perf_counter()
        with telemetry.span("feed.wait", cat="feed", batch=self._delivered):
            batch = next(self._it)
        self._delivered += 1
        if self.stats is not None:
            self.stats.note("wait", time.perf_counter() - t0)
            self.stats.count_batch(stage="device")
        return batch

    def close(self) -> None:
        """Stop the prefetch feeder and the put pool, dropping staged
        host batches and releasing staged device memory."""
        self._pf.close()
        self._pool.close()

    def __enter__(self) -> "DeviceFeed":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def device_feed(batches: Iterator[Mapping[str, Any]],
                depth: int | None = None, sharding: Any | None = None,
                stall_timeout: float | None = None,
                restarts: int = 1, putters: int | None = None,
                device_cast: Mapping[str, Any] | None = None,
                stats: Any | None = None) -> DeviceFeed:
    """Prefetch host batches and issue async ``device_put`` ahead of
    consumption — data is in HBM (with the requested sharding) by the time
    the train step asks for it.  ``depth`` defaults to
    ``SPARKNET_FEED_DEPTH`` (4): decode, transform, and transfer hide
    under device steps.  ``stall_timeout``/``restarts`` are the feeder
    watchdog knobs (see :class:`PrefetchIterator`); ``putters``/
    ``device_cast``/``stats`` are the staging knobs (see
    :class:`DeviceFeed`)."""
    return DeviceFeed(batches, depth=depth, sharding=sharding,
                      stall_timeout=stall_timeout, restarts=restarts,
                      putters=putters, device_cast=device_cast, stats=stats)
