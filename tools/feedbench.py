#!/usr/bin/env python
"""Serial-vs-parallel feed microbench + parity gate (the CI teeth of the
parallel input pipeline).

Builds a small synthetic LMDB, then streams the SAME batches through
``db_feed`` twice — once on the serial reference path (``workers=0``) and
once through the decode pool — and verifies the parallel stream is
bit-identical: same pixels, same labels, and (with ``--corrupt``) the same
quarantine accounting (same records quarantined, same replacement pulls).
Any divergence is a correctness regression in the pipeline's ordering
guarantees and fails the run (exit 1).

Wall time is bounded (default ~2 s): the serial leg runs until its time
budget, the parallel leg replays the same batch count — parity needs equal
streams, not equal durations.  Prints ONE JSON verdict line on stdout.

``--records-leg`` extends the parity triangle to pre-decoded record
shards: the LMDB is converted once (``tools/convert.py`` path), then the
SAME batches are replayed from local shards through the parallel
ranged-read pool AND from a ``VerifyingStore`` through a tiered
``ShardCache`` (RAM + disk spill) — all three streams must be
pixel/label/quarantine bit-identical to the serial LMDB reference,
including under ``--corrupt`` fault injection (admissions attributed to
shard sources), plus a planted on-disk corrupt record block that must
quarantine with source attribution, and cold/warm cache-tier counters
must show the spill tier working.

Usage:
  python tools/feedbench.py [--seconds 2] [--batch 32] [--records 256]
                            [--workers N] [--corrupt] [--records-leg]
                            [--out FILE]
Wired into tools/run_tier1.sh behind SPARKNET_FEEDBENCH=1 (or --feedbench);
the records triangle behind SPARKNET_RECORDBENCH=1 (or --recordbench).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_db(path: str, n: int, shape=(3, 16, 16), seed: int = 0) -> None:
    from sparknet_tpu.data.db import array_to_datum
    from sparknet_tpu.data.lmdb_io import write_lmdb
    rng = np.random.default_rng(seed)
    imgs = rng.integers(0, 256, size=(n,) + shape).astype(np.uint8)
    labels = rng.integers(0, 10, size=n)
    write_lmdb(path, [(b"%08d" % i, array_to_datum(imgs[i], int(labels[i])))
                      for i in range(n)])


def run_leg(path: str, batch: int, workers: int, n_batches: int | None,
            seconds: float, seed: int, records: int = 0) -> dict:
    """Stream batches off one fresh db_feed; returns arrays + quarantine
    report + throughput.  Bounded by ``n_batches`` when given (the parity
    replay), else by the time budget."""
    from sparknet_tpu.data.db import db_feed
    from sparknet_tpu.data.integrity import Quarantine, QuarantinePolicy
    from sparknet_tpu.data.pipeline import FeedStats
    from sparknet_tpu.models.dsl import layer
    from sparknet_tpu.proto.caffe_pb import Phase
    from sparknet_tpu.utils import faults

    faults.reset_injector()   # each leg re-arms one-shot fault state
    lp = layer("d", "Data", [], ["data", "label"],
               data_param={"source": path, "batch_size": batch,
                           "backend": "LMDB"},
               transform_param={"scale": 0.5, "mean_value": [16.0]})
    quarantine = Quarantine(QuarantinePolicy(max_fraction=0.5),
                            epoch_size=records or None, source=path)
    stats = FeedStats()
    feed = db_feed(lp, Phase.TRAIN, seed=seed, quarantine=quarantine,
                   workers=workers, stats=stats)
    batches = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while (len(batches) < n_batches if n_batches is not None
           else time.perf_counter() < deadline):
        b = next(feed)
        # copy: db_feed may rotate/reuse buffers; the parity compare
        # holds every batch at once
        batches.append({k: np.array(v) for k, v in b.items()})
    dt = time.perf_counter() - t0
    feed.close()
    images = sum(b["data"].shape[0] for b in batches)
    return {"batches": batches, "quarantine": quarantine.report(),
            "stats": stats.snapshot(), "seconds": round(dt, 3),
            "img_s": round(images / dt, 1) if dt > 0 else 0.0}


def compare(serial: dict, parallel: dict, cross_source: bool = False,
            label: str = "parallel") -> list[str]:
    errs = []
    a, b = serial["batches"], parallel["batches"]
    if len(a) != len(b):
        return [f"batch count mismatch: serial {len(a)} vs {label} "
                f"{len(b)}"]
    for i, (x, y) in enumerate(zip(a, b)):
        for k in x:
            if not np.array_equal(x[k], y[k]):
                errs.append(f"batch {i} key {k!r} differs vs {label} "
                            f"(max abs diff "
                            f"{np.abs(x[k] - y[k]).max():.3g})")
    qa, qb = dict(serial["quarantine"]), dict(parallel["quarantine"])
    for q in (qa, qb):   # examples carry reprs; counts are the contract
        q.pop("examples", None)
        if cross_source:
            # LMDB and records legs attribute to different source names
            # by construction; admission COUNTS are the cross-source
            # contract (positions are proven by the pixel parity above)
            q.pop("by_source", None)
    if qa != qb:
        errs.append(f"quarantine accounting differs: serial {qa} vs "
                    f"{label} {qb}")
    return errs


def run_records_leg(shards: str, batch: int, workers: int, n_batches: int,
                    seed: int, records: int = 0, verify: bool = False,
                    cache=None) -> dict:
    """Replay ``n_batches`` from a record-shard source through
    ``records_feed`` — same transform/quarantine configuration as
    :func:`run_leg`, so the streams must be bit-identical."""
    from sparknet_tpu.data.integrity import Quarantine, QuarantinePolicy
    from sparknet_tpu.data.pipeline import FeedStats
    from sparknet_tpu.data.records import records_feed
    from sparknet_tpu.models.dsl import layer
    from sparknet_tpu.proto.caffe_pb import Phase
    from sparknet_tpu.utils import faults

    faults.reset_injector()
    lp = layer("d", "Data", [], ["data", "label"],
               data_param={"source": shards, "batch_size": batch,
                           "backend": "RECORDS"},
               transform_param={"scale": 0.5, "mean_value": [16.0]})
    quarantine = Quarantine(QuarantinePolicy(max_fraction=0.5),
                            epoch_size=records or None, source=shards)
    stats = FeedStats()
    feed = records_feed(lp, Phase.TRAIN, seed=seed, quarantine=quarantine,
                        workers=workers, stats=stats, verify=verify,
                        cache=cache)
    batches = []
    t0 = time.perf_counter()
    for _ in range(n_batches):
        b = next(feed)
        batches.append({k: np.array(v) for k, v in b.items()})
    dt = time.perf_counter() - t0
    feed.close()
    images = sum(b["data"].shape[0] for b in batches)
    return {"batches": batches, "quarantine": quarantine.report(),
            "stats": stats.snapshot(), "seconds": round(dt, 3),
            "img_s": round(images / dt, 1) if dt > 0 else 0.0}


def convert_db_to_shards(db: str, out_dir: str, shard_bytes: int) -> dict:
    """LMDB → shards in cursor order (the tools/convert.py lmdb path)."""
    from sparknet_tpu.data.records import convert_to_shards
    import tools.convert as convert
    return convert_to_shards(convert.iter_db(db, "LMDB"), out_dir,
                             shard_bytes=shard_bytes)


def check_planted_corruption(shards_dir: str, tmp: str, batch: int,
                             records: int, seed: int) -> list[str]:
    """Flip one byte inside a record block of a COPY of the shard set;
    the records feed must quarantine exactly that record, attributed to
    the shard source — never yield wrong pixels, never crash."""
    import shutil
    from sparknet_tpu.data.records import RecordShard
    from sparknet_tpu.utils import faults

    faults.reset_injector()
    planted = os.path.join(tmp, "planted")
    shutil.copytree(shards_dir, planted)
    name = sorted(n for n in os.listdir(planted) if n.endswith(".rec"))[0]
    victim = os.path.join(planted, name)
    shard = RecordShard.open(victim)
    pos = shard.offset(0) + shard.stride // 2
    with open(victim, "r+b") as f:     # flip a byte mid-block of record 0
        f.seek(pos)
        orig = f.read(1)[0]
        f.seek(pos)
        f.write(bytes([orig ^ 0xFF]))
    leg = run_records_leg(planted, batch, 2,
                          max(1, records // batch), seed, records=records)
    rep = leg["quarantine"]
    errs = []
    if rep["total_bad"] < 1:
        errs.append("planted corrupt record block was NOT quarantined")
    if not any(name in src for src in rep.get("by_source", {})):
        errs.append(f"planted corruption not attributed to shard "
                    f"{name!r}: by_source={rep.get('by_source')}")
    return errs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="wall budget for the serial leg (default 2)")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--records", type=int, default=256)
    ap.add_argument("--workers", type=int, default=None,
                    help="parallel-leg pool width (default "
                         "SPARKNET_FEED_WORKERS, min 2 so the pool is "
                         "actually exercised)")
    ap.add_argument("--corrupt", action="store_true",
                    help="run with corrupt_record:0.1 fault injection — "
                         "parity must hold through the quarantine path")
    ap.add_argument("--records-leg", action="store_true",
                    help="also convert to record shards and replay through "
                         "records_feed (local, object-store+tiered-cache, "
                         "warm-cache) — all bit-identical to the serial leg")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)

    # a host-side parity gate: whatever touches JAX here stays on the CPU
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.corrupt:
        os.environ["SPARKNET_FAULT"] = "corrupt_record:0.1"
        os.environ["SPARKNET_FAULT_ATTEMPT"] = "0"

    from sparknet_tpu.data.pipeline import feed_workers
    workers = args.workers if args.workers is not None \
        else max(2, feed_workers())

    rec: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        db = os.path.join(tmp, "lmdb")
        build_db(db, args.records, seed=args.seed)
        serial = run_leg(db, args.batch, 0, None, args.seconds / 2,
                         args.seed, records=args.records)
        parallel = run_leg(db, args.batch, workers,
                           len(serial["batches"]), args.seconds, args.seed,
                           records=args.records)
        errs = compare(serial, parallel)
        if args.records_leg:
            from sparknet_tpu.data.pipeline import FeedStats, ShardCache
            shards_dir = os.path.join(tmp, "shards")
            n_batches = len(serial["batches"])
            stride = 3 * 16 * 16 + 8   # build_db geometry + i64 label
            conv = convert_db_to_shards(
                db, shards_dir,
                shard_bytes=max(stride, args.records * stride // 4))
            n_shards = len(conv["shards"])
            per_shard = -(-args.records // max(1, n_shards))
            rec_local = run_records_leg(shards_dir, args.batch, workers,
                                        n_batches, args.seed,
                                        records=args.records)
            errs += compare(serial, rec_local, cross_source=True,
                            label="records")
            cache_stats = FeedStats()
            cache = ShardCache(max_shards=2, stats=cache_stats,
                               spill_dir=os.path.join(tmp, "spill"),
                               max_spill=16)
            rec_store = run_records_leg(shards_dir, args.batch, workers,
                                        n_batches, args.seed,
                                        records=args.records,
                                        verify=True, cache=cache)
            errs += compare(serial, rec_store, cross_source=True,
                            label="records+store")
            cold = cache_stats.snapshot()
            rec_warm = run_records_leg(shards_dir, args.batch, workers,
                                       n_batches, args.seed,
                                       records=args.records,
                                       verify=True, cache=cache)
            errs += compare(serial, rec_warm, cross_source=True,
                            label="records+warm-cache")
            warm = cache_stats.snapshot()
            if cold["cache_misses"] < 1:
                errs.append("cold records replay never missed the cache "
                            "(cache not exercised)")
            if not (warm["cache_hits"] + warm["cache_disk_hits"]
                    > cold["cache_hits"] + cold["cache_disk_hits"]):
                errs.append("warm records replay produced no new cache "
                            "hits")
            # The disk tier only fires once the cold pass streamed past
            # the 2-shard RAM tier (evictions spilled, warm pass rereads)
            if (n_shards > 2 and n_batches * args.batch > 2 * per_shard
                    and warm["cache_disk_hits"] < 1):
                errs.append(
                    f"disk spill tier never hit (shards={n_shards}, "
                    f"tiers={cache.tier_counts()}, warm={warm})")
            if args.corrupt:
                rep = rec_local["quarantine"]
                if rep["total_bad"] and not any(
                        shards_dir in s for s in rep.get("by_source", {})):
                    errs.append(
                        "injected corruption not attributed to the shard "
                        f"source: by_source={rep.get('by_source')}")
            else:
                errs += check_planted_corruption(shards_dir, tmp,
                                                 args.batch, args.records,
                                                 args.seed)
            rec = {
                "records_leg": True,
                "shards": n_shards,
                "records_img_s": rec_local["img_s"],
                "records_store_img_s": rec_store["img_s"],
                "records_warm_img_s": rec_warm["img_s"],
                "records_speedup": round(
                    rec_local["img_s"] / serial["img_s"], 2)
                if serial["img_s"] else None,
                "records_read_s": rec_local["stats"].get("read_s"),
                "cache_cold": {k: cold[k] for k in
                               ("cache_hits", "cache_disk_hits",
                                "cache_misses")},
                "cache_warm": {k: warm[k] for k in
                               ("cache_hits", "cache_disk_hits",
                                "cache_misses")},
            }
    verdict = {
        "metric": "feed_parity",
        "backend": os.environ["JAX_PLATFORMS"],
        "ok": not errs,
        "errors": errs,
        "batches": len(serial["batches"]),
        "batch": args.batch,
        "workers": workers,
        "corrupt": bool(args.corrupt),
        "serial_img_s": serial["img_s"],
        "parallel_img_s": parallel["img_s"],
        "speedup": round(parallel["img_s"] / serial["img_s"], 2)
        if serial["img_s"] else None,
        "quarantined": serial["quarantine"]["total_bad"],
        **rec,
    }
    line = json.dumps(verdict)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if errs:
        for e in errs:
            print(f"feedbench: PARITY FAIL: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
