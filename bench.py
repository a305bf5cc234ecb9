"""Headline benchmark: CaffeNet (AlexNet-class) training throughput.

Methodology mirrors the reference's published numbers — 20 training
iterations at batch 256, full forward+backward+update, data resident on
device (reference: caffe/docs/performance_hardware.md:19-25, the `caffe
train` 20-iter protocol; best single-GPU baseline 19.2 s ⇒ ≈267 img/s on
K40+cuDNN).  Also reports the eval-pass throughput analog
(performance_hardware.md:20,25) and model-FLOPs MFU.

Prints ONE JSON line on stdout.  Progress and diagnostics go to stderr.
One process; a run that does not find a TPU exits non-zero (BENCH_PLATFORM=cpu
is the CPU smoke's way in), and so does a run in which any leg raises.

Both compute dtypes are measured in one run: f32 (the reference's
numerics) and bf16 mixed precision (the idiomatic TPU mode — params,
losses and BN stats stay f32; measured 28% less device time with the
same convergence, see tests/test_e2e.py bf16 trajectory test).  The
headline number is the faster (bf16), like the reference's headline was
its fastest engine (cuDNN); the f32 block is reported alongside.

Rep blocks are dispatched WITHOUT host sync between them (async JAX
dispatch, the production dispatch pattern); timing spans first dispatch
to final block_until_ready.

Env knobs (for smoke-testing): BENCH_PLATFORM=cpu, BENCH_MODEL=lenet,
BENCH_BATCH, BENCH_ITERS, BENCH_REPS,
BENCH_DTYPE=f32|bf16 (restrict to one compute dtype); feed tier:
BENCH_FEED_BATCH, BENCH_FEED_ITERS, BENCH_FEED_DELAY_S (per-batch host
decode stand-in, see measure_feed); round-overhead tier (outer-loop
host stalls with ckpt+guard+audit on, sync vs async — see
measure_round_overhead): BENCH_ROUND=0 to skip, BENCH_ROUND_N/_TAU/
_LAG/_BATCH/_EVERY; sharded-round tier (dp vs tensor-sharded boundary
bytes + wall with bit-parity assert — see measure_shard_round):
BENCH_SHARD=0 to skip, BENCH_SHARD_N/_TAU/_BATCH; serving tier
(closed-loop latency/QPS through the
inference engine — see measure_serving): BENCH_SERVING=0 to skip,
BENCH_SERVE_MODEL/_CLIENTS/_WINDOW/_SECONDS; vertical fusion:
BENCH_FUSE=off sets SPARKNET_FUSE=off for the run (graph/fusion.py;
captures carry the resulting fuse_plan id).
"""

from __future__ import annotations

import json
import os
import sys
import time

# Per-model K40+cuDNN baselines:
#   caffenet: 19.2 s / 20 iter × 256 train, 60.7 s / 50k eval
#     (caffe/docs/performance_hardware.md:24-25)
#   googlenet: 1123.8 ms fwd+bwd avg / 562.8 ms fwd @ batch 128
#     (caffe/models/bvlc_googlenet/readme.md:24-27)
_BASELINES = {
    "caffenet": (267.0, 50000 / 60.7, 19.2),
    "googlenet": (128 / 1.1238, 128 / 0.5628, None),
}
# models without a published reference row get null baselines — a wrong
# multiplier is worse than none
BASELINE_IMG_S, BASELINE_EVAL_IMG_S, BASELINE_BLOCK_S = _BASELINES.get(
    os.environ.get("BENCH_MODEL", "caffenet"), (None, None, None))

BATCH = int(os.environ.get("BENCH_BATCH", 256))
ITERS = int(os.environ.get("BENCH_ITERS", 20))
REPS = int(os.environ.get("BENCH_REPS", 5))
MODEL = os.environ.get("BENCH_MODEL", "caffenet")
DTYPE = os.environ.get("BENCH_DTYPE")
if DTYPE not in (None, "", "f32", "bf16"):
    print(f"[bench] BENCH_DTYPE={DTYPE!r} invalid (use f32 or bf16)",
          file=sys.stderr)
    sys.exit(2)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main() -> int:
    # BENCH_FUSE=off runs every net this run builds per layer
    # (graph/fusion.py); unset inherits the ambient SPARKNET_FUSE.  Must
    # land before the first Net construction: the plan latches there.
    if os.environ.get("BENCH_FUSE"):
        os.environ["SPARKNET_FUSE"] = os.environ["BENCH_FUSE"]
    import jax

    forced = os.environ.get("BENCH_PLATFORM")
    if forced:
        jax.config.update("jax_platforms", forced)

    t0 = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    _log(f"backend up in {time.perf_counter() - t0:.1f}s: "
         f"{dev.platform}/{dev.device_kind} ×{len(devices)}")
    if not forced and dev.platform != "tpu":
        # a CPU number must not pass for the TPU benchmark
        _log(f"backend is {dev.platform!r}, not 'tpu': nothing measured")
        return 1
    from sparknet_tpu.utils.compile_cache import use_compile_cache
    use_compile_cache()

    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.solvers import Solver
    from sparknet_tpu.utils.profiling import (
        BENCH_SOLVER_PROTOTXT,
        build_bench_model,
        peak_flops,
        scanned_train_block,
        step_cost_flops,
    )

    net, in_shape, classes = build_bench_model(MODEL, BATCH)
    sp = load_solver_prototxt_with_net(BENCH_SOLVER_PROTOTXT, net)
    peak = peak_flops(dev.device_kind)
    scan = os.environ.get("BENCH_SCAN", "1") != "0"
    windows = int(os.environ.get("BENCH_WINDOWS", 3))

    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.normal(size=(1, BATCH) + in_shape).astype(np.float32))
    label = jnp.asarray(rng.integers(0, classes, size=(1, BATCH)).astype(np.float32))
    batch = {"data": data, "label": label}

    def measure(dtype: str) -> dict:
        solver = Solver(sp, seed=0,
                        compute_dtype=jnp.bfloat16 if dtype == "bf16" else None)
        step_rng = jax.random.PRNGKey(0)
        params, state = solver.params, solver.state
        t0 = time.perf_counter()
        flops_per_step = step_cost_flops(solver, batch)

        # The framework's production execution model is a scanned
        # multi-step round in ONE compiled program
        # (DistributedTrainer.train_round) — the bench block runs the same
        # way unless BENCH_SCAN=0 falls back to per-step dispatch.
        if scan:
            block = scanned_train_block(solver, ITERS)

            def run_block(params, state, it0, rng):
                params, state, rng, loss = block(params, state, it0, batch,
                                                 rng)
                return params, state, rng, loss
        else:
            def run_block(params, state, it0, rng):
                loss = None
                for i in range(ITERS):
                    rng, sub = jax.random.split(rng)
                    params, state, loss = solver._step(params, state,
                                                       it0 + i, batch, sub)
                return params, state, rng, loss

        params, state, step_rng, loss = run_block(params, state, 0, step_rng)
        jax.block_until_ready(loss)
        _log(f"[{dtype}] train compile+warmup in "
             f"{time.perf_counter() - t0:.1f}s (scan={scan})")

        # Per window: REPS blocks dispatched back-to-back, one sync at the
        # end (async dispatch — the production dispatch pattern).  Median
        # over windows rejects transient host stalls.
        it = ITERS
        window_dts = []
        for win in range(windows):
            t0 = time.perf_counter()
            for rep in range(REPS):
                params, state, step_rng, loss = run_block(params, state, it,
                                                          step_rng)
                it += ITERS
            jax.block_until_ready(loss)
            window_dts.append(time.perf_counter() - t0)
            _log(f"[{dtype}] train window {win + 1}/{windows}: "
                 f"{BATCH * ITERS * REPS / window_dts[-1]:.1f} img/s "
                 f"({window_dts[-1]:.2f}s / {REPS}x{ITERS} iters)")
        dt = float(np.median(window_dts))
        img_s = BATCH * ITERS * REPS / dt
        block_s = dt / REPS * (20 / ITERS)  # normalized 20-iter protocol

        # eval pass (test-net forward only; performance_hardware.md:20,25)
        # — same windows-median outlier rejection as train
        eval_batch = {"data": data[0], "label": label[0]}
        t0 = time.perf_counter()
        out = solver._test_fwd(params, eval_batch)
        jax.block_until_ready(out)
        _log(f"[{dtype}] eval compile in {time.perf_counter() - t0:.1f}s")
        eval_dts = []
        for _win in range(windows):
            t0 = time.perf_counter()
            for _ in range(ITERS * REPS):
                out = solver._test_fwd(params, eval_batch)
            jax.block_until_ready(out)
            eval_dts.append(time.perf_counter() - t0)
        eval_img_s = BATCH * ITERS * REPS / float(np.median(eval_dts))
        _log(f"[{dtype}] eval: {eval_img_s:.1f} img/s")

        step_s = block_s / 20.0
        mfu = (flops_per_step / step_s / peak
               if (flops_per_step and peak) else None)
        return {
            "images_per_sec": round(img_s, 1),
            "block_20x256_s": round(block_s, 3),
            "eval_images_per_sec": round(eval_img_s, 1),
            "mfu": round(mfu, 4) if mfu is not None else None,
            "flops_per_step": flops_per_step,
            # the train net's vertical-fusion plan id — the ledger
            # fingerprint field keeping fused/unfused bands separate
            "fuse_plan": solver.train_net.fuse_plan_id(),
        }

    def measure_feed(dtype: str) -> dict:
        """Sustained throughput with the feed IN the loop: distinct host
        batches flow host→HBM through the production prefetch path
        (data/prefetch.device_feed → Solver.set_train_data → step), fixing
        the reference's synchronous-callback feed
        (java_data_layer.cpp:36-44) with a measurement, not a design
        claim.  All three legs — feed-alone, compute-alone, in-loop —
        are measured at the SAME batch and the same per-step dispatch
        mode, so overlap% is apples-to-apples.  BENCH_FEED_BATCH picks
        the batch (default BATCH).  BENCH_FEED_DELAY_S (default 0) adds a per-batch host delay to
        the feed leg — a stand-in for decode/augment cost, paid by the
        producer in BOTH the feed-alone leg and the in-loop source
        iterator, so a rig whose raw transfer is near-free (CPU
        platform) can still exercise and assert the non-degenerate
        overlap regime deterministically.

        Pipeline knobs under measurement: the feed leg runs the parallel
        pipeline defaults (SPARKNET_FEED_WORKERS / SPARKNET_FEED_DEPTH),
        ships pixels as uint8 with a post-transfer device cast
        (BENCH_FEED_U8=0 restores f32 staging — 4× the bytes), and
        reports the per-stage breakdown (decode_s / transform_s /
        device_put_s per batch) from data.pipeline.FeedStats so BENCH_r*
        files track WHERE feed time goes across PRs."""
        import itertools

        from sparknet_tpu.data import device_feed
        from sparknet_tpu.data.pipeline import (
            FeedStats, feed_depth, feed_workers,
        )

        fbatch = int(os.environ.get("BENCH_FEED_BATCH", BATCH))
        fdelay = float(os.environ.get("BENCH_FEED_DELAY_S", 0))
        use_u8 = os.environ.get("BENCH_FEED_U8", "1") != "0"
        depth = feed_depth()
        solver = Solver(sp, seed=0,
                        compute_dtype=jnp.bfloat16 if dtype == "bf16" else None)
        m = 4
        # real images leave decode as uint8 — ship them that way (4× less
        # host→HBM traffic than f32) and cast on device, unless pinned off
        if use_u8:
            host = [{"data": rng.integers(0, 256, size=(fbatch,) + in_shape
                                          ).astype(np.uint8),
                     "label": rng.integers(0, classes, size=fbatch
                                           ).astype(np.float32)}
                    for _ in range(m)]
            cast = {"data": jnp.float32}
        else:
            host = [{"data": rng.normal(size=(fbatch,) + in_shape
                                        ).astype(np.float32),
                     "label": rng.integers(0, classes, size=fbatch
                                           ).astype(np.float32)}
                    for _ in range(m)]
            cast = None
        feed_iters = int(os.environ.get("BENCH_FEED_ITERS", 8))

        def stage(hb) -> dict:
            out = {k: jax.device_put(v) for k, v in hb.items()}
            if cast:
                out = {k: (v.astype(cast[k]) if k in cast else v)
                       for k, v in out.items()}
            return out

        # compute-alone: per-step dispatch on device-resident batches —
        # the in-loop measurement's cost with the feed leg removed
        # (includes the per-step dispatch, as the in-loop steps do)
        dev = [stage(hb) for hb in host]
        jax.block_until_ready(dev)
        solver.set_train_data(itertools.cycle(dev))
        solver.step(2)  # warmup/compile at this batch
        t0 = time.perf_counter()
        solver.step(feed_iters)
        compute_s = (time.perf_counter() - t0) / feed_iters
        del dev

        # feed-alone: host work (BENCH_FEED_DELAY_S decode stand-in) +
        # host->HBM transfer (+ the device-side u8→f32 cast) per batch
        # with the transfers dispatched back-to-back (pipelined, like
        # the staging pool issues them) — a per-batch synchronous
        # measure would overstate the baseline and inflate the overlap
        t0 = time.perf_counter()
        staged = []
        for hb in host:
            if fdelay:
                time.sleep(fdelay)
            staged.append(stage(hb))
        jax.block_until_ready(staged)
        feed_alone = (time.perf_counter() - t0) / m
        del staged

        stats = FeedStats()

        def source():
            # the producer pays the same per-batch host delay as the
            # feed-alone leg; it books as the pipeline's decode stage
            for hb in itertools.islice(itertools.cycle(host),
                                       feed_iters + 4):
                if fdelay:
                    with stats.timed("decode"):
                        time.sleep(fdelay)
                yield hb

        solver2 = Solver(sp, seed=0,
                         compute_dtype=jnp.bfloat16 if dtype == "bf16"
                         else None)
        feed = device_feed(source(), depth=depth, device_cast=cast,
                           stats=stats)
        solver2.set_train_data(feed)
        solver2.step(2)  # warmup/compile
        t0 = time.perf_counter()
        solver2.step(feed_iters)
        total = (time.perf_counter() - t0) / feed_iters
        feed.close()
        # overlap fraction: 1.0 when total == max(feed, compute) (perfect
        # pipeline), 0.0 when total == feed + compute (fully serial)
        denom = min(feed_alone, compute_s) or 1.0
        overlap = (feed_alone + compute_s - total) / denom * 100.0
        bound = "feed" if feed_alone > compute_s else "compute"
        stages = stats.per_batch()
        out = {
            "batch": fbatch,
            "images_per_sec": round(fbatch / total, 1),
            "step_s": round(total, 4),
            "feed_alone_s_per_batch": round(feed_alone, 4),
            "compute_s_per_step": round(compute_s, 4),
            "bound": bound,
            "feed_compute_ratio": round(feed_alone / max(compute_s, 1e-9), 2),
            "overlap_pct": round(max(0.0, min(100.0, overlap)), 1),
            # per-stage breakdown (s/batch, averaged over the whole leg
            # incl. warmup) + the pipeline config that produced it
            "read_s": stages["read_s"],
            "decode_s": stages["decode_s"],
            "transform_s": stages["transform_s"],
            "device_put_s": stages["device_put_s"],
            "workers": feed_workers(),
            "depth": depth,
            "staged_dtype": "uint8" if use_u8 else "float32",
        }
        _log(f"[{dtype}] feed-in-loop @ b{fbatch}: "
             f"{out['images_per_sec']} img/s (feed-alone {feed_alone:.3f}s, "
             f"compute {compute_s:.4f}s, {bound}-bound, "
             f"overlap {out['overlap_pct']}%; stages decode "
             f"{stages['decode_s']:.4f}s / transform "
             f"{stages['transform_s']:.4f}s / put "
             f"{stages['device_put_s']:.4f}s per batch, "
             f"staged {out['staged_dtype']}, workers {out['workers']}, "
             f"depth {depth})")
        return out

    def measure_feed_records() -> dict:
        """The decode-once leg: sustained host feed throughput from
        pre-decoded record shards (data/records.py, warm tiered
        ShardCache) vs the per-epoch decode path (encoded-JPEG LMDB
        datums through the serial ``workers=0`` reference decode) — the
        convert-once trade the reference's workers re-pay every epoch
        (ImageNetLoader re-untars and re-decodes S3 tars per pass,
        ImageNetLoader.scala:56-86).  Both legs run the same transform
        and batch size; the serial leg pays JPEG decode per image per
        epoch, the records leg pays it once at convert (reported as
        ``convert_s``) and then streams crop-ready uint8 blocks.
        Knobs: BENCH_RECORDS_N/_EDGE/_BATCH/_EPOCHS;
        BENCH_FEED_RECORDS=0 skips the leg."""
        import io as _io
        import tempfile

        from PIL import Image

        from sparknet_tpu.data.db import (
            array_to_datum, datum_to_array, db_feed, open_db,
        )
        from sparknet_tpu.data.lmdb_io import write_lmdb
        from sparknet_tpu.data.pipeline import FeedStats, ShardCache
        from sparknet_tpu.data.records import convert_to_shards, records_feed
        from sparknet_tpu.models.dsl import layer
        from sparknet_tpu.proto.caffe_pb import Phase

        n = int(os.environ.get("BENCH_RECORDS_N", 96))
        edge = int(os.environ.get("BENCH_RECORDS_EDGE", 64))
        rbatch = int(os.environ.get("BENCH_RECORDS_BATCH", 32))
        epochs = int(os.environ.get("BENCH_RECORDS_EPOCHS", 3))
        rrng = np.random.default_rng(0)

        def mk_lp(source: str, backend: str):
            return layer("d", "Data", [], ["data", "label"],
                         data_param={"source": source, "batch_size": rbatch,
                                     "backend": backend},
                         transform_param={"scale": 1.0 / 255})

        n_batches = max(1, epochs * n // rbatch)
        with tempfile.TemporaryDirectory() as tmp:
            db_path = os.path.join(tmp, "lmdb")
            pairs = []
            for i in range(n):
                img = rrng.integers(0, 256,
                                    size=(edge, edge, 3)).astype(np.uint8)
                buf = _io.BytesIO()
                Image.fromarray(img).save(buf, format="JPEG", quality=90)
                pairs.append((b"%08d" % i,
                              array_to_datum(None, int(rrng.integers(10)),
                                             encoded=buf.getvalue())))
            write_lmdb(db_path, pairs)

            # serial decode reference: JPEG decode per image, per epoch
            stats_s = FeedStats()
            feedg = db_feed(mk_lp(db_path, "LMDB"), Phase.TRAIN, seed=0,
                            workers=0, stats=stats_s)
            for _ in range(2):
                next(feedg)   # warm the LMDB page cache / decoder
            t0 = time.perf_counter()
            for _ in range(n_batches):
                next(feedg)
            serial_s = time.perf_counter() - t0
            feedg.close()

            # convert once: the per-record decode paid here, never again
            shards_dir = os.path.join(tmp, "shards")
            reader = open_db(db_path, "LMDB")

            def decoded():
                for key, val in reader.items():
                    img, label = datum_to_array(val, key=key,
                                                source=db_path)
                    yield (np.clip(np.round(img), 0, 255).astype(np.uint8),
                           label)

            t0 = time.perf_counter()
            conv = convert_to_shards(decoded(), shards_dir)
            convert_s = time.perf_counter() - t0

            # warm-records leg: epoch 1 fills the cache, then measure
            cache = ShardCache(max_shards=max(4, len(conv["shards"])))
            stats_r = FeedStats()
            rfeed = records_feed(mk_lp(shards_dir, "RECORDS"), Phase.TRAIN,
                                 seed=0, stats=stats_r, cache=cache)
            for _ in range(max(1, n // rbatch)):
                next(rfeed)
            t0 = time.perf_counter()
            for _ in range(n_batches):
                next(rfeed)
            records_s = time.perf_counter() - t0
            rfeed.close()

        images = n_batches * rbatch
        out = {
            "feed_source": "records",
            "records": n,
            "edge": edge,
            "batch": rbatch,
            "epochs": epochs,
            "images_per_sec": round(images / records_s, 1),
            "serial_img_s": round(images / serial_s, 1),
            "speedup_x": round(serial_s / records_s, 2),
            "convert_s": round(convert_s, 3),
            "read_s": stats_r.per_batch()["read_s"],
            "serial_decode_s": stats_s.per_batch()["decode_s"],
            "cache": cache.tier_counts(),
        }
        _log(f"feed_records: warm {out['images_per_sec']} img/s vs serial "
             f"decode {out['serial_img_s']} img/s "
             f"({out['speedup_x']}x, convert paid once: {convert_s:.2f}s)")
        return out

    def measure_round_overhead() -> dict:
        """The zero-stall-outer-loop leg: training throughput with every
        safety feature enabled (round checkpointing + numerics guard +
        cross-replica audit) vs bare rounds, for the SYNCHRONOUS outer
        loop (every round blocks on the loss fetch, the finite-check,
        the audit fingerprint, and the checkpoint write) vs the ASYNC
        one (AsyncCheckpointWriter + TrainerConfig.harvest_lag round
        pipelining).  The compiled round is identical across legs — the
        difference is pure host bookkeeping, which is exactly what this
        leg isolates.  Per-component stall seconds come straight from
        ``DistributedTrainer.stall_s`` (loss_fetch / finite_check /
        audit_fetch / checkpoint), so BENCH_r* files record WHERE the
        between-round time goes and by how much the async loop shrinks
        it.  Runs f32 (DistributedTrainer is the f32 outer-loop path);
        the overhead ratios are dtype-independent.  Knobs:
        BENCH_ROUND_N (timed rounds), BENCH_ROUND_TAU, BENCH_ROUND_LAG,
        BENCH_ROUND_BATCH, BENCH_ROUND_EVERY (checkpoint cadence);
        BENCH_ROUND=0 skips the leg."""
        import tempfile

        from sparknet_tpu.parallel import (
            DistributedTrainer, TrainerConfig, make_mesh,
        )

        rounds_n = int(os.environ.get("BENCH_ROUND_N", 4))
        tau = int(os.environ.get("BENCH_ROUND_TAU", 4))
        lag = int(os.environ.get("BENCH_ROUND_LAG", 2))
        rbatch = int(os.environ.get("BENCH_ROUND_BATCH", BATCH))
        every = int(os.environ.get("BENCH_ROUND_EVERY", 2))
        mesh = make_mesh()
        feed = {"data": rng.normal(size=(tau, rbatch) + in_shape
                                   ).astype(np.float32),
                "label": rng.integers(0, classes, size=(tau, rbatch)
                                      ).astype(np.float32)}
        # retention must cover the harvest lag (TrainerConfig validates)
        keep = max(3, (lag + 1 + every - 1 + every - 1) // every + 1)

        def leg(name: str, async_on: bool, instrumented: bool) -> dict:
            from sparknet_tpu.utils import knobs
            saved = knobs.raw("SPARKNET_ASYNC_CKPT")
            os.environ["SPARKNET_ASYNC_CKPT"] = "1" if async_on else "0"
            try:
                with tempfile.TemporaryDirectory() as ck:
                    cfg = TrainerConfig(
                        strategy="local_sgd", tau=tau,
                        harvest_lag=lag if async_on else 0,
                        checkpoint_dir=ck if instrumented else None,
                        checkpoint_every=every, checkpoint_keep=keep,
                        guard_numerics=instrumented,
                        audit_every=1 if instrumented else 0)
                    tr = DistributedTrainer(sp, mesh, cfg, seed=0)
                    tr.train_round(feed)   # compile + warmup
                    tr.drain()
                    tr.stall_s = {k: 0.0 for k in tr.stall_s}
                    t0 = time.perf_counter()
                    for _ in range(rounds_n):
                        tr.train_round(feed)
                    tr.drain()
                    dt = time.perf_counter() - t0
            finally:
                if saved is None:
                    os.environ.pop("SPARKNET_ASYNC_CKPT", None)
                else:
                    os.environ["SPARKNET_ASYNC_CKPT"] = saved
            stalls = {k: round(v / rounds_n, 4)
                      for k, v in tr.stall_s.items()}
            out = {"img_s": round(rbatch * tau * rounds_n / dt, 1),
                   "round_s": round(dt / rounds_n, 4),
                   "stall_s_per_round": stalls,
                   "stall_total_s_per_round": round(sum(stalls.values()),
                                                    4)}
            _log(f"round_overhead[{name}]: {out['img_s']} img/s "
                 f"({out['round_s']}s/round, stalls {stalls})")
            return out

        bare = leg("bare", async_on=True, instrumented=False)
        sync = leg("sync", async_on=False, instrumented=True)
        async_ = leg("async", async_on=True, instrumented=True)
        return {
            "batch": rbatch, "tau": tau, "rounds": rounds_n,
            "harvest_lag": lag, "checkpoint_every": every,
            "workers": mesh.shape["data"], "dtype": "f32",
            "bare": bare, "sync": sync, "async": async_,
            "sync_overhead_pct": round(
                (sync["round_s"] - bare["round_s"])
                / bare["round_s"] * 100, 1),
            "async_overhead_pct": round(
                (async_["round_s"] - bare["round_s"])
                / bare["round_s"] * 100, 1),
            "stall_reduction_x": round(
                sync["stall_total_s_per_round"]
                / max(async_["stall_total_s_per_round"], 1e-6), 1),
        }

    def measure_shard_round() -> dict:
        """The hybrid-sharding leg: τ-boundary broadcast bytes and round
        wall for the replicated round (TrainerConfig.shard="off") vs the
        tensor-sharded one ("auto" — parallel/partition.py's rule table
        shards FC/inner-product weights across chips).  Both legs run the
        same seed and feed with codec none, so the sharded round is
        bit-identical to dp by the reduce-scatter/pmean identity — and
        the leg ASSERTS it (``parity_ok``) instead of trusting it.
        Bytes are analytic layout accounting
        (``partition.boundary_bytes_per_chip``), not a wire sniff, so
        the shrink claim is reproducible on any backend.  Knobs:
        BENCH_SHARD_N (timed rounds), BENCH_SHARD_TAU,
        BENCH_SHARD_BATCH; BENCH_SHARD=0 skips the leg."""
        from sparknet_tpu.parallel import (
            DistributedTrainer, TrainerConfig, make_mesh, partition,
        )

        rounds_n = int(os.environ.get("BENCH_SHARD_N", 4))
        tau = int(os.environ.get("BENCH_SHARD_TAU", 4))
        rbatch = int(os.environ.get("BENCH_SHARD_BATCH", BATCH))
        mesh = make_mesh()
        workers = int(mesh.shape["data"])
        if workers < 2:
            return {"skipped": f"{workers} worker(s): nothing to shard"}
        feed = {"data": rng.normal(size=(tau, rbatch) + in_shape
                                   ).astype(np.float32),
                "label": rng.integers(0, classes, size=(tau, rbatch)
                                      ).astype(np.float32)}

        def leg(shard: str) -> tuple:
            cfg = TrainerConfig(strategy="local_sgd", tau=tau,
                                shard=shard)
            tr = DistributedTrainer(sp, mesh, cfg, seed=0)
            losses = [tr.train_round(feed)]    # compile + warmup
            t0 = time.perf_counter()
            for _ in range(rounds_n):
                losses.append(tr.train_round(feed))
            dt = time.perf_counter() - t0
            out = {"img_s": round(rbatch * tau * rounds_n / dt, 1),
                   "round_s": round(dt / rounds_n, 4)}
            return tr, out, losses

        dp_tr, dp, dp_losses = leg("off")
        sh_tr, sh, sh_losses = leg("auto")
        plan = sh_tr.shard_plan
        if plan is None:
            return {"skipped": "no shardable leaves for this model"}
        dp["boundary_bytes_per_chip"] = partition.boundary_bytes_per_chip(
            dp_tr.params, None)
        sh["boundary_bytes_per_chip"] = partition.boundary_bytes_per_chip(
            sh_tr.params, plan)
        parity_ok = all(
            np.float32(a).tobytes() == np.float32(b).tobytes()
            for a, b in zip(dp_losses, sh_losses))
        shrink = round(dp["boundary_bytes_per_chip"]
                       / max(sh["boundary_bytes_per_chip"], 1), 2)
        _log(f"shard_round[{sh_tr.shard_plan_id}]: dp {dp['round_s']}s "
             f"/ {dp['boundary_bytes_per_chip']} B vs sharded "
             f"{sh['round_s']}s / {sh['boundary_bytes_per_chip']} B "
             f"per chip ({shrink}x, parity {'OK' if parity_ok else 'FAILED'})")
        return {"batch": rbatch, "tau": tau, "rounds": rounds_n,
                "workers": workers, "dtype": "f32",
                "plan": sh_tr.shard_plan_id, "dp": dp, "sharded": sh,
                "bytes_shrink_x": shrink, "parity_ok": parity_ok}

    def measure_serving() -> dict:
        """The serving-plane leg: closed-loop latency/QPS through the
        dynamic micro-batching engine (parallel/serving.py) — batch=1
        baseline vs dynamic saturation, a paced sweep with the
        bit-identity audit, and a 2x-overload point showing typed
        rejections with bounded p99.  Runs tools/serveload.run_report
        in-process so the BENCH JSON and the committed BENCH_serving_*
        artifacts share one methodology.  Knobs: BENCH_SERVE_MODEL
        (default BENCH_MODEL), BENCH_SERVE_CLIENTS/_WINDOW/_SECONDS;
        BENCH_SERVING=0 skips the leg."""
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tools"))
        import serveload
        rep = serveload.run_report(
            model=os.environ.get("BENCH_SERVE_MODEL", MODEL),
            clients=int(os.environ.get("BENCH_SERVE_CLIENTS", 8)),
            window=int(os.environ.get("BENCH_SERVE_WINDOW", 16)),
            seconds=float(os.environ.get("BENCH_SERVE_SECONDS", 1.5)),
            fractions=(0.5, 1.0))
        rep.pop("engine_stats", None)   # the BENCH line stays one screen
        _log(f"serving[{rep['model']}]: dynamic "
             f"{rep['saturation']['achieved_qps']} qps vs batch1 "
             f"{rep['batch1']['achieved_qps']} qps "
             f"({rep['verdicts']['batching_speedup_x']}x), overload p99 "
             f"{rep['overload']['p99_ms']} ms with "
             f"{rep['verdicts']['overload_rejected']} rejections, "
             f"mismatches {rep['verdicts']['exact_mismatches']}")
        return rep

    dtypes = [DTYPE] if DTYPE in ("f32", "bf16") else ["bf16", "f32"]
    runs = {d: measure(d) for d in dtypes}
    best = max(dtypes, key=lambda d: runs[d]["images_per_sec"])
    b = runs[best]
    # a leg that fails fails the run: no leg's exception is turned into
    # a field of a result that exits 0
    def leg(switch: str, fn, *args):
        return fn(*args) if os.environ.get(switch, "1") != "0" else None

    feed = leg("BENCH_FEED", measure_feed, best)
    feed_records = leg("BENCH_FEED_RECORDS", measure_feed_records)
    round_overhead = leg("BENCH_ROUND", measure_round_overhead)
    shard_round = leg("BENCH_SHARD", measure_shard_round)
    serving = leg("BENCH_SERVING", measure_serving)
    # provenance: git sha + config fingerprint + the telemetry plane's
    # correlation IDs, so every capture joins the perf ledger
    # (tools/perfwatch.py) without filename archaeology
    from sparknet_tpu.utils import perfledger
    fp = perfledger.fingerprint(
        model=MODEL, dtype=best, batch=BATCH, world=1,
        device=f"{dev.platform}/{dev.device_kind}", backend=dev.platform,
        fuse_plan=b.get("fuse_plan"),
        feed_source="records" if feed_records else "lmdb")
    result = {
        "metric": f"{MODEL}_train_images_per_sec",
        "value": b["images_per_sec"],
        "unit": "img/s",
        "vs_baseline": round(b["images_per_sec"] / BASELINE_IMG_S, 2)
        if BASELINE_IMG_S else None,
        "block_20x256_s": b["block_20x256_s"],
        "baseline_block_s": BASELINE_BLOCK_S,
        "eval_images_per_sec": b["eval_images_per_sec"],
        "eval_vs_baseline": round(b["eval_images_per_sec"] / BASELINE_EVAL_IMG_S, 2)
        if BASELINE_EVAL_IMG_S else None,
        "mfu": b["mfu"],
        "flops_per_step": b["flops_per_step"],
        "device": f"{dev.platform}/{dev.device_kind}",
        "dtype": best,
        "dtype_note": ("mixed precision; f32 master params/losses/BN stats"
                       if best == "bf16" else None),
        "fuse_plan": b.get("fuse_plan"),
        "batch": BATCH,
        "iters_per_block": ITERS,
        "reps": REPS,
        "windows": windows,
        "by_dtype": runs,
        "feed_in_loop": feed,
        "feed_records": feed_records,
        "round_overhead": round_overhead,
        "shard_round": shard_round,
        "serving": serving,
        "provenance": perfledger.provenance(fp),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
