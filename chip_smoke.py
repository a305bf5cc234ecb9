"""The quickest proof that the system still starts on the chip.

One process, no arguments.  It drives what a user drives, at CaffeNet's
published widths (3x227x227 in, 1000 classes, every layer as
``sparknet_tpu.models.caffenet`` builds it) with random weights from a
seed, on every chip of the host:

  kernels   the Pallas LRN epilogue against ``relu_lrn_reference`` at
            CaffeNet's two LRN shapes
  rounds    ``apps.imagenet_app.main`` (synthetic images -> mean image ->
            tau-step local_sgd rounds with on-device preprocessing -> eval
            -> snapshot), then ``DistributedTrainer`` rounds with
            checkpoint + guard + audit on, a flush, and a resume into a
            fresh trainer
  steps     ``Solver`` in bf16 at batch 256: a scanned block of steps, one
            test forward, then steps fed from uint8 host batches through
            ``device_feed``
  requests  ``ModelHouse`` + ``InferenceEngine``: concurrent submits, every
            row equal to its solo reference at the shape it was padded to

It refuses to run on anything but a ``tpu`` backend, exits non-zero with
the reason on any miss, reports seconds per leg with compile time apart
and no throughput, and prints as its last line of standard output
``{"ok": true, "device": {...}}`` with the device as JAX reports it.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import itertools
import json
import os
import re
import shutil
import sys
import tempfile
import time

# Depth of the run, not width of the model: batches, steps and rounds are
# small so the whole script compiles and runs well inside its time limit.
ROUNDS_BATCH = 8        # per worker; the synthetic set builds in seconds
ROUNDS_TAU = 2
ROUNDS_N = 3
STEPS_BATCH = 256       # the headline configuration of bench.py
STEPS_ITERS = 20
FEED_STEPS = 4
REQUESTS = 32

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",   # or the cache read
)


class SmokeFailure(Exception):
    """A check that did not hold; its message is the reason printed."""


def check(ok: bool, reason: str) -> None:
    if not ok:
        raise SmokeFailure(reason)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Clock:
    """Wall seconds per leg, with the seconds JAX spent tracing, lowering
    and compiling (or reading the compile cache) kept apart."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.legs: dict[str, dict[str, float]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += seconds

    @contextlib.contextmanager
    def leg(self, name: str):
        say(f"leg {name}: start")
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        wall, comp = time.perf_counter() - t0, self.compile_s - c0
        self.legs[name] = {"wall_s": round(wall, 1),
                           "compile_s": round(comp, 1)}
        say(f"leg {name}: ok in {wall:.1f} s wall, of which {comp:.1f} s "
            f"tracing, lowering and compiling")


def cache_entries(path: str) -> int:
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


def all_finite(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x, np.float32)).all())
               for x in jax.tree_util.tree_leaves(tree))


def host_copy(tree):
    import jax
    import numpy as np
    return jax.tree_util.tree_map(lambda x: np.array(x), tree)


def trees_equal(a, b) -> bool:
    import jax
    import numpy as np
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def leg_kernels(shapes=((4, 96, 27, 27), (4, 256, 13, 13))) -> None:
    """The default path's Pallas kernel against the XLA reference, forward
    (inference and training variants) and backward, relu folded and not,
    at CaffeNet's norm1 and norm2 shapes.  f32 to 1e-4 of the largest
    value; bf16 to its own rounding (8 mantissa bits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops.pallas_kernels import relu_lrn_across_channels
    from sparknet_tpu.ops.vision import relu_lrn_reference

    geom = (5, 1e-4, 0.75, 1.0)
    rng = np.random.default_rng(0)
    for shape, dtype, relu in itertools.product(
            shapes, (jnp.float32, jnp.bfloat16), (False, True)):
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        x = jnp.asarray(rng.normal(scale=20.0, size=shape), dtype)
        w = jnp.asarray(rng.normal(size=shape), jnp.float32)

        def out_and_grad(fn):
            def loss(x):
                return jnp.sum(fn(x, *geom, relu).astype(jnp.float32) * w)
            y = jax.jit(lambda x: fn(x, *geom, relu))(x)
            g = jax.jit(jax.grad(loss))(x)
            return (np.asarray(y, np.float32), np.asarray(g, np.float32))

        y_k, g_k = out_and_grad(relu_lrn_across_channels)
        y_r, g_r = out_and_grad(relu_lrn_reference)
        for what, k, r in (("forward", y_k, y_r), ("backward", g_k, g_r)):
            check(bool(np.isfinite(k).all()),
                  f"Pallas LRN {what} not finite at {shape} "
                  f"{jnp.dtype(dtype).name} relu={relu}")
            err = float(np.max(np.abs(k - r)) / (np.max(np.abs(r)) + 1e-30))
            check(err <= tol,
                  f"Pallas LRN {what} differs from relu_lrn_reference by "
                  f"{err:.2e} (> {tol:g}) at {shape} "
                  f"{jnp.dtype(dtype).name} relu={relu}")
    say(f"kernels: relu_lrn_across_channels matches relu_lrn_reference at "
        f"{list(shapes)}, f32 and bf16, forward and backward")


# the benchmark's cells: CaffeNet bf16 1,024 and GoogLeNet bf16 256
# (resident), CaffeNet float32 512 a chip (rounds); (shape, dtype, relu
# folded: GoogLeNet pools before its LRN)
CELL_LRN_SHAPES = (
    ((1024, 96, 27, 27), "bfloat16", True),
    ((1024, 256, 13, 13), "bfloat16", True),
    ((256, 64, 56, 56), "bfloat16", False),
    ((256, 192, 56, 56), "bfloat16", False),
    ((512, 96, 27, 27), "float32", True),
    ((512, 256, 13, 13), "float32", True),
)


def _lrn_one_image_a_block(x, dy, size, alpha, beta, k, relu):
    """y, scale and dx of the epilogue's own arithmetic
    (``pallas_kernels._fwd_math``/``_bwd_math``) in the blocking the
    kernel had until PR 33: ``[N, C, H*W]``, the positions on the lanes,
    one image and 512 lanes a block, a block at once."""
    import functools

    import jax
    from jax.experimental import pallas as pl

    from sparknet_tpu.ops import pallas_kernels as pk

    n, c, h, w = x.shape
    spec = pl.BlockSpec((None, c, 512), lambda i, j: (i, 0, j))
    like = jax.ShapeDtypeStruct((n, c, h * w), x.dtype)
    call = functools.partial(pl.pallas_call, grid=(n, pl.cdiv(h * w, 512)))

    def fwd(x_ref, y_ref, scale_ref):
        y, scale = pk._fwd_math(x_ref[:], size=size, alpha=alpha,
                                beta=beta, k=k, relu=relu)
        y_ref[:] = y.astype(y_ref.dtype)
        scale_ref[:] = scale.astype(scale_ref.dtype)

    def bwd(x_ref, scale_ref, dy_ref, dx_ref):
        dx_ref[:] = pk._bwd_math(
            x_ref[:], scale_ref[:], dy_ref[:], size=size, alpha=alpha,
            beta=beta, relu=relu).astype(dx_ref.dtype)

    xs = x.reshape(like.shape)
    y, scale = call(fwd, out_shape=(like, like), in_specs=[spec],
                    out_specs=(spec, spec))(xs)
    dx = call(bwd, out_shape=like, in_specs=[spec] * 3, out_specs=spec)(
        xs, scale, dy.reshape(like.shape))
    return tuple(v.reshape(x.shape) for v in (y, scale, dx))


def leg_kernel_layouts(cases=CELL_LRN_SHAPES) -> None:
    """The layout is not the arithmetic: at the cells' own LRN shapes the
    kernel's y, scale and dx, with the batch on the lanes and a block of
    many positions walked a row and a lane tile at a time, equal bit for
    bit what the same arithmetic gives with the positions on the lanes,
    one image a block (the kernel until PR 33).  No timed window."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.ops import pallas_kernels as pk

    geom = (5, 1e-4, 0.75, 1.0)
    rng = np.random.default_rng(1)
    for shape, dtype, relu in cases:
        check(pk.lrn_lanes(shape) == "batch_lanes",
              f"{shape} should put the batch on the lanes, "
              f"got {pk.lrn_lanes(shape)}")
        x = jnp.asarray(20 * rng.standard_normal(shape, np.float32), dtype)
        dy = jnp.asarray(rng.standard_normal(shape, np.float32), dtype)

        @jax.jit
        def now(x, dy):
            y, (_, scale) = pk._lrn_vjp_fwd(x, *geom, relu)
            return y, scale, pk._lrn_vjp_bwd(*geom, relu, (x, scale),
                                             dy)[0]

        before = jax.jit(lambda x, dy: _lrn_one_image_a_block(
            x, dy, *geom, relu))
        for what, a, b in zip(("y", "scale", "dx"), now(x, dy),
                              before(x, dy)):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            check(np.array_equal(a, b),
                  f"LRN epilogue {what} at {shape} {dtype} relu={relu}: "
                  f"{int((a != b).sum())} of {a.size} elements differ "
                  f"between the two blockings, by at most "
                  f"{float(np.max(np.abs(a - b))):.3e}")
    say(f"kernels: y, scale and dx array_equal between batch-on-lanes "
        f"blocks and one image a block at {[c[0] for c in cases]}")


# ---------------------------------------------------------------------------
# rounds
# ---------------------------------------------------------------------------

def check_placement(tree, n_dev: int, what: str) -> None:
    """Every leaf has addressable shards on ``n_dev`` distinct devices:
    what catches "everything on device 0"."""
    import jax
    for leaf in jax.tree_util.tree_leaves(tree):
        devs = {s.device for s in leaf.addressable_shards}
        check(len(devs) == n_dev,
              f"{what}: a leaf of shape {leaf.shape} has shards on "
              f"{len(devs)} device(s), not {n_dev}")


def check_memory_in_use() -> None:
    import jax
    for d in jax.local_devices():
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        check(used > 0, f"{d} reports bytes_in_use={used} with the "
                        f"parameters placed")


def leg_rounds(n_dev: int, *, batch: int = ROUNDS_BATCH,
               tau: int = ROUNDS_TAU, rounds: int = ROUNDS_N) -> None:
    import numpy as np

    from sparknet_tpu.apps import imagenet_app
    from sparknet_tpu.models import caffenet
    from sparknet_tpu.parallel import (
        DistributedTrainer, TrainerConfig, make_mesh,
    )
    from sparknet_tpu.proto import load_solver_prototxt_with_net

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        # -- the app, front door to snapshot ----------------------------
        snap = os.path.join(tmp, "app.npz")
        scores = imagenet_app.main([
            "--synthetic", "--model", "caffenet", "--workers", str(n_dev),
            "--batch", str(batch), "--tau", str(tau),
            "--rounds", str(rounds), "--test-interval", "2",
            "--device-preprocess", "--snapshot", snap, "--log-dir", tmp])
        logs = [f for f in os.listdir(tmp) if f.startswith("training_log_")]
        check(len(logs) == 1, f"imagenet_app wrote {len(logs)} logs")
        with open(os.path.join(tmp, logs[0])) as f:
            losses = [float(v) for v in re.findall(r"loss=(\S+)", f.read())]
        check(len(losses) == rounds,
              f"imagenet_app logged {len(losses)} round losses, "
              f"not {rounds}")
        check(all(np.isfinite(losses)),
              f"imagenet_app round loss not finite: {losses}")
        check(bool(scores) and all_finite(scores),
              f"imagenet_app eval scores not finite: {scores}")
        check(os.path.exists(snap), "imagenet_app wrote no snapshot")
        say(f"rounds: imagenet_app on {n_dev} worker(s): losses {losses}, "
            f"eval {scores}")

        # -- the safety plane: checkpoint + guard + audit, then resume ---
        gb = batch * n_dev
        sp = load_solver_prototxt_with_net(imagenet_app.SOLVER,
                                           caffenet(gb, gb))
        mesh = make_mesh(n_dev)
        lag, every = 2, 2     # bench.py's round_overhead configuration
        ck = os.path.join(tmp, "ckpt")
        tr = DistributedTrainer(sp, mesh, TrainerConfig(
            strategy="local_sgd", tau=tau, harvest_lag=lag,
            checkpoint_dir=ck, checkpoint_every=every, checkpoint_keep=3,
            guard_numerics=True, audit_every=1), seed=0)
        check_placement(tr.params, n_dev, "trainer params")
        check_memory_in_use()
        before = host_copy(tr.params)
        rng = np.random.default_rng(0)

        def host_rounds():
            for _ in range(every):
                yield {"data": rng.normal(size=(tau, gb, 3, 227, 227)
                                          ).astype(np.float32),
                       "label": rng.integers(0, 1000, size=(tau, gb)
                                             ).astype(np.float32)}

        with tr.input_feed(host_rounds()) as feed:
            for staged in feed:
                check_placement(staged, n_dev, "staged round")
                tr.train_round(staged)
        round_losses = tr.drain()
        tr.flush_checkpoints()
        check(tr.round == every and len(round_losses) == every,
              f"guarded trainer finished round {tr.round} with "
              f"{len(round_losses)} losses, not {every} (a guard or audit "
              f"trip rolled it back)")
        check(all(np.isfinite(list(round_losses.values()))),
              f"guarded round loss not finite: {round_losses}")
        check(tr.guard_trips == 0 and tr.audit_trips == 0,
              f"guard tripped {tr.guard_trips}x, audit {tr.audit_trips}x")
        fps = np.asarray(tr.audit_params())
        check(fps.shape[0] == n_dev and bool((fps == fps[0]).all()),
              f"audit fingerprints differ across replicas: {fps.tolist()}")
        after = host_copy(tr.params)
        check(all_finite(after), "parameters not finite after rounds")
        check(not trees_equal(before, after),
              "parameters did not change after a round")

        fresh = DistributedTrainer(sp, mesh, TrainerConfig(
            strategy="local_sgd", tau=tau), seed=1)
        check(not trees_equal(fresh.params, after),
              "a fresh trainer from another seed already equals the "
              "saved one")
        manifest = fresh.resume_latest(ck)
        check(manifest is not None and fresh.round == tr.round,
              f"resume_latest found {manifest and manifest.get('round')}, "
              f"saved round {tr.round}")
        check(trees_equal(fresh.params, after)
              and trees_equal(fresh.state, tr.state)
              and fresh.iter == tr.iter
              and trees_equal(fresh._rng, tr._rng),
              "the restored trainer does not equal the saved one")
        check_placement(fresh.params, n_dev, "restored params")
        say(f"rounds: guarded rounds {dict(round_losses)}; audit "
            f"fingerprints equal on {n_dev} replica(s); resume at round "
            f"{fresh.round} bit-identical")


# ---------------------------------------------------------------------------
# steps
# ---------------------------------------------------------------------------

def leg_steps(*, batch: int = STEPS_BATCH, iters: int = STEPS_ITERS,
              feed_steps: int = FEED_STEPS) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.data import device_feed
    from sparknet_tpu.proto import load_solver_prototxt_with_net
    from sparknet_tpu.solvers import Solver
    from sparknet_tpu.utils.profiling import (
        BENCH_SOLVER_PROTOTXT, build_bench_model, scanned_train_block,
    )

    net, in_shape, classes = build_bench_model("caffenet", batch)
    sp = load_solver_prototxt_with_net(BENCH_SOLVER_PROTOTXT, net)
    solver = Solver(sp, seed=0, compute_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.normal(size=(1, batch) + in_shape
                                  ).astype(np.float32))
    label = jnp.asarray(rng.integers(0, classes, size=(1, batch)
                                     ).astype(np.float32))
    resident = {"data": data, "label": label}

    # a fused chain whose kernel gave way to the XLA reference is a
    # failure here, not a slower pass
    chains = solver.train_net._fuse_plan.chains
    check(len(chains) >= 2,
          f"the fusion plan names {len(chains)} LRN chain(s) for CaffeNet; "
          f"conv1..norm1 and conv2..norm2 expected")
    kernels = re.findall(r'kernel_name = "(\w+)"', solver._step.lower(
        solver.params, solver.state, 0, resident,
        jax.random.PRNGKey(1)).as_text())
    for name in ("relu_lrn_fwd", "relu_lrn_bwd"):
        check(kernels.count(name) >= len(chains),
              f"the lowered train step holds {kernels.count(name)} "
              f"{name} custom call(s) for {len(chains)} fused LRN chains "
              f"({[ch.members for ch in chains]}); found {kernels}")

    before = host_copy(solver.params)
    block = scanned_train_block(solver, iters)
    params, state, _, loss = block(solver.params, solver.state, 0,
                                   resident, jax.random.PRNGKey(0))
    loss = float(loss)
    check(np.isfinite(loss), f"loss after {iters} resident steps: {loss}")
    check(all_finite(params) and not trees_equal(before, params),
          "parameters not finite, or unchanged, after the scanned block")
    out = solver._test_fwd(params, {"data": data[0], "label": label[0]})
    check(bool(out) and all_finite(out), f"test forward not finite: {out}")
    say(f"steps: {iters} resident bf16 steps at batch {batch}, loss "
        f"{loss:.4f}; test forward "
        f"{ {k: np.asarray(v).tolist() for k, v in out.items()} }")

    # steps fed from uint8 host batches, cast on the device after transfer
    host = [{"data": rng.integers(0, 256, size=(batch,) + in_shape
                                  ).astype(np.uint8),
             "label": rng.integers(0, classes, size=batch
                                   ).astype(np.float32)} for _ in range(3)]
    fed = Solver(sp, seed=0, compute_dtype=jnp.bfloat16)
    with device_feed(itertools.islice(itertools.cycle(host), feed_steps),
                     device_cast={"data": jnp.float32}) as feed:
        fed.set_train_data(feed)
        fed_loss = fed.step(feed_steps)
    check(np.isfinite(fed_loss) and fed.iter == feed_steps,
          f"loss after {fed.iter} fed steps: {fed_loss}")
    check(all_finite(fed.params), "parameters not finite after fed steps")
    say(f"steps: {feed_steps} steps through device_feed from uint8, "
        f"smoothed loss {fed_loss:.4f}")


# ---------------------------------------------------------------------------
# requests
# ---------------------------------------------------------------------------

def leg_requests(*, requests: int = REQUESTS, cfg=None) -> None:
    import numpy as np

    from sparknet_tpu.parallel.serving import (
        InferenceEngine, ModelHouse, ServeConfig, solo_references,
    )

    house = ModelHouse(cfg or ServeConfig())
    lm = house.load("caffenet")
    rng = np.random.default_rng(0)
    inputs = [rng.normal(size=lm.in_shape).astype(np.float32)
              for _ in range(requests)]
    refs = solo_references(lm, inputs)
    engine = InferenceEngine(house)
    try:
        with concurrent.futures.ThreadPoolExecutor(8) as pool:
            futures = list(pool.map(
                lambda x: engine.submit("caffenet", x), inputs))
        results = [f.result(timeout=300.0) for f in futures]
    finally:
        engine.stop()
    check(not engine._dispatcher.is_alive()
          and not engine._harvester.is_alive(),
          "the engine's threads outlived stop()")
    shapes: dict[int, int] = {}
    for i, res in enumerate(results):
        check(res.probs.shape == (lm.classes,)
              and bool(np.isfinite(res.probs).all()),
              f"request {i}: probs shape {res.probs.shape}, not finite "
              f"or not ({lm.classes},)")
        check(np.array_equal(res.probs, refs[res.padded_to][i]),
              f"request {i} (batch of {res.batch_n} padded to "
              f"{res.padded_to}) differs from its solo reference by "
              f"{np.max(np.abs(res.probs - refs[res.padded_to][i])):.3e}")
        shapes[res.padded_to] = shapes.get(res.padded_to, 0) + 1
    say(f"requests: {requests} concurrent submits answered at padded "
        f"shapes {shapes} ({lm.dtype}, warmed at {lm.batch_shapes}), "
        f"every row equal to its solo reference")


# ---------------------------------------------------------------------------

def main() -> int:
    import jax

    dev = jax.devices()
    platform = dev[0].platform
    if platform != "tpu":
        print(f"chip_smoke: the backend JAX found is {platform!r} "
              f"({dev[0].device_kind} x{len(dev)}), not 'tpu': nothing "
              f"was built or run", file=sys.stderr)
        return 1

    import jaxlib
    from importlib import metadata

    import sparknet_tpu
    from sparknet_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    # Keep every executable, however quick its compile: which entries
    # exist must not depend on how long a compile happened to take, or a
    # second run of this script still finds something to add.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    entries_before = cache_entries(cache_dir)
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "unknown"
    n_dev = len(dev)
    say(f"platform {platform}, device_kind {dev[0].device_kind}, "
        f"{n_dev} device(s); jax {jax.__version__}, jaxlib "
        f"{jaxlib.__version__}, libtpu {libtpu}")
    say(f"compile cache {cache_dir}: {entries_before} entries before")
    clock = Clock()

    try:
        # the native data plane, built here from data_pipeline.cpp: a
        # library left on disk by an earlier build proves nothing about
        # what a checkout can build
        from sparknet_tpu import native
        shutil.rmtree(os.path.join(os.path.dirname(native.__file__),
                                   "_build"), ignore_errors=True)
        with clock.leg("native"):
            check(native.available(),
                  "sparknet_tpu.native did not build from source (g++ and "
                  "libjpeg are on this machine); see stderr")
        with clock.leg("kernels"):
            leg_kernels()
            leg_kernel_layouts()
        with clock.leg("rounds"):
            leg_rounds(n_dev)
        with clock.leg("steps"):
            leg_steps()
        with clock.leg("requests"):
            leg_requests()
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            say(f"{d}: bytes_in_use {stats.get('bytes_in_use')}, peak "
                f"{stats.get('peak_bytes_in_use')}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1

    entries_after = cache_entries(cache_dir)
    say(f"compile cache {cache_dir}: {entries_after} entries after "
        f"(+{entries_after - entries_before})")
    say(f"seconds per leg: {json.dumps(clock.legs)}")
    say(f"sparknet_tpu {sparknet_tpu.__version__}: all legs passed; "
        f"throughput: not measured")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev[0].device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
