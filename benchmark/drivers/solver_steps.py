"""Driver for single-chip training through ``Solver.step``.

One general generator for every mix that trains a configuration with the
host ``Solver`` on one chip.  The mix file decides the compute dtype, how
many steps one call runs before the loss is fetched (a user's display
interval), and where the batches come from:

- ``"source": {"kind": "resident", "batches": n}``: n raw uint8 batches
  made on the device from the seed and cycled, so the feed is bypassed;
- ``"source": {"kind": "records", "images": n, "dataset_seed": s}``: record
  shards written once per checkout with the program's own converter, read
  back through ``records_feed(raw=True)`` and ``device_feed`` with the
  pipeline's defaults: no knob set and no ``ShardCache`` attached, as
  ``data/db.py`` builds the feed for a ``RECORDS`` source.

Either way the crop, mirror and mean run inside the compiled step
(``Solver.set_augment``), and both kinds hand the step the same shapes and
dtypes, so they run the same compiled program.
"""

from __future__ import annotations

import itertools
import os
import shutil

import numpy as np

from ..lib import check
from ..lib.harness import Cell, TimedIterator, TrainingDriver


class Driver(TrainingDriver):
    def __init__(self, cell: Cell):
        super().__init__(cell)
        self.steps = int(self.mix["steps_per_call"])
        self.feed = None            # the DeviceFeed to close, if any
        self.feed_stats = {}        # stage -> FeedStats

    # -- set-up -----------------------------------------------------------
    def make_solver(self):
        """The system under test: the configuration's net in a ``Solver``
        at the mix's compute dtype, with the augmentation in its step."""
        from sparknet_tpu.ops.augment import AugmentSpec
        from sparknet_tpu.proto import load_solver_prototxt_with_net
        from sparknet_tpu.solvers import Solver

        self.net_param = self.cell.net_param(self.batch, check.CHECK_BATCH)
        sp = load_solver_prototxt_with_net(self.cfg["solver"], self.net_param)
        solver = Solver(sp, seed=self.cell.seed,
                        compute_dtype=self._compute_dtype())
        solver.set_augment(AugmentSpec(
            crop=int(self.inp["crop"]), mirror=True,
            mean=np.asarray(self.inp["mean"], np.float32).reshape(-1, 1, 1)))
        return solver

    def build(self) -> None:
        import jax

        self.solver = self.make_solver()
        self.key = jax.random.PRNGKey(self.cell.seed)
        kind = self.mix["source"]["kind"]
        if kind == "resident":
            batches = self._resident_batches()
        elif kind == "records":
            batches = self._records_feed()
        else:
            raise SystemExit(f"mix source kind {kind!r} is not one this "
                             f"driver generates")
        self.solver.set_train_data(TimedIterator(batches, self.cell.spans))

    def _compute_dtype(self):
        """What ``Solver`` takes: nothing for float32, its own default."""
        import jax.numpy as jnp
        return None if self.dtype == "float32" else jnp.dtype(self.dtype)

    def _resident_batches(self):
        import jax
        import jax.numpy as jnp

        n = int(self.mix["source"]["batches"])
        classes = int(self.inp["classes"])

        @jax.jit
        def make(key):
            kd, kl = jax.random.split(key)
            data = jax.random.bits(kd, self.raw_shape(n, self.batch),
                                   jnp.uint8)
            label = jax.random.randint(kl, (n, self.batch), 0, classes)
            return ([data[i] for i in range(n)],
                    [label[i].astype(jnp.float32) for i in range(n)])

        data, label = make(jax.random.fold_in(self.key, 1))
        return itertools.cycle(
            [{"data": d, "label": l} for d, l in zip(data, label)])

    def _records_feed(self):
        from sparknet_tpu.data import device_feed
        from sparknet_tpu.data.pipeline import FeedStats
        from sparknet_tpu.data.records import records_feed
        from sparknet_tpu.models.dsl import layer
        from sparknet_tpu.proto.caffe_pb import Phase

        source = self._ordered_shards(self._dataset())
        lp = layer("data", "Data", [], ["data", "label"], data_param={
            "source": source, "batch_size": self.batch,
            "backend": "RECORDS"})
        # one FeedStats a stage: both stages count the batches they pass,
        # and a shared one would count each twice
        self.feed_stats = {"records": FeedStats(), "device": FeedStats()}
        host = records_feed(lp, Phase.TRAIN, raw=True,
                            stats=self.feed_stats["records"])
        self.feed = device_feed(host, stats=self.feed_stats["device"])
        return self.feed

    def _dataset(self) -> str:
        """The directory of record shards, written on the first run in a
        checkout.  The data set stands for the one on a user's disk, so it
        comes from the mix's own ``dataset_seed`` and every run finds the
        same files; the run's seed decides the order of the shards."""
        from sparknet_tpu.data.records import convert_to_shards

        src = self.mix["source"]
        n, dseed = int(src["images"]), int(src["dataset_seed"])
        shape = self.raw_shape()
        name = f"records-{'x'.join(map(str, shape))}-n{n}-s{dseed}"
        final = os.path.join(self.cell.cache_dir, name)
        if os.path.isdir(final):
            return final
        tmp = final + ".writing"
        shutil.rmtree(tmp, ignore_errors=True)
        rng = np.random.default_rng(dseed)
        classes = int(self.inp["classes"])

        def records(chunk: int = 256):
            for lo in range(0, n, chunk):
                m = min(chunk, n - lo)
                imgs = rng.integers(0, 256, size=(m, *shape), dtype=np.uint8)
                labels = rng.integers(0, classes, size=m)
                yield from zip(imgs, labels)

        convert_to_shards(records(), os.path.join(tmp, "data"))
        os.sync()       # or the write-back would run under the first window
        os.rename(tmp, final)
        return final

    def _ordered_shards(self, dataset: str) -> str:
        """A directory of links to the data set's shards, renamed so that
        the feed, which reads shards in the order of their names, visits
        them in an order drawn from the run's seed."""
        order_dir = os.path.join(dataset, f"order-s{self.cell.seed}")
        if os.path.isdir(order_dir):
            return order_dir
        shards = sorted(os.listdir(os.path.join(dataset, "data")))
        perm = np.random.default_rng(self.cell.seed).permutation(len(shards))
        tmp = order_dir + ".writing"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        for i, j in enumerate(perm):
            os.symlink(os.path.join("..", "data", shards[j]),
                       os.path.join(tmp, f"shard-{i:05d}.rec"))
        os.rename(tmp, order_dir)
        return order_dir

    # -- correctness ------------------------------------------------------
    def check(self) -> dict:
        import jax

        from sparknet_tpu.proto.caffe_pb import NetState, Phase

        batch = check.seeded_batch(jax.random.fold_in(self.key, 2),
                                   self.inp, check.CHECK_BATCH)
        logits = check.system_logits(
            self.net_param, self.solver.params, batch, self.cfg["logits"],
            compute_dtype=self._compute_dtype())
        self.solver.set_test_data(lambda: iter([batch]))
        loss = self.solver.test(1)[self.cfg["loss"]]
        verdict = check.compare(
            self.dtype, self.net_param.filtered(NetState(Phase.TEST)),
            self.solver.params, batch, logits, loss, self.cfg["logits"],
            self.cfg["loss"])
        return self.with_precision(verdict, self.solver.train_net,
                                   self.solver.params)

    # -- the work ---------------------------------------------------------
    def warm(self) -> None:
        """The first call loads or compiles the step.  A fed mix asks for
        more calls, until the batches staged while set-up ran are used up
        and the window starts in the feed's steady state."""
        for _ in range(int(self.mix.get("warm_calls", 1))):
            self.solver.step(self.steps)

    def unit(self) -> tuple[int, int, float]:
        """One call of ``Solver.step``: ``steps`` steps dispatched, then
        the smoothed loss fetched, which waits for the last of them."""
        with self.cell.spans.span("step_call"):
            loss = self.solver.step(self.steps)
        return self.steps * self.batch, self.steps, loss

    def describe(self) -> dict:
        return {"fuse_plan": self.solver.train_net.fuse_plan_id(),
                "tune_plan": self.solver.train_net.tune_plan_id(),
                "batch": self.batch, "compute_dtype": self.dtype,
                "steps_per_call": self.steps}

    def counters(self) -> dict:
        out = {}
        for stage, stats in self.feed_stats.items():
            out[f"feed_{stage}"] = {**stats.snapshot(),
                                    "per_batch": stats.per_batch()}
        return out

    def used_devices(self):
        import jax
        return jax.devices()[:1]

    def close(self) -> None:
        if self.feed is not None:
            self.feed.close()
