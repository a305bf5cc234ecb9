"""Driver for the paper's mechanism: rounds of ``DistributedTrainer``.

One general generator for every mix that trains a configuration by rounds
of tau steps over the chips of the cell, as ``apps/common.run_training``
does: ``train_round`` once a round, the loss fetched each round.  The mix
file gives the strategy and tau; the configuration gives the batch a
worker takes a step.  One round of raw uint8 images is staged across the
devices once, from the seed, and reused; the crop, mirror and mean run
inside the compiled round (``device_crop_mirror_mean``).  The trainer has
no compute dtype, so a mix for it states ``float32``.
"""

from __future__ import annotations

import time

import numpy as np

from ..lib import check
from ..lib.harness import Cell, TrainingDriver


class Driver(TrainingDriver):
    def __init__(self, cell: Cell):
        super().__init__(cell)
        self.tau = int(self.mix["tau"])
        if self.dtype != "float32":
            raise SystemExit("DistributedTrainer has no compute dtype: a "
                             "mix for this driver states float32")

    # -- set-up -----------------------------------------------------------
    def make_trainer(self, n_workers: int):
        from sparknet_tpu.parallel import (
            DistributedTrainer, TrainerConfig, device_crop_mirror_mean,
            make_mesh,
        )
        from sparknet_tpu.proto import load_solver_prototxt_with_net

        gb = self.batch * n_workers
        net = self.cell.net_param(gb, check.CHECK_BATCH * n_workers)
        sp = load_solver_prototxt_with_net(self.cfg["solver"], net)
        e = int(self.inp["raw_edge"])
        mean = np.broadcast_to(
            np.asarray(self.inp["mean"], np.float32).reshape(-1, 1, 1),
            (int(self.inp["channels"]), e, e))
        pre = device_crop_mirror_mean(int(self.inp["crop"]), mirror=True,
                                      mean=mean)
        trainer = DistributedTrainer(sp, make_mesh(n_workers), TrainerConfig(
            strategy=self.mix["strategy"], tau=self.tau,
            device_preprocess=pre), seed=self.cell.seed)
        return trainer, net

    def _round(self, trainer, n_workers: int, key):
        """One round's raw batches, made on the devices in one call and
        sharded as the trainer wants its feed."""
        import jax
        import jax.numpy as jnp

        gb = self.batch * n_workers
        classes = int(self.inp["classes"])
        rows = self.tau * trainer.sp.iter_size

        def make(key):
            kd, kl = jax.random.split(key)
            return {"data": jax.random.bits(kd, self.raw_shape(rows, gb),
                                            jnp.uint8),
                    "label": jax.random.randint(
                        kl, (rows, gb), 0, classes).astype(jnp.float32)}

        return jax.jit(make, out_shardings=trainer.input_sharding)(key)

    def build(self) -> None:
        import jax
        self.key = jax.random.PRNGKey(self.cell.seed)
        self.trainer, self.net_param = self.make_trainer(self.cell.chips)
        self.round = self._round(self.trainer, self.cell.chips,
                                 jax.random.fold_in(self.key, 1))

    # -- correctness ------------------------------------------------------
    def check(self) -> dict:
        import jax

        from sparknet_tpu.proto.caffe_pb import NetState, Phase

        batch = check.seeded_batch(jax.random.fold_in(self.key, 2), self.inp,
                                   check.CHECK_BATCH * self.cell.chips)
        params = jax.tree_util.tree_map(np.asarray, self.trainer.params)
        logits = check.system_logits(self.net_param, params, batch,
                                     self.cfg["logits"])
        # the trainer's own distributed eval: every worker scores its rows
        totals = self.trainer.test(iter([batch]), 1)
        loss = totals[self.cfg["loss"]] / totals["__test_batches__"]
        verdict = check.compare(
            self.dtype, self.net_param.filtered(NetState(Phase.TEST)),
            params, batch, logits, loss, self.cfg["logits"],
            self.cfg["loss"])
        return self.with_precision(verdict, self.trainer.train_net,
                                   self.trainer.params)

    # -- the work ---------------------------------------------------------
    def warm(self) -> None:
        self.trainer.train_round(self.round)
        self.stall0 = dict(self.trainer.stall_s)

    def unit(self) -> tuple[int, int, float]:
        with self.cell.spans.span("train_round"):
            loss = self.trainer.train_round(self.round)
        return self.tau * self.batch * self.cell.chips, 1, loss

    def describe(self) -> dict:
        return {"fuse_plan": self.trainer.train_net.fuse_plan_id(),
                "tune_plan": self.trainer.train_net.tune_plan_id(),
                "batch_per_worker": self.batch, "workers": self.cell.chips,
                "tau": self.tau, "strategy": self.mix["strategy"],
                "compute_dtype": self.dtype,
                "shard_plan": self.trainer.shard_plan_id}

    def counters(self) -> dict:
        return {"stall_s": {k: v - self.stall0.get(k, 0.0)
                            for k, v in self.trainer.stall_s.items()}}

    def after_trace(self) -> dict:
        """In the traced run only, after the windows: the same round on a
        one-device mesh, for the scaling efficiency."""
        import jax
        rounds = int(self.mix["one_device_rounds"])
        trainer, _ = self.make_trainer(1)
        batches = self._round(trainer, 1, jax.random.fold_in(self.key, 3))
        for _ in range(2):                            # compiles, settles
            trainer.train_round(batches)
        t0 = time.perf_counter()
        for _ in range(rounds):
            trainer.train_round(batches)              # fetches its loss
        dt = time.perf_counter() - t0
        return {"one_device_img_s": rounds * self.tau * self.batch / dt}

    def used_devices(self):
        return list(self.trainer.mesh.devices.flat)
