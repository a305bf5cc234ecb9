"""Driver for single-chip training of a language model through
``Solver.step``.

The mix file decides the compute dtype, the solver text, the positions a
sequence has and the sequences a step takes, how many steps one call runs
before the loss is fetched, and where the batches come from:
``"source": {"kind": "resident", "batches": n}`` is n batches of token ids
made on the device from the seed, uniform over the configuration's slice
of the vocabulary, and cycled, so the feed is bypassed.  One "image" of the
window is one sequence.

What it brings of its own, where ``lib/harness.py`` and ``lib/check.py``
read image keys: the ``as_built`` check (``lib/lm_flops.py``), the seeded
tokens, the comparison with the reference and the types check
(``lib/check_lm.py``), and the expert layers' load on the first resident
batch (``ops.sequence.moe_load``, one forward a sequence), read twice: on
the weights the seed gave (``moe_load_seeded`` on the ``counters`` line:
what the fillers route like) and after the window, on the weights the run
left (``moe_load``: what the timed steps ended with, which
``moe_imbalance`` reads).  A run in which a row was left out is not
correct.
"""

from __future__ import annotations

import itertools

from ..lib import check, check_lm, harness, lm_flops
from ..lib.harness import Cell, TimedIterator


class Driver:
    def __init__(self, cell: Cell):
        self.cell = cell
        self.cfg, self.mix = cell.config, cell.mix
        self.dtype = self.mix["compute_dtype"]
        self.positions = int(self.mix["sequence_length"])
        self.batch = int(self.mix["sequences_per_step"])
        self.steps = int(self.mix["steps_per_call"])
        self.vocab = int(self.cfg["vocab_size"])
        self.loss_margin = check.LOSS_MARGIN
        self._load = {}             # "seeded", "after" -> moe_load's result

    def raw_shape(self, *lead: int) -> tuple:
        """Shape of the token ids under the leading dimensions ``lead``
        (``rehearse.py`` asks every driver)."""
        return (*lead, self.positions)

    def _compute_dtype(self):
        import jax.numpy as jnp
        return None if self.dtype == "float32" else jnp.dtype(self.dtype)

    def net_for(self, sequences: int, positions: int):
        """The configuration's net as the program builds it, held to the
        widths and the parameter count the configuration file states."""
        from sparknet_tpu import models
        net = getattr(models, self.cfg["builder"])(
            sequences, 1, seq_len=positions,
            **self.cfg.get("builder_args", {}))
        from sparknet_tpu.proto.caffe_pb import NetState, Phase
        lm_flops.check_as_built(self.cfg,
                                net.filtered(NetState(Phase.TRAIN)))
        return net

    def train_net_param(self):
        from sparknet_tpu.proto.caffe_pb import NetState, Phase
        return self.net_param.filtered(NetState(Phase.TRAIN))

    # -- set-up -----------------------------------------------------------
    def make_solver(self):
        from sparknet_tpu.proto import load_solver_prototxt_with_net
        from sparknet_tpu.solvers import Solver

        self.net_param = self.net_for(self.batch, self.positions)
        sp = load_solver_prototxt_with_net(self.mix["solver"],
                                           self.net_param)
        return Solver(sp, seed=self.cell.seed,
                      compute_dtype=self._compute_dtype())

    def build(self) -> None:
        import jax

        self.solver = self.make_solver()
        self.key = jax.random.PRNGKey(self.cell.seed)
        kind = self.mix["source"]["kind"]
        if kind != "resident":
            raise SystemExit(f"mix source kind {kind!r} is not one this "
                             f"driver generates")
        n = int(self.mix["source"]["batches"])
        tokens = jax.jit(lambda k: check_lm.seeded_tokens(
            k, n * self.batch, self.positions, self.vocab))(
                jax.random.fold_in(self.key, 1))
        self.batches = [{"tokens": tokens[i * self.batch:
                                          (i + 1) * self.batch]}
                        for i in range(n)]
        self.solver.set_train_data(TimedIterator(
            itertools.cycle(self.batches), self.cell.spans))
        self.moe_load("seeded")

    # -- correctness ------------------------------------------------------
    def check(self) -> dict:
        import jax

        from sparknet_tpu.proto.caffe_pb import NetState, Phase

        train = self.train_net_param()
        params, cd = self.solver.params, self._compute_dtype()
        k1, k2 = jax.random.split(jax.random.fold_in(self.key, 2))
        tokens = check_lm.seeded_tokens(k1, 1, self.positions, self.vocab)
        grad_positions = min(check_lm.GRAD_POSITIONS, self.positions)
        grad_tokens = check_lm.seeded_tokens(k2, 1, grad_positions,
                                             self.vocab)
        leaves = check_lm.grad_leaves(train)
        logits, loss = check_lm.system_forward(train, params, tokens, cd)
        grads = check_lm.system_grads(
            self.net_for(1, grad_positions).filtered(NetState(Phase.TRAIN)),
            params, grad_tokens, leaves, cd)
        verdict = check_lm.compare(self.dtype, self.cfg, params, tokens,
                                   grad_tokens, leaves, logits, loss, grads)
        held = check_lm.held_precision(self.dtype, self.solver.train_net,
                                       params, self.batch, self.positions)
        # the check's programs go: a loaded program keeps its temporaries
        # reserved, and the step needs the room
        jax.clear_caches()
        return {**verdict, "ok": verdict["ok"] and held["ok"],
                "precision": held}

    # -- the work ---------------------------------------------------------
    def warm(self) -> None:
        self.solver.step(self.steps)

    def unit(self) -> tuple[int, int, float]:
        with self.cell.spans.span("step_call"):
            loss = self.solver.step(self.steps)
        return self.steps * self.batch, self.steps, loss

    def measure(self, seconds: float) -> harness.Window:
        return harness.run_window(self, seconds, self.cell.spans)

    def moe_load(self, when: str) -> dict:
        """Rows every expert layer was sent on the first resident batch
        with the weights as they are at the first call for ``when``."""
        if when not in self._load:
            from sparknet_tpu.ops.sequence import moe_load
            self._load[when] = moe_load(
                self.solver.train_net, self.solver.params, self.batches[0])
        return self._load[when]

    def verdict(self, windows) -> tuple[bool, str]:
        ok, why = harness.losses_ok([x for w in windows for x in w.losses],
                                    self.loss_margin)
        dropped = sum(v["dropped"] for v in self._load["seeded"].values())
        dropped += sum(v["dropped"] for v in self.moe_load("after").values())
        if dropped:
            return False, f"{why}; {dropped} routed row(s) left out"
        return ok, why

    def describe(self) -> dict:
        return {"fuse_plan": self.solver.train_net.fuse_plan_id(),
                "tune_plan": self.solver.train_net.tune_plan_id(),
                "batch": self.batch, "positions": self.positions,
                "compute_dtype": self.dtype, "steps_per_call": self.steps}

    def counters(self) -> dict:
        from sparknet_tpu.utils import telemetry
        snap = telemetry.get_registry().snapshot()
        return {"moe_load": self.moe_load("after"),
                "moe_load_seeded": self._load["seeded"],
                **{k: snap[k] for k in (
                    "attn_lowering_total", "moe_lowering_total",
                    "moe_rows_total", "moe_dropped_total") if k in snap}}

    def after_trace(self) -> dict:
        return {}

    def used_devices(self):
        import jax
        return jax.devices()[:1]

    def close(self) -> None:
        pass
