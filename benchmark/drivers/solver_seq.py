"""Driver for single-chip training of any sequence model through
``Solver.step``: ``solver_tokens.Driver`` with the model taken out.

The configuration file names what is particular to its model, so that the
next token configuration adds data and a reference and no driver:

- ``modules.reference``: the module of ``benchmark/lib`` that holds its
  plain reference (``model(config)``, ``logits``, ``loss``,
  ``expert_rows``, ``highest``);
- ``modules.operations``: the module that counts its operations and holds
  it to ``as_built`` (``check_as_built``, ``train_flops_per_sequence``, ...;
  ``lib/seq_flops.py`` knows every sequence layer type);
- ``check.grad_leaves``: the ``[layer, blob]`` pairs whose gradients are
  compared, and ``check.tolerance``: the limits by compute dtype,
  ``logits``, ``loss``, ``rows`` and one of ``grads`` a leaf, each with its
  two readings and its reason under ``check.why``;
- ``check.controls``: results the comparison has to refuse (``control``;
  ``benchmark/control.py`` runs them on the chip).

The comparison itself is ``check_lm.py``'s, with the reference by name:
the system's own TRAIN net runs one sequence of the timed length forward
and its logits and loss are held against the reference's (``logits``: the
largest absolute difference over the largest absolute reference logit;
``loss``: the absolute difference); the gradients of the named leaves on a
sequence of ``check_lm.GRAD_POSITIONS`` positions are held against
``jax.grad`` of the reference's loss (``|g - g_ref| / |g_ref|``,
Frobenius; of an experts' stack the first held expert); the rows each
expert layer sent each held expert on the forward sequence
(``ops.sequence.moe_load``) are held against the rows the reference's own
choice sends (``rows``: ``sum |rows - rows_ref| / sum rows_ref`` of the
layer where it is largest; a selection bias moves an expert's rows by far
more than rounding does, and the weights do not show it); and
``check_lm.held_precision`` reads the types.  Everything else (the seeded
tokens, the resident batches, the window, the experts' load before and
after it, a run with a row left out being not correct) is inherited.
"""

from __future__ import annotations

import importlib

from ..lib import check_lm
from . import solver_tokens


class Driver(solver_tokens.Driver):
    def __init__(self, cell):
        super().__init__(cell)
        lib = lambda key: importlib.import_module(
            f"benchmark.lib.{self.cfg['modules'][key]}")
        self.ref, self.ops = lib("reference"), lib("operations")

    def net_for(self, sequences: int, positions: int):
        """The configuration's net as the program builds it, held to the
        widths and the parameter count the configuration file states."""
        from sparknet_tpu import models
        from sparknet_tpu.proto.caffe_pb import NetState, Phase
        builder = getattr(models, self.cfg["builder"], None)
        if builder is None:
            raise SystemExit(f"configuration {self.cfg['name']!r}: this "
                             f"checkout's sparknet_tpu.models has no "
                             f"builder {self.cfg['builder']!r}")
        net = builder(sequences, 1, seq_len=positions,
                      **self.cfg.get("builder_args", {}))
        self.ops.check_as_built(self.cfg,
                                net.filtered(NetState(Phase.TRAIN)))
        return net

    def grad_leaves(self) -> list[tuple[str, int]]:
        return [(name, int(i)) for name, i in
                self.cfg["check"]["grad_leaves"]]

    def check_inputs(self) -> tuple:
        """What the comparison is made on: the weights the seed gave, one
        seeded sequence of the timed length for logits and loss, one of
        ``check_lm.GRAD_POSITIONS`` positions for the gradients, and the
        leaves."""
        import jax
        k1, k2 = jax.random.split(jax.random.fold_in(self.key, 2))
        grad_positions = min(check_lm.GRAD_POSITIONS, self.positions)
        return (self.solver.params,
                check_lm.seeded_tokens(k1, 1, self.positions, self.vocab),
                check_lm.seeded_tokens(k2, 1, grad_positions, self.vocab),
                self.grad_leaves())

    def system_results(self, params, tokens, grad_tokens, leaves) -> tuple:
        """(logits, loss, gradients of the leaves, rows each expert layer
        sent each held expert on ``tokens``) of the system's own TRAIN
        net."""
        from sparknet_tpu.ops.sequence import moe_load
        from sparknet_tpu.proto.caffe_pb import NetState, Phase
        cd = self._compute_dtype()
        logits, loss = check_lm.system_forward(self.train_net_param(),
                                               params, tokens, cd)
        grad_net = self.net_for(1, grad_tokens.shape[1]).filtered(
            NetState(Phase.TRAIN))
        grads = check_lm.system_grads(grad_net, params, grad_tokens, leaves,
                                      cd)
        sent = moe_load(self.solver.train_net, params, {"tokens": tokens})
        return logits, loss, grads, {k: v["rows"] for k, v in sent.items()}

    def reference_results(self, params, tokens, grad_tokens, leaves,
                          dtype=None) -> tuple:
        """The same four of the named reference; ``dtype`` rounds both
        operands of every product to that type first."""
        ref, m = self.ref, self.ref.model(self.cfg)
        loss = lambda p, t: ref.loss(p, t, m, dtype)
        return (
            ref.highest(lambda p, t: ref.logits(p, t, m, dtype))(
                params, tokens[0]),
            ref.highest(loss)(params, tokens),
            ref.highest(check_lm._grad_of_leaves(loss, leaves))(
                [params[name][i] for name, i in leaves], params,
                grad_tokens),
            ref.highest(lambda p, t: ref.expert_rows(p, t, m, dtype))(
                params, tokens[0]))

    def compare(self, params, tokens, grad_tokens, leaves, logits, loss,
                grads, rows) -> dict:
        """Run the named reference on the same weights and tokens and hold
        the given results against it; ``refused_by`` names the numbers
        over their limits."""
        import numpy as np
        ref_logits, ref_loss, ref_grads, ref_rows = self.reference_results(
            params, tokens, grad_tokens, leaves)
        err = check_lm.errors(logits, loss, grads, ref_logits, ref_loss,
                              ref_grads)
        by_layer = {k: float(np.abs(np.asarray(rows[k]) - np.asarray(r)
                                    ).sum() / np.asarray(r).sum())
                    for k, r in ref_rows.items()}
        err["rows_rel_err"] = max(by_layer.values())
        tol = self.cfg["check"]["tolerance"][self.dtype]
        names = [f"{n}/{i}" for n, i in leaves]
        over = [name for name, e, t in (
            ("logits", err["logits_rel_err"], tol["logits"]),
            ("loss", err["loss_abs_err"], tol["loss"]),
            ("rows", err["rows_rel_err"], tol["rows"]),
            *zip(names, err["grads_rel_err"], tol["grads"], strict=True))
            if not e <= t]
        return {"ok": err["finite"] and not over, "refused_by": over,
                "dtype": self.dtype, "positions": int(tokens.shape[1]),
                "grad_positions": int(grad_tokens.shape[1]),
                "grad_leaves": names, **err,
                "rows_rel_err_by_layer": by_layer,
                "loss": float(loss), "loss_ref": float(ref_loss),
                "logits_tol": tol["logits"], "loss_tol": tol["loss"],
                "rows_tol": tol["rows"], "grads_tol": list(tol["grads"])}

    def check(self) -> dict:
        import jax

        inputs = self.check_inputs()
        verdict = self.compare(*inputs, *self.system_results(*inputs))
        held = check_lm.held_precision(self.dtype, self.solver.train_net,
                                       inputs[0], self.batch, self.positions)
        # the check's programs go: a loaded program keeps its temporaries
        # reserved, and the step needs the room
        jax.clear_caches()
        return {**verdict, "ok": verdict["ok"] and held["ok"],
                "precision": held}

    def control(self, name: str) -> dict:
        """The comparison's verdict on a result it has to refuse, on this
        seed's weights and tokens and at the check's own sizes, as the
        configuration's ``check.controls`` describes it.
        ``reference_operands``: the reference with both operands of every
        product rounded to that type stands in the program's place (the
        nearest precision below the one the mix states).
        ``reference_without``: the program's own results are held against
        the reference with those blobs zeroed, which is a program that left
        the mechanism out held against the right reference."""
        import jax.numpy as jnp

        spec = self.cfg["check"]["controls"][name]
        params, *rest = self.check_inputs()
        if "reference_operands" in spec:
            return self.compare(params, *rest, *self.reference_results(
                params, *rest, jnp.dtype(spec["reference_operands"])))
        without = {k: list(v) for k, v in params.items()}
        for layer, i in spec["reference_without"]:
            without[layer][i] = jnp.zeros_like(without[layer][i])
        return self.compare(without, *rest,
                            *self.system_results(params, *rest))
