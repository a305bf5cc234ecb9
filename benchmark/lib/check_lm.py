"""The comparison that decides ``correct`` for a token cell.

Each run, outside the measured window and on the weights the seed gave:

- the system's own TRAIN net (its ``Net``, cast policy, kernels and
  recomputation; the net ``Solver.step`` differentiates) runs one sequence
  of the timed length forward, and its logits and loss are held against the
  plain float32 reference (``reference_lm.py``): ``logits`` bounds the
  largest absolute difference over the largest absolute reference logit,
  ``loss`` the absolute difference of the mean cross-entropy;
- the gradients of four leaves (a router, one held expert's ``W_down``, an
  attention gate ``W_g``, layer 0's ``W_q``) on a sequence of
  ``GRAD_POSITIONS`` positions are held against ``jax.grad`` of the
  reference's loss: ``grads`` bounds ``|g - g_ref| / |g_ref|`` (Frobenius)
  of each;
- ``held_precision`` reads the types: the run fails where a weight is
  stored, or a matrix product or Pallas kernel of the train net is fed, in
  a type with fewer mantissa bits than the mix states (``lib/check.py``
  says why the numbers alone cannot tell float32 storage from bfloat16).

The driver adds that no expert layer left a row out and that the fetched
losses are finite and do not rise (``harness.losses_ok``).

Tolerances (bfloat16: float32 master weights, every blob and product
operand rounded to 8 bits of mantissa, float32 accumulation, router scores
and softmaxes in float32) are set between two readings on the chip
(TPU v5 lite, libtpu 0.0.34; PERF.md, Findings, PR 35), at about three
times the largest the system gave:

| | system, largest over 5 seeds | reference in ``float8_e4m3fn`` | limit |
| logits | 0.105 (0.072 to 0.105) | 0.099 | 0.25 |
| loss | 2.7e-4 (3.0e-5 to 2.7e-4) | 6.5e-4 | 1.0e-3 |
| grads: router, expert W_down | 0.129, 0.159 | 1.004, 1.000 | 0.4, 0.5 |
| grads: gate W_g, layer 0 W_q | 0.022, 0.024 | 1.001, 1.000 | 0.07, 0.07 |

The float8 reading is the reference with the operands of every product
rounded to ``float8_e4m3fn``, the nearest precision below, against the
same reference in float32: it is refused by each of the four gradient
limits (its smallest normal number is 2^-6, so the backward pass's small
cotangents vanish and nothing of the gradient is left), and by neither
the logits nor the loss.

What is particular to a router, and why those two cannot tell: a bfloat16
error of a few thousandths in a router's input moves a score across the
gap between a token's 8th and 9th expert for a few tokens in a hundred,
and such a token then adds another expert's output.  Its logits are then
off by far more than rounding alone would put them, whatever the
precision of the router's own product (the reference with bfloat16
operands alone reads 0.082), so the largest logit error over a sequence is
set by those tokens in any precision; the loss averages them away in any
precision too.  The same tokens are why the router's and an expert's
gradients, which a few dozen tokens of 1,024 make, read 0.12 to 0.16 where
the dense leaves read 0.02.
"""

from __future__ import annotations

import dataclasses

from . import reference_lm
from .check import _FED, _equations

# "grads" is a limit a leaf, in the order of ``grad_leaves``: the router,
# a held expert's W_down, an attention gate, layer 0's W_q
TOLERANCE = {
    "bfloat16": {"logits": 0.25, "loss": 1.0e-3,
                 "grads": (0.4, 0.5, 0.07, 0.07)},
    "float32": {"logits": 1.0e-3, "loss": 1.0e-4,
                "grads": (1.0e-2, 1.0e-2, 1.0e-2, 1.0e-2)},
}

GRAD_POSITIONS = 1024


def grad_leaves(net_param) -> list[tuple[str, int]]:
    """(layer, blob) of the four leaves whose gradients are compared."""
    moe = next(lp.name for lp in net_param.layer
               if lp.type == "MixtureOfExperts")
    attn = [lp.name for lp in net_param.layer if lp.type == "Attention"]
    return [(moe, 0), (moe, 3), (attn[min(1, len(attn) - 1)], 3),
            (attn[0], 0)]


def with_logits(net_param):
    """The train net with the head's logits as a second top."""
    layers = [dataclasses.replace(lp, top=[*lp.top, "logits"])
              if lp.type == "LMHeadLoss" else lp for lp in net_param.layer]
    return dataclasses.replace(net_param, layer=layers)


def seeded_tokens(key, sequences: int, positions: int, vocab: int):
    import jax
    return jax.random.randint(key, (sequences, positions), 0, vocab)


def _train_net(net_param, compute_dtype):
    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.proto.caffe_pb import NetState, Phase
    return Net(net_param, NetState(Phase.TRAIN), compute_dtype=compute_dtype)


def system_forward(net_param, params, tokens, compute_dtype=None):
    """(logits [S, vocab], loss) of the system's train net on one
    sequence ``tokens [1, S]``."""
    import jax
    net = _train_net(with_logits(net_param), compute_dtype)

    @jax.jit
    def fwd(p, t):
        out = net.apply(p, {"tokens": t}, train=True)
        return out.blobs["logits"][0], out.loss

    return fwd(params, tokens)


def _grad_of_leaves(loss, leaves):
    """``loss(params, tokens)`` differentiated with respect to ``leaves``
    alone: a function of (the picked leaves, params, tokens)."""
    import jax

    def of_picked(picked, params, tokens):
        params = {k: list(v) for k, v in params.items()}
        for (name, i), leaf in zip(leaves, picked):
            params[name][i] = leaf
        return loss(params, tokens)

    return jax.grad(of_picked)


def system_grads(net_param, params, tokens, leaves, compute_dtype=None):
    """Gradients of the train net's loss on ``tokens [1, S]`` with respect
    to ``leaves``."""
    import jax
    net = _train_net(net_param, compute_dtype)
    grad = _grad_of_leaves(
        lambda p, t: net.apply(p, {"tokens": t}, train=True).loss, leaves)
    return jax.jit(grad)([params[name][i] for name, i in leaves], params,
                         tokens)


def reference_grads(params, tokens, leaves, m, dtype=None):
    grad = _grad_of_leaves(
        lambda p, t: reference_lm.loss(p, t, m, dtype), leaves)
    return reference_lm.highest(grad)(
        [params[name][i] for name, i in leaves], params, tokens)


def held_precision(dtype: str, net, params, sequences: int,
                   positions: int) -> dict:
    """``check.held_precision`` for a net whose input is token ids."""
    import jax
    import jax.numpy as jnp

    traced = jax.make_jaxpr(
        lambda p, t: net.apply(p, {"tokens": t}, train=True).loss)(
            params, jax.ShapeDtypeStruct((sequences, positions), jnp.int32))
    fed = {v.aval.dtype for eqn in _equations(traced.jaxpr)
           if eqn.primitive.name in _FED for v in eqn.invars
           if jnp.issubdtype(v.aval.dtype, jnp.floating)}
    stored = {x.dtype for x in jax.tree_util.tree_leaves(params)
              if jnp.issubdtype(x.dtype, jnp.floating)}
    need = jnp.finfo(jnp.dtype(dtype)).nmant
    ok = bool(fed) and all(jnp.finfo(d).nmant >= need for d in fed | stored)
    return {"ok": ok, "stated": dtype,
            "products_fed": sorted(str(d) for d in fed),
            "params_stored": sorted(str(d) for d in stored)}


def errors(logits, loss, grads, ref_logits, ref_loss, ref_grads) -> dict:
    """The three compared numbers, from the system's (or a lower
    precision's) results and the reference's."""
    import numpy as np
    ref_logits = np.asarray(ref_logits, np.float32)
    got = np.asarray(logits, np.float32)
    out = {"logits_rel_err": float(np.max(np.abs(got - ref_logits))
                                   / np.max(np.abs(ref_logits))),
           "loss_abs_err": abs(float(loss) - float(ref_loss)),
           "finite": bool(np.isfinite(got).all())}
    rel = []
    for g, r in zip(grads, ref_grads):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        if r.ndim == 3:             # the experts' stack: the first held one
            g, r = g[0], r[0]
        rel.append(float(np.linalg.norm(g - r) / np.linalg.norm(r)))
    out["grads_rel_err"] = rel
    return out


def compare(dtype: str, config: dict, params, tokens, grad_tokens, leaves,
            logits, loss, grads) -> dict:
    """Run the reference on the same weights and tokens and hold the
    system's results against it."""
    m = reference_lm.model(config)
    ref_logits = reference_lm.highest(
        lambda p, t: reference_lm.logits(p, t, m))(params, tokens[0])
    ref_loss = reference_lm.highest(
        lambda p, t: reference_lm.loss(p, t, m))(params, tokens)
    ref_grads = reference_grads(params, grad_tokens, leaves, m)
    err = errors(logits, loss, grads, ref_logits, ref_loss, ref_grads)
    tol = TOLERANCE[dtype]
    ok = (err["finite"] and err["logits_rel_err"] <= tol["logits"]
          and err["loss_abs_err"] <= tol["loss"]
          and all(e <= t for e, t in zip(err["grads_rel_err"],
                                         tol["grads"])))
    return {"ok": bool(ok), "dtype": dtype, "positions": int(tokens.shape[1]),
            "grad_positions": int(grad_tokens.shape[1]),
            "grad_leaves": [f"{n}/{i}" for n, i in leaves],
            **err, "loss": float(loss), "loss_ref": float(ref_loss),
            "logits_tol": tol["logits"], "loss_tol": tol["loss"],
            "grads_tol": list(tol["grads"])}
