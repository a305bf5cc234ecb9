"""The comparison that decides ``correct``.

Each run, outside the measured window, the system's test-phase forward on
its seeded weights is compared with the plain float32 reference
(``reference.py``) on a seeded batch: the logits and the loss.  A training
run is also correct only if its fetched losses stay finite and do not rise
(``harness.losses_ok``).

Tolerances.  ``logits`` bounds the largest absolute difference over the
largest absolute reference logit; ``loss`` bounds the absolute difference
of the mean softmax loss.  They are set from what the chip measured in
PR 22 (TPU v5 lite, libtpu 0.0.34; PERF.md, Findings), with about three
times the largest error seen as room:

- ``bfloat16`` (``Solver(compute_dtype=bfloat16)``): weights and
  activations are rounded to 8 bits of mantissa in every layer, float32
  accumulation.  Measured: logits 3.0e-3 to 5.4e-3 over 49 runs of CaffeNet
  (8 weighted layers), 4.7e-3 to 7.3e-3 over 14 runs of GoogLeNet (22
  deep); loss up to 2.5e-3.  An 8-bit float (3 or 4 bits of mantissa) is
  some 16 times coarser and would be out by 5e-2 and more.
- ``float32`` (``DistributedTrainer``, which has no compute dtype): float32
  storage and accumulation, and on the TPU the default matrix precision,
  which rounds the operands of each product to bfloat16.  Measured on four
  chips: logits 2.8e-3 to 3.5e-3, loss 3e-5 to 5.3e-4, over 10 runs of
  CaffeNet.

What the numbers cannot tell apart, the types do.  On the chip a net that
stores float32 and one that stores bfloat16 feed the same bfloat16
operands to every product, so their logits are equally far from the
reference (2.8e-3 to 3.5e-3 against 3.0e-3 to 5.4e-3), and the loss of the
bfloat16 one (up to 1.8e-3 on CaffeNet) is outside the float32 tolerance
in some runs only.  No tolerance on 8 or 32 images separates them in every
run.  So ``held_precision`` reads the types themselves: it traces the
system's own train net (the one ``describe`` takes the plan ids from, and
the one the compiled step or round applies) and fails the run where a
weight is stored, or a convolution, matrix product or Pallas kernel is
fed, in a type with fewer mantissa bits than the cell states.  A cell that
states float32 fails the moment the net computes in bfloat16; one that
states bfloat16 keeps float32 master weights and fails on an 8-bit float.
What it cannot see is a cast made around the net, outside
``train_net.apply`` (PERF.md, Open questions).
"""

from __future__ import annotations

import dataclasses

TOLERANCE = {
    "bfloat16": {"logits": 2.0e-2, "loss": 1.0e-2},
    "float32": {"logits": 1.0e-2, "loss": 1.5e-3},
}

# How far the last fetched loss may stand above the first.  Labels are
# random, so the loss starts near ln(classes) and creeps down; dropout and
# the crop make single fetches wander by a few hundredths.
LOSS_MARGIN = 0.25

CHECK_BATCH = 8     # images per chip; XLA's TPU compiler refuses CaffeNet
#                     below 8 (PERF.md, PR 21)


def logits_net_param(net_param):
    """The net without its loss and accuracy layers, so that the system's
    own forward returns the logits as its output blob."""
    return dataclasses.replace(net_param, layer=[
        lp for lp in net_param.layer
        if lp.type not in ("SoftmaxWithLoss", "Accuracy")])


def center_crop(raw, mean, crop: int):
    """uint8 [n, c, e, e] -> float32 [n, c, crop, crop], mean subtracted
    per channel: what the test phase of the data layer does."""
    import jax.numpy as jnp
    off = (raw.shape[-1] - crop) // 2
    x = raw.astype(jnp.float32) - jnp.asarray(mean, jnp.float32).reshape(
        1, -1, 1, 1)
    return x[:, :, off:off + crop, off:off + crop]


def seeded_batch(key, inp: dict, n: int) -> dict:
    """``n`` test-phase inputs from ``key``: raw uint8 images of the
    configuration's size, centre-cropped with the means subtracted, and
    labels, as host arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    kd, kl = jax.random.split(key)
    e, c = int(inp["raw_edge"]), int(inp["channels"])
    raw = jax.random.bits(kd, (n, c, e, e), jnp.uint8)
    label = jax.random.randint(kl, (n,), 0, int(inp["classes"]))
    return {"data": np.asarray(center_crop(raw, inp["mean"],
                                           int(inp["crop"]))),
            "label": np.asarray(label, np.float32)}


def system_logits(net_param, params, batch, blob: str, compute_dtype=None):
    """The logits of the system's own test-phase forward (its ``Net``,
    fusion plan and kernels), from host or single-device arrays, so that
    it runs on one chip: a jit over arrays replicated on a mesh would be
    partitioned over it, which a Pallas kernel outside a ``shard_map``
    cannot be."""
    import jax

    from sparknet_tpu.graph.net import Net
    from sparknet_tpu.proto.caffe_pb import NetState, Phase

    net = Net(logits_net_param(net_param), NetState(Phase.TEST),
              compute_dtype=compute_dtype)
    return jax.jit(lambda p, b: net.apply(p, b, train=False).blobs[blob])(
        params, batch)


_FED = ("conv_general_dilated", "dot_general", "pallas_call")


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (a jit, a scan, a custom gradient, a kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in _jaxprs_in(value):
                yield from _equations(sub)


def _jaxprs_in(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(getattr(value, "jaxpr", None), "eqns"):
        yield value.jaxpr
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_in(v)


def held_precision(dtype: str, net, params, batch: int, inp: dict) -> dict:
    """Whether the system's train ``net`` stores and multiplies in no
    narrower a type than the ``dtype`` the cell states: the floating types
    of ``params``' leaves, and of the operands of every convolution,
    matrix product and Pallas kernel in a trace of one training forward at
    ``batch`` cropped images.  Nothing runs."""
    import jax
    import jax.numpy as jnp

    crop, c = int(inp["crop"]), int(inp["channels"])
    inputs = {"data": jax.ShapeDtypeStruct((batch, c, crop, crop),
                                           jnp.float32),
              "label": jax.ShapeDtypeStruct((batch,), jnp.float32)}
    traced = jax.make_jaxpr(
        lambda p, b, rng: net.apply(p, b, train=True, rng=rng).loss)(
            params, inputs, jax.random.PRNGKey(0))
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


def compare(dtype: str, test_net_param, params, batch, logits, loss,
            logits_blob: str, loss_blob: str) -> dict:
    """Run the reference on ``batch`` with ``params`` and hold the
    system's ``logits`` and ``loss`` against it."""
    import jax
    import numpy as np

    from . import reference

    ref = jax.jit(lambda p, b: {
        k: v for k, v in reference.forward(test_net_param, p, b).items()
        if k in (logits_blob, loss_blob)})(params, batch)
    ref_logits = np.asarray(ref[logits_blob], np.float32)
    got = np.asarray(logits, np.float32).reshape(ref_logits.shape)
    scale = float(np.max(np.abs(ref_logits)))
    logit_err = float(np.max(np.abs(got - ref_logits))) / scale
    loss_err = abs(float(loss) - float(ref[loss_blob]))
    tol = TOLERANCE[dtype]
    ok = (np.isfinite(got).all() and logit_err <= tol["logits"]
          and loss_err <= tol["loss"])
    return {"ok": bool(ok), "dtype": dtype, "images": int(got.shape[0]),
            "logits_rel_err": logit_err, "logits_tol": tol["logits"],
            "loss": float(loss), "loss_ref": float(ref[loss_blob]),
            "loss_abs_err": loss_err, "loss_tol": tol["loss"]}
