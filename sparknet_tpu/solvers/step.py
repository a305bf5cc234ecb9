"""The single-step update pipeline shared by the host Solver and the
distributed trainers.

One authoritative implementation of: forward+backward (with BatchNorm
forward-state aux) → ClipGradients → Normalize → Regularize → rule update —
the ``Solver::Step`` inner body + ``ApplyUpdate`` sequence (reference:
caffe/src/caffe/solver.cpp:221-262, solvers/sgd_solver.cpp:102-143).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.net import Net
from ..proto.caffe_pb import SolverParameter
from .lr_policies import learning_rate
from .update_rules import SolverUpdate, preprocess_grads

# The step's phases beside the layers', in the one namespace a profiler
# trace is read by (``L[<layer>]``, graph/net.py): what the step does to its
# input before the net sees it and to the micro-batches' sum; the gradient
# preparation; the rate and the rule's update.
INPUT_SCOPE = "L[step.input]"
GRADS_SCOPE = "L[step.grads]"
UPDATE_SCOPE = "L[step.update]"


def apply_update(sp: SolverParameter, rule: SolverUpdate, params, grads,
                 state, it, lr_scale, lr_mults, decay_mults):
    """ClipGradients → Normalize → Regularize → the rule's update at the
    policy's rate times ``lr_scale``: ``(params, state)``."""
    with jax.named_scope(GRADS_SCOPE):
        grads = preprocess_grads(sp, params, grads, lr_mults, decay_mults)
    with jax.named_scope(UPDATE_SCOPE):
        rate = learning_rate(sp, it) * lr_scale
        return rule.apply(params, grads, state, rate, it, lr_mults=lr_mults)


def make_step_fns(sp: SolverParameter, net: Net, rule: SolverUpdate,
                  lr_mults, decay_mults, remat: bool = False,
                  in_scan: bool = False):
    """Returns (loss_and_grads, local_update, accum_loss_and_grads):

    - ``loss_and_grads(params, batch, rng) -> (loss, params_with_bn, grads)``
    - ``local_update(params, state, it, batches, rng, lr_scale=1.0) ->
      (params, state, loss)`` — one full solver step over
      [iter_size, batch, ...] feeds; ``lr_scale`` multiplies the policy
      rate (the numerical-integrity guard's LR-backoff channel — a
      traced scalar, so changing it does not recompile)
    - ``accum_loss_and_grads(params, batches, rng) -> (loss, params, grads)``
      — the ``iter_size`` micro-batch accumulation of ``Solver::Step``
      (reference: solver.cpp:221-224), raw summed grads (normalization by
      iter_size happens in ``preprocess_grads``)

    ``remat=True`` wraps the forward in ``jax.checkpoint`` so the backward
    recomputes activations instead of storing them — trades FLOPs for HBM
    on memory-bound configs (big batches / VGG-class activation volumes).
    ``in_scan=True`` (the DistributedTrainer, whose round bodies live in
    ``lax.scan``) drops the CSE-prevention barriers — scan already keeps
    XLA from undoing the rematerialization, and the barriers only block
    fusion there (jax.checkpoint docs' prevent_cse guidance).
    """

    def raw_fwd(p, batch, rng):
        out = net.apply(p, batch, train=True, rng=rng)
        return out.loss, out.params

    if remat:
        fwd = jax.checkpoint(raw_fwd, prevent_cse=not in_scan)
        fwd_in_scan = jax.checkpoint(raw_fwd, prevent_cse=False)
    else:
        fwd = fwd_in_scan = raw_fwd

    def loss_and_grads(params, batch, rng):
        (loss, new_params), grads = jax.value_and_grad(
            fwd, has_aux=True)(params, batch, rng)
        return loss, new_params, grads

    def accum_loss_and_grads(params, batches, rng):
        """``batches`` leaves carry a leading iter_size axis."""
        if sp.iter_size == 1:
            with jax.named_scope(INPUT_SCOPE):
                batch = jax.tree_util.tree_map(lambda x: x[0], batches)
            return loss_and_grads(params, batch, rng)

        # the scan's carry and the sum over micro-batches are the step's;
        # the scan itself stays under no scope (the compiler names what it
        # makes inside a loop after the loop)
        def body(carry, batch):
            params, acc, rng = carry
            with jax.named_scope(INPUT_SCOPE):
                rng, sub = jax.random.split(rng)
            (loss, params), g = jax.value_and_grad(
                fwd_in_scan, has_aux=True)(params, batch, sub)
            with jax.named_scope(INPUT_SCOPE):
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (params, acc, rng), loss

        with jax.named_scope(INPUT_SCOPE):
            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (params, grads, _), losses = jax.lax.scan(
            body, (params, zero, rng), batches)
        with jax.named_scope(INPUT_SCOPE):
            return jnp.mean(losses), params, grads

    def local_update(params, state, it, batches, rng, lr_scale=1.0):
        loss, params, grads = accum_loss_and_grads(params, batches, rng)
        params, state = apply_update(sp, rule, params, grads, state, it,
                                     lr_scale, lr_mults, decay_mults)
        return params, state, loss

    return loss_and_grads, local_update, accum_loss_and_grads
