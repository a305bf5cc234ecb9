"""The state-space mixer: Mamba-2's selective scan, in its chunked form.

``StateSpaceMixer`` (``state_space_param``) carries a state along the
positions, ``heads`` heads of ``head_dim`` (P) over ``groups`` groups of
``state`` (N) channels, head ``h`` reading group ``h // (heads / groups)``:

- ``z, x, B, C, dt = h W_z, h W_x, h W_B, h W_C, h W_dt`` (the published
  ``in_proj``'s five parts in its order, five blobs of one product's
  columns), no bias;
- ``[x | B | C] <- silu(conv(x | B | C) + b)``, a depthwise causal
  convolution of ``conv_kernel`` taps over the positions (scope
  ``ssm_conv``): on a TPU two Pallas kernels a tensor, one a pass, in its
  own layout (``ssm_kernels.conv_act``, chosen and counted by
  ``ssm_conv_lowering`` in ``ssm_conv_lowering_total{path, blocks}``),
  ``_conv_act``'s ``jax.numpy`` elsewhere (``path=xla``, also the kernels'
  reference);
- ``Δ = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; the state
  ``s_t = exp(Δ_t A) s_{t-1} + Δ_t x_t ⊗ B_t`` (``[P, N]`` a head) and
  ``y_t = s_t C_t + D x_t`` (scope ``ssm_scan``);
- ``RMSNorm_grouped(y * silu(z))``: gated first, then each of ``groups``
  groups of channels normalised (``norm_eps``), then a weight (scope
  ``ssm_norm``);
- ``W_out``, no bias.

The scan is computed in the chunked form (``ssd_scan``): the positions in
chunks of ``chunk``, four matrix products (within a chunk ``(C Bᵀ ∘ L) x``;
each chunk's state ``Bᵀ (decay ∘ x)``; the states passed between chunks;
``C`` times the state a chunk starts from) around decays ``L =
exp(segsum(Δ A))`` in float32.  On a TPU, where the chunk and the state are
whole lane rows, two Pallas kernels (``ssm_kernels``) do it a pass, each
chunk's decays and products kept in VMEM and the state carried across
chunks (``path=pallas``); elsewhere ``jax.numpy`` products with the decays
inside XLA's fusions (``path=chunked``, also the kernels' reference).
``ssm_lowering`` chooses and counts it once a trace in
``ssm_lowering_total{path, chunk, heads, state, blocks}``.  The products
take the compute dtype with float32 accumulation.  Heads-major throughout,
each tensor pinned where a product makes it: ``x``, ``z`` and ``y`` are
``[heads, head_dim, positions]``, the positions on the lanes (a head of 64
on the lanes would fill half of each lane row, and every such array would
take twice its size: 2.46 GB against 1.35 for one sequence's forward and
backward at the published widths), and ``B`` and ``C`` ``[groups,
positions, state]``, the state's 128 on the lanes; within a chunk every
product then contracts or writes 128 lanes.  A sequence at a time,
recomputed in the backward pass, as every sequence layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils import telemetry
from . import ssm_kernels
from .fillers import fill
from .registry import LayerImpl, register_layer
from .sequence import _filler, _heads_major, _per_sequence, causal_taps


def ssm_geometry(lp) -> dict:
    p = lp.sub("state_space_param")
    return dict(heads=int(p.get("heads", 0)),
                head_dim=int(p.get("head_dim", 0)),
                groups=int(p.get("groups", 1)),
                state=int(p.get("state", 0)),
                kernel=int(p.get("conv_kernel", 4)),
                chunk=int(p.get("chunk", 128)),
                eps=float(p.get("norm_eps", 1e-5)))


def ssm_lowering(g: dict, positions: int, itemsize: int) -> str:
    """Which lowering the scan of one sequence of ``positions`` takes, of
    operands of ``itemsize`` bytes, on this backend; counted once a trace
    in ``ssm_lowering_total``.  On a TPU, where the chunk and the state are
    whole lane rows, the head a whole sublane tile and a block of one
    group's heads fits (``ssm_kernels.scan_blocks``), the two Pallas kernels
    (``path=pallas``, whose sample carries the ``blocks``); the chunked form
    of ``jax.numpy`` products elsewhere (``path=chunked``)."""
    blocks = None
    if (jax.default_backend() == "tpu" and g["chunk"] % 128 == 0
            and g["state"] % 128 == 0 and g["head_dim"] % 8 == 0):
        blocks = ssm_kernels.scan_blocks(
            positions, g["heads"], g["head_dim"], g["groups"], g["state"],
            g["chunk"], itemsize)
    path = "pallas" if blocks else "chunked"
    telemetry.get_registry().counter(
        "ssm_lowering_total", "traces of a state-space mixer's scan").inc(
            path=path, chunk=g["chunk"], heads=g["heads"], state=g["state"],
            **({"blocks": blocks.label()} if blocks else {}))
    return path


def ssm_conv_lowering(g: dict, positions: int, itemsize: int) -> str:
    """Which lowering the causal convolution, its bias and SiLU take over
    one sequence of ``positions``, operands of ``itemsize`` bytes, on this
    backend; counted once a trace in ``ssm_conv_lowering_total``.  On a
    TPU, where the positions are whole lane rows and the kernels can block
    both x (positions on the lanes, blocks of whole packed sublane tiles of
    channels) and B and C (positions on the sublanes) with the taps inside
    the tile beside a block (``ssm_kernels.conv_blocks``), the two Pallas
    kernels (``path=pallas``, whose sample carries the ``blocks`` of x and
    of B and C); XLA's ``_conv_act`` elsewhere (``path=xla``)."""
    blocks = None
    if jax.default_backend() == "tpu" and positions % 128 == 0:
        blocks = _conv_blocks(g, positions, itemsize)
    path = "pallas" if blocks and all(blocks) else "xla"
    telemetry.get_registry().counter(
        "ssm_conv_lowering_total",
        "traces of a state-space mixer's causal convolution").inc(
            path=path, **({"blocks": "x {} bc {}".format(
                *(b.label() for b in blocks))} if path == "pallas" else {}))
    return path


def _conv_blocks(g: dict, positions: int, itemsize: int):
    """The convolution kernels' blocks for x ``[H * P, S]`` and for B and C
    ``[G, S, N]``."""
    return (ssm_kernels.conv_blocks(positions, g["heads"] * g["head_dim"],
                                    g["kernel"], itemsize, True),
            ssm_kernels.conv_blocks(positions, g["state"], g["kernel"],
                                    itemsize, False))


def _pass_states(states, ends, starts):
    """The state each chunk starts from: ``states [..., chunks, P, N]``
    (what each chunk adds, at its end) carried over the chunks between, a
    chunk's decay ``exp(ends - starts)`` a step.  One product over the
    chunks with the decays ``exp(starts[z] - ends[c])`` of ``c < z`` in
    float32 inside its fusion."""
    n = states.shape[-3]
    before = jnp.arange(n)[:, None] > jnp.arange(n)[None, :]
    carry = jnp.exp(jnp.where(before, starts[..., :, None]
                              - ends[..., None, :], -jnp.inf))
    cd = states.dtype
    return jnp.einsum("...zc,...cpn->...zpn", carry.astype(cd), states,
                      preferred_element_type=jnp.float32)


def ssd_scan(x, dt, a, b, c, d, chunk: int, path: str = "chunked"):
    """``y_t = s_t C_t + D x_t`` with ``s_t = exp(Δ_t A) s_{t-1} + Δ_t x_t ⊗
    B_t`` of one sequence, in the chunked form: by the Pallas kernels of
    ``ssm_kernels`` where ``path`` is ``pallas``, by ``jax.numpy`` products
    otherwise.  ``x [H, P, S]``, ``b`` and ``c [G, S, N]`` in the compute
    dtype; ``dt`` (Δ) ``[H, S]``, ``a`` (A) and ``d`` (D) ``[H]`` in
    float32.  Returns ``y [H, P, S]`` float32.  A length that is not a
    multiple of ``chunk`` is padded with Δ = 0 in the last chunk: no decay,
    no input."""
    heads, p, s = x.shape
    groups, _, n = b.shape
    cd, f32 = x.dtype, jnp.float32
    pad = -s % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
        b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (b, c))
        dt = jnp.pad(dt, ((0, 0), (0, pad)))
    if path == "pallas":
        blocks = ssm_kernels.scan_blocks(s + pad, heads, p, groups, n, chunk,
                                         jnp.dtype(cd).itemsize)
        y = ssm_kernels.scan(x, dt, a, b, c, chunk, blocks)[..., :s]
        return y + d[:, None, None] * x[..., :s].astype(f32)
    nc = (s + pad) // chunk
    xq = x.reshape(groups, heads // groups, p, nc, chunk)
    bq, cq = (t.reshape(groups, nc, chunk, n) for t in (b, c))
    dtq = dt.reshape(groups, heads // groups, nc, chunk)
    cs = jnp.cumsum(dtq * a.reshape(groups, -1, 1, 1), axis=-1)
    # within a chunk: y_i = sum over j <= i of C_i.B_j exp(cs_i - cs_j) Δ_j x_j
    seen = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))
    cb = jnp.einsum("gcin,gcjn->gcij", cq, bq, preferred_element_type=f32)
    m = (cb[:, None] * decay * dtq[..., None, :]).astype(cd)
    y = jnp.einsum("ghcij,ghpcj->ghpci", m, xq, preferred_element_type=f32)
    # what each chunk adds to the state by its end, and where each starts
    last = cs[..., -1:]
    xw = (xq * (jnp.exp(last - cs) * dtq)[:, :, None]).astype(cd)
    states = jnp.einsum("gcjn,ghpcj->ghcpn", bq, xw,
                        preferred_element_type=f32)
    ends = jnp.cumsum(cs[..., -1], axis=-1)
    entering = _pass_states(states.astype(cd), ends, ends - cs[..., -1])
    y = y + jnp.einsum("gcin,ghcpn->ghpci", cq, entering.astype(cd),
                       preferred_element_type=f32) * jnp.exp(cs)[:, :, None]
    y = y.reshape(heads, p, s + pad)[..., :s]
    return y + d[:, None, None] * x[..., :s].astype(f32)


def _conv_act(t, taps, bias, positions_last: bool = False):
    """``silu(conv(t) + bias)`` over the positions of ``t [..., S, W]`` (or
    ``[..., W, S]``), taps ``[..., W, K]``, bias ``[..., W]``; float32
    inside."""
    f32 = jnp.float32
    bias = bias[..., None] if positions_last else bias[..., None, :]
    acc = (causal_taps(t.astype(f32), taps.astype(f32), positions_last)
           + bias.astype(f32))
    return jax.nn.silu(acc).astype(t.dtype)


def conv_act(t, taps, bias, positions_last: bool, path: str,
             blocks: ssm_kernels.ConvBlocks | None = None):
    """``silu(conv(t) + bias)`` as ``_conv_act`` gives it: by the Pallas
    kernels of ``ssm_kernels`` in ``blocks`` where ``path`` is ``pallas``,
    by ``_conv_act`` otherwise."""
    if path != "pallas":
        return _conv_act(t, taps, bias, positions_last)
    f32 = jnp.float32
    return ssm_kernels.conv_act(t, taps.astype(f32), bias.astype(f32),
                                positions_last, blocks)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """``RMSNorm`` of ``y * silu(z)`` over each of ``groups`` groups of
    heads (their channels together), times ``w``: ``y, z [H, P, S]``, ``w
    [H * P]``; float32 inside, ``z``'s dtype out."""
    heads, p, s = z.shape
    f32 = jnp.float32
    u = (y.astype(f32) * jax.nn.silu(z.astype(f32))).reshape(
        groups, heads // groups, p, s)
    ms = jnp.mean(u * u, axis=(1, 2), keepdims=True)
    u = (u * jax.lax.rsqrt(ms + eps)).reshape(heads, p, s)
    return (u * w.reshape(heads, p, 1).astype(f32)).astype(z.dtype)


@register_layer("StateSpaceMixer")
class StateSpaceMixerLayer(LayerImpl):
    """Mamba-2's mixer (``state_space_param``; the module's docstring has
    its equations).  Blobs: W_z, W_x ``[hidden, heads x head_dim]``, W_B,
    W_C ``[hidden, groups x state]``, W_dt ``[hidden, heads]``; the taps
    ``[conv_dim, conv_kernel]`` (``kernel_filler``) and their bias
    ``[conv_dim]`` (``bias_filler``), ``conv_dim`` the channels of x, B and
    C in that order; dt_bias, A_log, D ``[heads]``; the norm's weight
    ``[heads x head_dim]``; W_out ``[heads x head_dim, hidden]``.  The
    family's own fillers: ``A_log = log(1..heads)``, ``D = 1``, the norm 1,
    ``dt_bias`` the inverse softplus of Δ log-uniform on ``[dt_min,
    dt_max]`` and at least ``dt_floor``."""

    def init(self, rng, lp, bottom_shapes):
        p, g = lp.sub("state_space_param"), ssm_geometry(lp)
        hidden = bottom_shapes[0][-1]
        heads, inner = g["heads"], g["heads"] * g["head_dim"]
        gn = g["groups"] * g["state"]
        conv_dim = inner + 2 * gn
        r = jax.random.split(rng, 9)
        wf = _filler(p)
        lo, hi = (math.log(float(p.get("dt_min", 1e-3))),
                  math.log(float(p.get("dt_max", 0.1))))
        dt = jnp.maximum(jnp.exp(jax.random.uniform(r[8], (heads,),
                                                    minval=lo, maxval=hi)),
                         float(p.get("dt_floor", 1e-4)))
        return [fill(r[0], wf, (hidden, inner)),
                fill(r[1], wf, (hidden, inner)),
                fill(r[2], wf, (hidden, gn)),
                fill(r[3], wf, (hidden, gn)),
                fill(r[4], wf, (hidden, heads)),
                fill(r[5], _filler(p, "kernel_filler"),
                     (conv_dim, g["kernel"])),
                fill(r[6], _filler(p, "bias_filler"), (conv_dim,)),
                dt + jnp.log(-jnp.expm1(-dt)),
                jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
                jnp.ones((heads,), jnp.float32),
                jnp.ones((inner,), jnp.float32),
                fill(r[7], wf, (inner, hidden))]

    def apply(self, lp, params, bottoms, train, rng):
        g = ssm_geometry(lp)
        (wz, wx, wb, wc, wdt, taps, conv_b, dt_bias, a_log, d, norm_w,
         w_out) = params
        heads, p, groups, n = g["heads"], g["head_dim"], g["groups"], g["state"]
        itemsize = jnp.result_type(bottoms[0], wx).itemsize
        positions = bottoms[0].shape[1]
        path = ssm_lowering(g, positions, itemsize)
        conv = ssm_conv_lowering(g, positions, itemsize)
        xb, bcb = (_conv_blocks(g, positions, itemsize) if conv == "pallas"
                   else (None, None))
        hidden, inner = wx.shape[0], heads * p
        wz, wx = wz.reshape(hidden, heads, p), wx.reshape(hidden, heads, p)
        wb, wc = (w.reshape(hidden, groups, n) for w in (wb, wc))
        w_out = w_out.reshape(heads, p, hidden)
        # the taps and their bias of x, B and C, each in its tensor's shape
        cuts = (inner, inner + groups * n)
        tx, tb, tc = jnp.split(taps, cuts)
        bx, bb, bc = jnp.split(conv_b, cuts)
        tx, bx = tx.reshape(heads, p, -1), bx.reshape(heads, p)
        tb, tc = (t.reshape(groups, n, -1) for t in (tb, tc))
        bb, bc = (t.reshape(groups, n) for t in (bb, bc))
        f32 = jnp.float32

        def one(h):
            z = _heads_major(jnp.einsum("sd,dhp->hps", h, wz))
            x = _heads_major(jnp.einsum("sd,dhp->hps", h, wx))
            b = _heads_major(jnp.einsum("sd,dgn->gsn", h, wb))
            c = _heads_major(jnp.einsum("sd,dgn->gsn", h, wc))
            # Δ's product out in float32: its rounding would reach every
            # decay of the head
            dt = jnp.einsum("sd,dh->hs", h, wdt,
                            preferred_element_type=f32)
            with jax.named_scope("ssm_conv"):
                x, b, c = (conv_act(x, tx, bx, True, conv, xb),
                           conv_act(b, tb, bb, False, conv, bcb),
                           conv_act(c, tc, bc, False, conv, bcb))
            with jax.named_scope("ssm_scan"):
                delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)
                                        [:, None])
                y = ssd_scan(x, delta, -jnp.exp(a_log.astype(f32)), b, c,
                             d.astype(f32), g["chunk"], path)
            with jax.named_scope("ssm_norm"):
                y = gated_group_norm(y, z, norm_w, groups, g["eps"])
            return jnp.einsum("hps,hpd->sd", _heads_major(y), w_out)

        return [_per_sequence(one, bottoms[0])]
