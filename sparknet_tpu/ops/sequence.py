"""Sequence layers: RMSNorm, Attention, LatentAttention, ShortConv,
GatedMLP, MixtureOfExperts, LMHeadLoss.

The layer types a decoder-only language model needs beside ``Embed`` and
``Eltwise`` (ROADMAP R3): blobs are ``[sequences, positions, width]`` and
token ids ``[sequences, positions]`` integers.  Matrices are stored
``[in, out]``.  Shapes are inferred in plain Python like every other
layer's; nothing here is traced for a net that has none of these types.

Memory.  Each layer's ``apply`` recomputes its own forward in the backward
pass (``jax.checkpoint``), and the attention, the dense MLP and the head
run one sequence at a time (``jax.lax.map``), so what a step keeps between
the passes is the blobs between layers and what it holds at once is one
sequence's projections, not a batch's.

Layout.  Between its projections and its flash kernels an attention layer
keeps one shape, one layout and one width: ``[kv heads, query heads a kv
head, positions, head_dim]`` (keys and values without the second), head_dim
on the lanes and positions on the sublanes, in the compute dtype (the latent
layer's heads are key/value heads of one query head each).  The
products write and read it as it is (``_heads_major``), rotary turns a head
by a product and not by slicing it, and float32 lives only inside a fusion.

Lowerings, one an operation, chosen here from shapes and backend and counted
once a trace (``attn_lowering_total{path, mask, backward, blocks}``,
``moe_lowering_total{path, gate_up, down}``):

- the attention core (scores, mask, softmax, weighted sum; scope
  ``attn_core``): on a TPU, where ``flash_blocks`` can tile the positions
  (whole lane rows of 128) and the head is a multiple of 64, JAX's
  block-sparse flash kernels (``splash_attention``: ``path=splash``),
  which skip the blocks a causal or window mask empties and never hold a
  score matrix, at heads of 128 and of 64 alike; elsewhere masked scores
  in XLA (``path=xla``), which a TPU refuses where they would not fit.
  The kernels' blocks and their backward form are one function of
  ``(positions, window, head_dim, head_dim_v)``, ``flash_blocks``: the
  block size at which the blocks the mask leaves cost least once a grid
  step is priced, and the fused ``dkv``/``dq`` kernel where a causal layer
  can hold its partial ``dq``, its query block as large as its VMEM takes
  at these heads, the split kernels otherwise and under every window; the
  counter's sample carries ``mask``, ``backward`` and ``blocks``, and
  ``head_dim`` and ``v_head_dim`` where the two differ;
- the experts' products (scope ``moe_experts``): rows sorted by expert and
  multiplied group by group, on a TPU by JAX's ``megablox`` grouped matrix
  kernels (``path=gmm``), elsewhere by ``jax.lax.ragged_dot``
  (``path=ragged_dot``).  Each of a product's three kernels (the forward
  ``gmm``, the rows' gradient ``dlhs`` and the weights' ``tgmm``) takes
  the tiles one rule, ``_gmm_tiling``, gives its own problem; the
  counter's ``gmm`` sample carries them.

The route of an expert layer (scope ``moe_route``; one path on every
backend) is made once a step and kept: ``moe_route`` runs outside what the
layer's backward pass computes again, and what it keeps is the tokens'
picks and chosen scores, the rows' token, weight and slot and the groups'
sizes, a few MB a layer (the ``[tokens, experts]`` scores are not kept:
their product runs again for the sigmoid's derivative).  Whatever is as
many as ``tokens * top_k`` is counted and selected densely on the vector
unit (a comparison against ``arange``, never a scatter or a gather of
scalars, which a TPU prices by the element); one sort carries the slots and
the weights with the keys; and rows of the hidden width are the only thing a
gather or a scatter moves: ``x[token]`` in, the weighted outputs added back
in float32, and each one's transpose in the backward pass.
"""

from __future__ import annotations

import functools
import importlib
import math
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..proto.caffe_pb import FillerParameter
from ..utils import telemetry
from .fillers import fill
from .registry import LayerImpl, register_layer

_GMM_ROWS = 512         # rows a tile of the grouped product holds
# Mosaic's scoped VMEM on a v5e is 16 MB; what a grouped product's tiles may
# ask of it by ``_gmm_vmem``
_GMM_VMEM_MOST = 16 * 2**20


def _filler(p, key: str = "weight_filler") -> FillerParameter:
    return FillerParameter.from_pmsg(p.get(key))


def _per_sequence(fn, *seqs):
    """``fn`` over the leading axis one sequence at a time, recomputed in
    the backward pass."""
    return jax.lax.map(lambda a: jax.checkpoint(fn)(*a), seqs)


def _swiglu(gate, up):
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _rms_norm(x, w, eps: float):
    """``x * rsqrt(mean(x^2, last axis) + eps) * w`` in float32."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


@register_layer("RMSNorm")
class RMSNormLayer(LayerImpl):
    """``x * rsqrt(mean(x^2, last axis) + eps) * weight``, in float32."""

    def init(self, rng, lp, bottom_shapes):
        return [jnp.ones((bottom_shapes[0][-1],), jnp.float32)]

    def apply(self, lp, params, bottoms, train, rng):
        eps = float(lp.sub("rms_norm_param").get("eps", 1e-6))
        norm = functools.partial(_rms_norm, eps=eps)
        return [jax.checkpoint(norm)(bottoms[0], params[0])]


# -- rotary position embedding ----------------------------------------------

def rope_inv_freq(rotary_dim: int, theta: float, yarn_factor: float = 0.0,
                  original_length: int = 0, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """The ``rotary_dim / 2`` inverse frequencies.  With ``yarn_factor``
    they are YaRN's blend of interpolated (``/ factor``) and original
    frequencies on the linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_length``
    positions: computed once, whatever the sequence length."""
    pos_freqs = theta ** (np.arange(0, rotary_dim, 2, dtype=np.float64)
                          / rotary_dim)
    if not yarn_factor:
        return 1.0 / pos_freqs

    def correction_dim(rotations: float) -> float:
        return (rotary_dim * math.log(original_length
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp          # 1 where the original frequency is kept
    return (1.0 / (yarn_factor * pos_freqs)) * (1.0 - keep) \
        + (1.0 / pos_freqs) * keep


def _rotate_half_matrix(head_dim: int, half: int, dtype,
                        offset: int = 0) -> jnp.ndarray:
    """The ``[head_dim, head_dim]`` matrix of 0 and ±1 with ``x @ R`` =
    ``[0, -x2, x1, 0]`` for ``x = [lead, x1, x2, rest]``, x1 and x2 of
    ``half`` and ``lead`` of ``offset``."""
    r = np.zeros((head_dim, head_dim), np.float32)
    i = np.arange(half) + offset
    r[i + half, i] = -1.0
    r[i, i + half] = 1.0
    return jnp.asarray(r, dtype)


def _rotate(x, inv_freq: tuple, factor: float, scale: float, sign: float,
            offset: int = 0):
    """``(x * cos + rotate_half(x) * sign * sin) * scale`` in float32, cos
    and sin ``[positions, head_dim]`` tables that read 1 and 0 on the
    dimensions before ``offset`` and past the rotated ones."""
    s, d = x.shape[-2:]
    half = len(inv_freq)
    pos = jnp.arange(s, dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    rest = d - 2 * half - offset
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * (sign * factor)
    cos = jnp.concatenate([cos, cos, jnp.ones((s, rest), jnp.float32)], -1)
    sin = jnp.concatenate([sin, sin, jnp.zeros((s, rest), jnp.float32)], -1)
    if offset:
        cos = jnp.concatenate([jnp.ones((s, offset), jnp.float32), cos], -1)
        sin = jnp.concatenate([jnp.zeros((s, offset), jnp.float32), sin], -1)
    # one term a column, so the product is exact in any dtype: the half
    # turn stays on the lanes and no head is sliced
    turned = jnp.matmul(x, _rotate_half_matrix(d, half, x.dtype, offset),
                        precision=jax.lax.Precision.HIGHEST)
    out = x.astype(jnp.float32) * cos + turned.astype(jnp.float32) * sin
    return (out * scale).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _rope(x, inv_freq, factor, scale, offset):
    return _rotate(x, inv_freq, factor, scale, 1.0, offset)


# a rotation's transpose is the rotation by the negative angle: the
# backward pass is the forward's one fusion on the cotangent
_rope.defvjp(
    lambda x, inv_freq, factor, scale, offset: (
        _rope(x, inv_freq, factor, scale, offset), None),
    lambda inv_freq, factor, scale, offset, _, g: (
        _rotate(g, inv_freq, factor, scale, -1.0, offset),))


def apply_rope(x, inv_freq: np.ndarray, factor: float, scale: float = 1.0,
               offset: int = 0):
    """Rotate ``2 * len(inv_freq)`` of the last axis of ``x [...,
    positions, head_dim]``, from ``offset`` on, by position, halves paired
    as ``transformers`` pairs them; ``factor`` multiplies cos and sin.  In
    float32; ``scale`` (the score scale, on queries) rides along, over the
    whole head."""
    return _rope(x, tuple(float(f) for f in inv_freq), float(factor),
                 float(scale), int(offset))


# -- attention ---------------------------------------------------------------

def _attn_core_xla(q, k, v, window: int):
    """q [kv, group, S, D], k [kv, S, D], v [kv, S, Dv] -> [kv, group, S,
    Dv]: masked scores, softmax in float32, weighted sum."""
    s = q.shape[2]
    scores = jnp.einsum("kgsd,ktd->kgst", q, k,
                        preferred_element_type=jnp.float32)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (j <= i) & ((i - j < window) if window else True)
    p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("kgst,ktd->kgsd", p.astype(v.dtype), v)


class FlashBlocks(typing.NamedTuple):
    """What one layer's flash kernels are built with: ``(block_q, block_kv,
    block_kv_compute)`` of the forward and of the ``dkv`` kernel, and
    ``(block_q, block_kv)`` of the ``dq`` kernel, or None where the fused
    backward kernel makes ``dq`` in the ``dkv`` pass."""
    fwd: tuple
    dkv: tuple
    dq: tuple | None

    @property
    def fused(self) -> bool:
        return self.dq is None

    def labels(self, window: int) -> dict:
        """The ``attn_lowering_total`` labels that say what engaged."""
        x = lambda b: "x".join(map(str, b))
        return {"mask": "window" if window else "causal",
                "backward": "fused" if self.fused else "split",
                "blocks": f"fwd {x(self.fwd)} dkv {x(self.dkv)}"
                          + ("" if self.fused else f" dq {x(self.dq)}")}


# the flash kernels' memory blocks: whole lane rows, and no larger than the
# compiler's 16 MB of scoped VMEM take (it refuses a forward or ``dq``
# kernel of 2,048 x 2,048 at a head of 128)
_FLASH_SIZES = (128, 256, 512, 1024)
_FLASH_COMPUTE = 512    # score columns computed at once inside a block
# what a grid step costs before it computes, in score elements of work: on
# a v5e 0.5-0.8 us a step against 0.9-1.2 us a 512 x 512 block of scores
_STEP_SCORES = 120_000
# the fused backward kernel writes one partial dq, the queries' size, a
# key/value memory block and sums them: taken up to this many, in blocks
# of at most this many keys (what its VMEM takes round a compute block)
_FUSED_DQ_COPIES = 4
_FUSED_KV_MOST = 2048
# what one step of the fused kernel holds in VMEM, in lane-padded elements
# of its blocks' rows (``_fused_rows``): 1,441,792 at heads of 128 over
# blocks of 1,024 x 2,048 compile; 2,228,224 at 192/128 are refused (17.9
# of 16 MB at 16 heads), 1,900,544 over 512 x 2,048 compile
_FUSED_ROWS_MOST = 2_000_000
_INTERPRET = False      # tests patch this: the kernels in Pallas' interpreter


def _blocks_seen(positions: int, window: int, block: int) -> int:
    """Blocks of ``block`` x ``block`` scores a causal (``window`` 0) or
    sliding mask leaves something of: the grid steps of a kernel."""
    seen = 0
    for first in range(0, positions, block):
        low = max(0, first - (window - 1)) if window else 0
        seen += (first + block - 1) // block - low // block + 1
    return seen


def _fused_rows(block_q: int, block_kv: int, head_dim: int,
                head_dim_v: int) -> int:
    """Lane-padded elements of the rows one step of the fused ``dkv``/``dq``
    kernel holds: a query block's q, partial dq and output cotangent, a
    key/value block's k and v and the gradients it accumulates for them."""
    lanes = lambda w: -(-w // 128) * 128
    d, dv = lanes(head_dim), lanes(head_dim_v)
    return block_q * (2 * d + dv) + 2 * block_kv * (d + dv)


def flash_blocks(positions: int, window: int, head_dim: int,
                 head_dim_v: int | None = None) -> FlashBlocks | None:
    """The blocks and the backward form of the flash kernels for one
    sequence of ``positions`` under a causal mask (``window`` 0) or a
    sliding one, or None where they cannot tile it.  One rule:

    - every kernel takes square memory blocks of the size (of
      ``_FLASH_SIZES``, dividing the positions) at which the blocks the
      mask leaves cost least, a block costing its scores plus
      ``_STEP_SCORES`` for the step (a kernel computes a block it visits
      whole, so a window of 512 wastes half of what it computes at 512
      and a third at 256, and 512 still wins: 3 smaller steps a row cost
      more than 2 larger ones), round compute blocks of ``_FLASH_COMPUTE``;
    - a causal layer takes the fused ``dkv``/``dq`` kernel, 5 products and
      one exponential a score where the two kernels make 7 and two, where
      its key/value block can grow (to ``_FUSED_KV_MOST``) until the
      partial ``dq`` it writes is at most ``_FUSED_DQ_COPIES`` times the
      queries, its query block halved until one step's rows
      (``_fused_rows``) fit its VMEM (``_FUSED_ROWS_MOST``); a window
      never does: the fused kernel's grid is not shrunk by the mask and it
      writes zeros for every block the mask skips.

    ``head_dim`` (of q and k) and ``head_dim_v`` (of v, ``head_dim`` if not
    given) decide whether the kernels take the heads (whole or half lane
    rows) and the fused kernel's query block: on a v5e the soft-max's
    float32 elementwise work and not the products bounds a block, at 64 as
    at 128 and at 192/128, and a sweep of the blocks found the same
    winners at each."""
    head_dim_v = head_dim if head_dim_v is None else head_dim_v
    sizes = [b for b in _FLASH_SIZES if positions % b == 0]
    if not sizes or head_dim % 64 or head_dim_v % 64:
        return None
    b = min(sizes, key=lambda b: _blocks_seen(positions, window, b)
            * (b * b + _STEP_SCORES))
    compute = min(b, _FLASH_COMPUTE)
    if not window:
        kv = b
        while (positions // kv > _FUSED_DQ_COPIES and kv < _FUSED_KV_MOST
               and positions % (2 * kv) == 0):
            kv *= 2
        if positions // kv <= _FUSED_DQ_COPIES:
            q = b
            while (q > _FLASH_SIZES[0]
                   and _fused_rows(q, kv, head_dim, head_dim_v)
                   > _FUSED_ROWS_MOST):
                q //= 2
            return FlashBlocks((b, b, compute), (q, kv, min(q, compute)),
                               None)
    return FlashBlocks((b, b, compute), (b, b, compute), (b, b))


def _block_sizes(blocks: FlashBlocks):
    """``blocks`` as JAX's ``BlockSizes``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    q, kv, compute = blocks.fwd
    q_dkv, kv_dkv, compute_dkv = blocks.dkv
    q_dq, kv_dq = blocks.dq or (None, None)
    return sk.BlockSizes(
        block_q=q, block_kv=kv, block_kv_compute=compute,
        block_q_dkv=q_dkv, block_kv_dkv=kv_dkv,
        block_kv_dkv_compute=compute_dkv, block_q_dq=q_dq,
        block_kv_dq=kv_dq, use_fused_bwd_kernel=blocks.fused)


@functools.lru_cache(maxsize=16)
def _splash_kernel(s: int, group: int, window: int, head_dim: int,
                   interpret: bool, head_dim_v: int | None = None):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    one = (sm.LocalMask((s, s), (window - 1, 0), 0) if window
           else sm.CausalMask((s, s)))
    sizes = _block_sizes(flash_blocks(s, window, head_dim, head_dim_v))
    # the kernel object holds its block mask as arrays: made concrete
    # here, or a cached one would carry the tracers of the trace that
    # first asked for it into the next
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mqa_single_device(
            sm.MultiHeadMask([one] * group), block_sizes=sizes,
            interpret=interpret)


# float32 scores the masked-scores lowering may hold for one sequence on a
# chip: a sixteenth of its 16 GB
_XLA_SCORE_BYTES = 1 << 30


def attn_lowering(positions: int, head_dim: int, heads: int = 1,
                  window: int = 0, head_dim_v: int | None = None) -> str:
    """Which lowering the attention core takes at these sizes on this
    backend; counted in ``attn_lowering_total``.  The flash kernels take
    whatever ``flash_blocks`` can tile, a head of 64 as it is (half a lane
    row a head: the kernels pad their own scratch, nothing is padded
    here), and the counter's sample says what it chose: ``mask``,
    ``backward`` and ``blocks`` beside ``path``.  On a TPU the masked
    scores are refused, not taken, where one sequence's would not fit."""
    tpu = jax.default_backend() == "tpu"
    blocks = (flash_blocks(positions, window, head_dim, head_dim_v) if tpu
              else None)
    path = "splash" if blocks else "xla"
    if tpu and not blocks and (4 * heads * positions * positions
                               > _XLA_SCORE_BYTES):
        raise ValueError(
            f"attention over {positions} positions with heads of "
            f"{head_dim} fits no flash kernel (positions in blocks of "
            f"{_FLASH_SIZES[0]}, heads of a multiple of 64), and its masked "
            f"scores ({heads} x {positions} x {positions} float32) do not "
            f"fit the chip")
    heads_unequal = ({} if head_dim_v in (None, head_dim) else
                     {"head_dim": head_dim, "v_head_dim": head_dim_v})
    telemetry.get_registry().counter(
        "attn_lowering_total",
        "traces of the attention core, by lowering").inc(
            path=path, **(blocks.labels(window) if blocks else {}),
            **heads_unequal)
    return path


def _heads_major(t):
    """``t [..., positions, head_dim]`` and its cotangent kept in that
    order in memory, head_dim on the lanes: what the flash kernels read
    and write, so the products on either side make and take it as it is
    and nothing is transposed between them."""
    return with_layout_constraint(
        t, Layout(major_to_minor=tuple(range(t.ndim))))


def attn_core(q, k, v, window: int, path: str):
    """Causal (and, with ``window``, sliding) grouped-query attention of
    one sequence.  q [kv, group, S, D] already scaled, k [kv, S, D] and v
    [kv, S, Dv]; returns [kv, group, S, Dv]."""
    _, group, s, _ = q.shape
    with jax.named_scope("attn_core"):
        if path == "splash":
            return jax.vmap(_splash_kernel(s, group, window, q.shape[-1],
                                           _INTERPRET, v.shape[-1]))(q, k, v)
        return _attn_core_xla(q, k, v, window)


@register_layer("Attention")
class AttentionLayer(LayerImpl):
    """Grouped-query self-attention with rotary positions
    (``attention_param``): ``num_heads`` query heads share
    ``num_kv_heads`` key/value heads of ``head_dim``; causal, and with
    ``window`` w a key j is seen from i only if ``0 <= i - j < w``; the
    first ``rotary_dim`` dimensions of each head are rotated (``rope_theta``;
    ``yarn_factor``, ``yarn_original_length``, ``yarn_beta_fast``,
    ``yarn_beta_slow`` for YaRN; ``rope_attention_factor`` on cos and
    sin); scores are scaled by ``1/sqrt(head_dim)``.  With ``gate`` (the
    default) each head's output is multiplied by ``sigmoid(x W_g)``, one
    scalar a head a token, before the output projection.  With ``qk_norm``
    each head of q and of k is RMS-normalised (``qk_norm_eps``) before it
    is rotated, by one weight of ``head_dim`` for q and one for k.  Blobs:
    W_q, W_k, W_v, W_g if gated, W_o, then the q and k norm weights if
    normalised; no bias."""

    def _geom(self, lp):
        p = lp.sub("attention_param")
        d = int(p.get("head_dim", 0))
        return dict(
            heads=int(p.get("num_heads", 0)),
            kv=int(p.get("num_kv_heads", 0)), d=d,
            window=int(p.get("window", 0)),
            gate=bool(p.get("gate", True)),
            qk_norm=bool(p.get("qk_norm", False)),
            qk_eps=float(p.get("qk_norm_eps", 1e-6)),
            factor=float(p.get("rope_attention_factor", 1.0)),
            inv_freq=rope_inv_freq(
                int(p.get("rotary_dim", d)), float(p.get("rope_theta", 1e4)),
                float(p.get("yarn_factor", 0.0)),
                int(p.get("yarn_original_length", 0)),
                float(p.get("yarn_beta_fast", 32.0)),
                float(p.get("yarn_beta_slow", 1.0))))

    def init(self, rng, lp, bottom_shapes):
        g = self._geom(lp)
        hidden = bottom_shapes[0][-1]
        wf = _filler(lp.sub("attention_param"))
        q, kvw = g["heads"] * g["d"], g["kv"] * g["d"]
        shapes = [(hidden, q), (hidden, kvw), (hidden, kvw),
                  *([(hidden, g["heads"])] if g["gate"] else []),
                  (q, hidden)]
        # two arrays, not one twice: a step donates each blob's buffer
        norms = [jnp.ones((g["d"],), jnp.float32)
                 for _ in range(2 * g["qk_norm"])]
        return [fill(r, wf, s) for r, s in zip(jax.random.split(rng, 5),
                                               shapes)] + norms

    def apply(self, lp, params, bottoms, train, rng):
        g = self._geom(lp)
        heads, kv, d = g["heads"], g["kv"], g["d"]
        blobs = iter(params)
        wq, wk, wv = next(blobs), next(blobs), next(blobs)
        wg = next(blobs) if g["gate"] else None
        wo = next(blobs)
        q_norm, k_norm = ((next(blobs), next(blobs)) if g["qk_norm"]
                          else (None, None))
        path = attn_lowering(bottoms[0].shape[-2], d, heads, g["window"])

        hidden, group = wq.shape[0], heads // kv
        # the query heads grouped by their key/value head, as the kernels
        # take them: no tensor between the products is reshaped
        wq = wq.reshape(hidden, kv, group, d)
        wk, wv = wk.reshape(hidden, kv, d), wv.reshape(hidden, kv, d)
        if wg is not None:
            wg = wg.reshape(hidden, kv, group)
        wo = wo.reshape(kv, group, d, hidden)

        def one(x):
            q = _heads_major(jnp.einsum("sh,hkgd->kgsd", x, wq))
            if q_norm is not None:
                q = _rms_norm(q, q_norm, g["qk_eps"])
            q = apply_rope(q, g["inv_freq"], g["factor"], scale=d ** -0.5)
            k = _heads_major(jnp.einsum("sh,hkd->ksd", x, wk))
            if k_norm is not None:
                k = _rms_norm(k, k_norm, g["qk_eps"])
            k = apply_rope(k, g["inv_freq"], g["factor"])
            v = _heads_major(jnp.einsum("sh,hkd->ksd", x, wv))
            out = attn_core(q, k, v, g["window"], path)
            if wg is not None:
                gate = jax.nn.sigmoid(
                    jnp.einsum("sh,hkg->kgs", x, wg).astype(jnp.float32))
                out = (out.astype(jnp.float32)
                       * gate[..., None]).astype(x.dtype)
            return jnp.einsum("kgsd,kgdh->sh", _heads_major(out), wo)

        return [_per_sequence(one, bottoms[0])]


@register_layer("LatentAttention")
class LatentAttentionLayer(LayerImpl):
    """Multi-head latent attention in its expanded (training) form
    (``latent_attention_param``), causal, ``num_heads`` heads:

    - ``c = x W_dkv`` (hidden -> ``kv_lora_rank``), ``ĉ = RMSNorm(c) γ_kv``
      (``kv_norm_eps``); ``[k_nope_h | v_h] = ĉ W_ukv``, ``qk_nope_head_dim``
      and ``v_head_dim`` a head;
    - one rotary key ``k_r = RoPE(x W_kr)`` of ``qk_rope_head_dim``, shared
      by every head and not normalised;
    - ``[q_nope_h | q_r_h] = x W_q``, ``q_r_h`` rotated (``rope_theta``;
      ``yarn_factor``, ``yarn_original_length``, ``yarn_beta_fast``,
      ``yarn_beta_slow``; ``rope_attention_factor`` on cos and sin);
    - ``softmax_causal(τ (q_h · [k_nope_h | k_r])) v_h``, τ
      ``softmax_scale`` (``1/sqrt(qk_nope_head_dim + qk_rope_head_dim)``
      if not given), then ``W_o``.

    Blobs: W_q ``[hidden, heads x (nope + rope)]``, W_dkv, W_kr, γ_kv
    ``[kv_lora_rank]``, W_ukv ``[kv_lora_rank, heads x (nope + v)]``, W_o
    ``[heads x v, hidden]``; no bias.  As ``Attention``, a sequence at a time,
    recomputed in the backward pass, heads-major from the projections
    through the core to ``W_o``; the core is ``attn_core`` with heads of
    ``nope + rope`` for q and k and of ``v_head_dim`` for v.  The latent's
    products, its norm and the keys' assembly run under the sub-scope
    ``mla_latent``."""

    def _geom(self, lp):
        p = lp.sub("latent_attention_param")
        nope, rope = (int(p.get("qk_nope_head_dim", 0)),
                      int(p.get("qk_rope_head_dim", 0)))
        return dict(
            heads=int(p.get("num_heads", 0)),
            rank=int(p.get("kv_lora_rank", 0)), nope=nope, rope=rope,
            v=int(p.get("v_head_dim", 0)),
            eps=float(p.get("kv_norm_eps", 1e-6)),
            scale=float(p.get("softmax_scale", (nope + rope) ** -0.5)),
            factor=float(p.get("rope_attention_factor", 1.0)),
            inv_freq=rope_inv_freq(
                rope, float(p.get("rope_theta", 1e4)),
                float(p.get("yarn_factor", 0.0)),
                int(p.get("yarn_original_length", 0)),
                float(p.get("yarn_beta_fast", 32.0)),
                float(p.get("yarn_beta_slow", 1.0))))

    def init(self, rng, lp, bottom_shapes):
        g = self._geom(lp)
        hidden, heads = bottom_shapes[0][-1], g["heads"]
        wf = _filler(lp.sub("latent_attention_param"))
        r = jax.random.split(rng, 5)
        return [fill(r[0], wf, (hidden, heads * (g["nope"] + g["rope"]))),
                fill(r[1], wf, (hidden, g["rank"])),
                fill(r[2], wf, (hidden, g["rope"])),
                jnp.ones((g["rank"],), jnp.float32),
                fill(r[3], wf, (g["rank"], heads * (g["nope"] + g["v"]))),
                fill(r[4], wf, (heads * g["v"], hidden))]

    def apply(self, lp, params, bottoms, train, rng):
        g = self._geom(lp)
        heads, nope, rope, dv = g["heads"], g["nope"], g["rope"], g["v"]
        wq, wdkv, wkr, gamma, wukv, wo = params
        path = attn_lowering(bottoms[0].shape[-2], nope + rope, heads,
                             head_dim_v=dv)
        hidden, rank = wq.shape[0], wdkv.shape[1]
        # one head a key/value head: the kernels' [kv, group, S, D] with a
        # group of 1, so no tensor between the products is reshaped
        wq = wq.reshape(hidden, heads, 1, nope + rope)
        wukv = wukv.reshape(rank, heads, nope + dv)
        wuk, wuv = wukv[..., :nope], wukv[..., nope:]
        wo = wo.reshape(heads, 1, dv, hidden)

        def one(x):
            q = _heads_major(jnp.einsum("sh,hkgd->kgsd", x, wq))
            q = apply_rope(q, g["inv_freq"], g["factor"], scale=g["scale"],
                           offset=nope)
            with jax.named_scope("mla_latent"):
                c = _rms_norm(x @ wdkv, gamma, g["eps"])
                k_r = apply_rope(x @ wkr, g["inv_freq"], g["factor"])
                k_nope = _heads_major(jnp.einsum("sc,ckd->ksd", c, wuk))
                v = _heads_major(jnp.einsum("sc,ckd->ksd", c, wuv))
                # the shared rotary key beside each head's own: a
                # concatenation on the lanes, no head transposed
                k = _heads_major(jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_r, (heads, *k_r.shape))],
                    axis=-1))
            out = attn_core(q, k, v, 0, path)
            return jnp.einsum("kgsd,kgdh->sh", _heads_major(out), wo)

        return [_per_sequence(one, bottoms[0])]


# -- short convolution --------------------------------------------------------

def _conv_mix(b, c, x, taps):
    """``c * conv(b * x)``: the part of the gated short convolution that is
    no matrix product, in float32 inside and the operands' dtype out.
    ``b, c, x [positions, width]``; ``taps [width, kernel]``, tap ``kernel -
    1`` on the position itself and tap ``kernel - 1 - j`` on the one ``j``
    before it, zeros before the sequence.  The shift is along the
    positions (the sublanes) and a sequence is whole here, so no halo."""
    s, kernel = x.shape[0], taps.shape[1]
    u = b.astype(jnp.float32) * x.astype(jnp.float32)
    w = taps.astype(jnp.float32)
    acc = u * w[:, kernel - 1]
    for j in range(1, kernel):
        acc = acc + jnp.pad(u, ((j, 0), (0, 0)))[:s] * w[:, kernel - 1 - j]
    return (c.astype(jnp.float32) * acc).astype(x.dtype)


@register_layer("ShortConv")
class ShortConvLayer(LayerImpl):
    """Double-gated short convolution over the positions
    (``short_conv_param``): ``[B, C, x] = h W_in`` in that order, ``W_in:
    hidden -> 3 x hidden``; ``u = B * x``; a depthwise causal convolution
    of ``kernel`` taps over the positions of ``u``; ``(C * conv) W_out``.
    No activation, no bias.  Blobs: W_in ``[hidden, 3, hidden]`` (the three
    thirds are three outputs of one product: none is cut out of a
    ``[positions, 3 x hidden]`` array), the taps ``[hidden, kernel]``
    (``kernel_filler``), W_out ``[hidden, hidden]``.  A sequence at a
    time, recomputed in the backward pass; what lies between the two
    products runs under the scope ``conv_mix``, as XLA fuses it on every
    backend."""

    def init(self, rng, lp, bottom_shapes):
        p = lp.sub("short_conv_param")
        hidden, kernel = bottom_shapes[0][-1], int(p.get("kernel", 3))
        r = jax.random.split(rng, 3)
        return [fill(r[0], _filler(p), (hidden, 3, hidden)),
                fill(r[1], _filler(p, "kernel_filler"), (hidden, kernel)),
                fill(r[2], _filler(p), (hidden, hidden))]

    def apply(self, lp, params, bottoms, train, rng):
        w_in, taps, w_out = params

        def one(h):
            b, c, x = _heads_major(jnp.einsum("sh,hjo->jso", h, w_in))
            with jax.named_scope("conv_mix"):
                mixed = _heads_major(_conv_mix(b, c, x, taps))
            return mixed @ w_out

        return [_per_sequence(one, bottoms[0])]


# -- MLPs ---------------------------------------------------------------------

@register_layer("GatedMLP")
class GatedMLPLayer(LayerImpl):
    """``down(silu(gate(x)) * up(x))`` of ``gated_mlp_param.width``.
    Blobs: W_gate, W_up, W_down; no bias."""

    def init(self, rng, lp, bottom_shapes):
        p = lp.sub("gated_mlp_param")
        hidden, width = bottom_shapes[0][-1], int(p.get("width", 0))
        wf = _filler(p)
        shapes = [(hidden, width), (hidden, width), (width, hidden)]
        return [fill(r, wf, s)
                for r, s in zip(jax.random.split(rng, 3), shapes)]

    def apply(self, lp, params, bottoms, train, rng):
        wg, wu, wd = params
        return [_per_sequence(lambda x: _swiglu(x @ wg, x @ wu) @ wd,
                              bottoms[0])]


def moe_geometry(lp) -> dict:
    p = lp.sub("moe_param")
    return dict(experts=int(p.get("num_experts", 0)),
                top_k=int(p.get("top_k", 1)),
                lo=int(p.get("experts_held_lo", 0)),
                hi=int(p.get("experts_held_hi", p.get("num_experts", 0))),
                scaling=float(p.get("routed_scaling", 1.0)),
                eps=float(p.get("norm_eps", 0.0)),
                shared=int(p.get("shared_width", 0)),
                select_bias=bool(p.get("select_bias", False)),
                scoring=str(p.get("scoring", "sigmoid")),
                norm_topk=bool(p.get("norm_topk", True)),
                detached=bool(p.get("detach_router", False)))


def moe_row_bound(tokens: int, g: dict) -> int:
    """Rows the experts' products are sized for: a quarter over the
    ``tokens * top_k * held / experts`` an even router sends here, in whole
    tiles, and never more than every token choosing every held expert.  A
    buffer's size and no capacity of an expert: it binds only if a quarter
    more picks than the held share land here, and ``moe_route`` counts the
    rows it would then leave out."""
    held = g["hi"] - g["lo"]
    most = tokens * min(g["top_k"], held)
    even = tokens * g["top_k"] * held / g["experts"]
    return min(most, -(-math.ceil(1.25 * even) // _GMM_ROWS) * _GMM_ROWS)


def _router_logits(x, w_router):
    """``x W_r`` in float32, the product at ``HIGHEST``."""
    return jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _router_scores(x, w_router):
    """``sigmoid(x W_r)`` in float32; run again in the backward pass for the
    sigmoid's derivative, so that what is kept of it is ``x`` as it
    came."""
    return jax.nn.sigmoid(_router_logits(x, w_router))


def _router_softmax(x, w_router):
    """``softmax(x W_r)`` over every expert in float32; like
    ``_router_scores`` run again in the backward pass, so that nothing
    ``[tokens, experts]`` is kept."""
    return jax.nn.softmax(_router_logits(x, w_router), axis=-1)


_SCORERS = {"sigmoid": _router_scores, "softmax": _router_softmax}


def _chosen(scores, top_i):
    """``scores[t, top_i[t, j]]`` as a dense selection over ``[tokens,
    top_k, experts]``, never in memory: one fusion on the vector unit whose
    transpose is the same selection, where a gather's would be a scatter of
    ``tokens * top_k`` scalars."""
    hit = top_i[..., None] == jnp.arange(scores.shape[-1], dtype=top_i.dtype)
    return jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _by_expert(key, weight, rows: int):
    """The first ``rows`` of ``(key, slot, weight)`` sorted by ``key``, the
    lower slot first among equal keys: one sort that carries its payload.
    ``key`` and ``weight`` are ``[tokens, top_k]`` and a slot is a position
    in them flattened."""
    flat = key.reshape(-1)
    slot = jax.lax.iota(jnp.int32, flat.shape[0])
    skey, slot, weight = jax.lax.sort((flat, slot, weight.reshape(-1)),
                                      num_keys=1, is_stable=True)
    return skey[:rows], slot[:rows], weight[:rows]


def _by_expert_fwd(key, weight, rows):
    out = _by_expert(key, weight, rows)
    return out, (out[1], key.shape)


def _by_expert_bwd(rows, saved, cot):
    # the weights go back to where they were taken from, ``rows`` of them
    # and a token's ``top_k`` at a time: the sort's own transpose would
    # scatter ``tokens * top_k`` scalars
    slot, (tokens, k) = saved
    mine = (slot % k)[:, None] == jnp.arange(k, dtype=slot.dtype)
    back = jnp.zeros((tokens, k), cot[2].dtype).at[slot // k].add(
        jnp.where(mine, cot[2][:, None], 0.0))
    return None, back


_by_expert.defvjp(_by_expert_fwd, _by_expert_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(y, w, token, shared, tokens: int):
    """``shared + sum over a token's rows of w * y`` in float32, in
    ``y``'s dtype: the rows' way back to their tokens (``shared`` is the
    shared expert's output ``[tokens, hidden]``, or None)."""
    y32 = y.astype(jnp.float32) * w[:, None]
    routed = jnp.zeros((tokens, y.shape[-1]), jnp.float32).at[token].add(y32)
    if shared is not None:
        routed = routed + shared
    return routed.astype(y.dtype)


def _combine_fwd(y, w, token, shared, tokens):
    return _combine(y, w, token, shared, tokens), (y, w, token,
                                                   shared is None)


def _combine_bwd(tokens, saved, cot):
    # the cotangent's rows are taken as they come, in the compute dtype:
    # no float32 copy of every token's row is made to gather from
    y, w, token, alone = saved
    g = cot[token].astype(jnp.float32)
    dy = (g * w[:, None]).astype(y.dtype)
    dw = jnp.sum(g * y.astype(jnp.float32), axis=-1)
    return dy, dw, None, None if alone else cot


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_route(x, w_router, g: dict, bias=None):
    """Route ``x [tokens, hidden]`` over all the experts and list the rows
    the held ones compute.  Scores are ``sigmoid(x W_r)`` in float32, or
    with ``g["scoring"]`` softmax ``softmax(x W_r)`` over every expert; a
    token takes the ``top_k`` experts with the largest score, or with
    ``bias [experts]`` the largest ``score + bias`` (the lower index on a
    tie); the weights are the chosen experts' scores, without the bias,
    over their sum plus ``g["eps"]`` (as they are where ``g["norm_topk"]``
    is false), times ``scaling``.  Returns (token
    index, weight, rows of each held expert as sized for the products) of
    the ``moe_row_bound`` rows sorted by expert, then (rows each held
    expert was sent, rows left out because the bound bound).

    Differentiable in ``x`` and ``w_router`` through the weights; the picks
    are constants of the backward pass.  Kept for it: ``x`` and
    ``w_router`` as they came (the scores' product runs again), the picks
    and their scores ``[tokens, top_k]``, each token's sum, and the rows'
    slots; nothing of size ``[tokens, experts]``."""
    tokens, held, k = x.shape[0], g["hi"] - g["lo"], g["top_k"]
    if g.get("detached"):
        x = jax.lax.stop_gradient(x)
    scores = jax.checkpoint(_SCORERS[g.get("scoring", "sigmoid")])(
        x, w_router)
    pick = scores if bias is None else scores + bias.astype(jnp.float32)
    _, top_i = jax.lax.top_k(jax.lax.stop_gradient(pick), k)
    top_s = _chosen(scores, top_i)
    if g.get("norm_topk", True):
        total = jnp.sum(top_s, axis=-1, keepdims=True)
        if g.get("eps"):
            total = total + g["eps"]
        weight = top_s / total * g["scaling"]
    else:
        weight = top_s * g["scaling"]
    here = (top_i >= g["lo"]) & (top_i < g["hi"])
    key = jnp.where(here, top_i - g["lo"], held)
    rows = moe_row_bound(tokens, g)
    skey, slot, w = _by_expert(key, weight, rows)
    # counted by comparison on the vector unit, not by a scatter of ones
    sent = jnp.sum(key[..., None] == jnp.arange(held, dtype=key.dtype),
                   axis=(0, 1), dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(sent), rows)
    sized = jnp.diff(ends, prepend=0)
    # the rows past the last one sent go to the last expert with weight 0,
    # so the products do the same work whatever the router chose (added by
    # a mask: ``.at[-1].add`` is one more scatter of a scalar)
    sized = sized + jnp.where(jnp.arange(held) == held - 1,
                              rows - ends[-1], 0)
    w = jnp.where(skey < held, w, 0.0)
    return slot // k, w, sized, sent, jnp.sum(sent) - ends[-1]


def moe_lowering(rows: int, hidden: int, width: int,
                 itemsize: int) -> str:
    """Which lowering the experts' grouped products take at these sizes, of
    operands of ``itemsize`` bytes, on this backend; counted in
    ``moe_lowering_total``, whose ``gmm`` sample carries the tiles of each
    product's three kernels (``gate_up`` and ``down``: ``fwd``, ``dlhs``,
    ``tgmm``)."""
    gmm = (jax.default_backend() == "tpu" and rows % _GMM_ROWS == 0
           and hidden % 128 == 0 and width % 128 == 0)
    path = "gmm" if gmm else "ragged_dot"
    tiles = {}
    if gmm:
        for product, (k, n) in (("gate_up", (hidden, width)),
                                ("down", (width, hidden))):
            tiles[product] = " ".join(
                f"{kernel} {'x'.join(map(str, t))}" for kernel, t in
                zip(("fwd", "dlhs", "tgmm"),
                    gmm_tiles(rows, k, n, itemsize)))
    telemetry.get_registry().counter(
        "moe_lowering_total",
        "traces of an expert layer's grouped products, by lowering").inc(
            path=path, **tiles)
    return path


def _grouped(rows, w, sizes, path: str):
    """``rows[group i] @ w[i]`` for rows sorted by group."""
    if path != "gmm":
        return jax.lax.ragged_dot(rows, w, sizes)
    return _gmm(rows, w, sizes,
                gmm_tiles(rows.shape[0], *w.shape[1:],
                          jnp.result_type(rows, w).itemsize), _INTERPRET)


def _gmm_vmem(kernel: str, tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Bytes of scoped VMEM one of JAX's ``megablox`` kernels holds at
    tiles ``(tm, tk, tn)`` of operands of ``itemsize`` bytes: its blocks of
    lhs ``(tm, tk)``, of rhs (``(tk, tn)``; ``tgmm``'s ``(tm, tn)``) and of
    its output (``(tm, tn)``; ``tgmm``'s ``(tk, tn)``) double-buffered, the
    float32 accumulator the size of an output block, and one more copy of
    the lhs block (the kernel's own, transposed in ``tgmm``; at four bytes
    ``gmm`` makes none, so there the estimate is an upper bound)."""
    out = tk * tn if kernel == "tgmm" else tm * tn
    rhs = tm * tn if kernel == "tgmm" else tk * tn
    return (2 * itemsize * (tm * tk + rhs + out) + 4 * out
            + itemsize * tm * tk)


def _gmm_tiling(kernel: str, m: int, k: int, n: int, itemsize: int) -> tuple:
    """``(tm, tk, tn)`` for one ``megablox`` kernel (``gmm``, ``dlhs`` --
    ``gmm`` with the right operand transposed -- or ``tgmm``) on its own
    problem of operands of ``itemsize`` bytes: ``m`` rows in groups,
    contracted over ``k`` into ``n``.  ``tm`` is ``_GMM_ROWS``; ``tk`` and
    ``tn`` divide ``k`` and ``n`` in whole lane rows, so no tile hangs over
    an edge and no contraction is masked.  Of
    the tiles with which the kernel fits ``_GMM_VMEM_MOST`` (``_gmm_vmem``)
    it takes those with which it reads least: the lhs once for every tile
    of ``n``; in ``gmm`` the rhs once for every tile of rows where the
    contraction takes more than one tile (a group's rhs block is kept from
    one row tile to the next only where it is the whole of ``k``), in
    ``tgmm`` the rhs once for every tile of ``k``; then the fewest steps.
    The budget is a v5e's, and the estimate was fitted to what its compiler
    takes and refuses at bfloat16 and checked at float32, where it refuses
    some tilings the compiler takes; another chip generation, or a width no
    test compiles, may ask for a refit."""
    lanes = range(128, max(k, n) + 1, 128)

    def cost(tiles):
        tk, tn = tiles
        if kernel == "tgmm":
            read = m * k * (n // tn) + m * n * (k // tk)
        else:
            read = m * k * (n // tn) + (m // _GMM_ROWS * k * n if tk < k
                                        else 0)
        return read, (k // tk) * (n // tn)

    fits = [(tk, tn) for tk in lanes if k % tk == 0 for tn in lanes
            if n % tn == 0
            and _gmm_vmem(kernel, _GMM_ROWS, tk, tn, itemsize)
            <= _GMM_VMEM_MOST]
    if not fits:
        raise ValueError(f"no tiling of ({k}, {n}) fits {kernel}'s VMEM")
    return (_GMM_ROWS, *min(fits, key=cost))


def gmm_tiles(m: int, k: int, n: int, itemsize: int) -> tuple:
    """The tiles of the three kernels of ``rows [m, k] @ w [groups, k, n]``
    and its gradient, operands of ``itemsize`` bytes: the forward ``gmm``,
    ``dlhs`` (the rows' gradient, its own problem ``(m, n, k)``) and
    ``tgmm`` (the weights')."""
    return tuple(_gmm_tiling(kernel, m, *kn, itemsize) for kernel, kn in
                 (("gmm", (k, n)), ("dlhs", (n, k)), ("tgmm", (k, n))))


def _megablox():
    """JAX's ``megablox`` kernels themselves, ``gmm`` and ``tgmm``, without
    the VJP that ``megablox.gmm`` puts round them."""
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(rows, w, sizes, tiles: tuple, interpret: bool):
    """``rows[group i] @ w[i]`` by JAX's ``megablox`` kernels, each of the
    three at its own tiles (``gmm_tiles``): ``megablox.gmm`` hands one
    tiling to all three, and gate's forward ``gmm`` and its ``tgmm`` ask a
    tiling callable with the same ``(m, k, n)``."""
    return _megablox().gmm(rows, w, sizes, rows.dtype, tiles[0],
                           interpret=interpret)


def _gmm_fwd(rows, w, sizes, tiles, interpret):
    return _gmm(rows, w, sizes, tiles, interpret), (rows, w, sizes)


def _gmm_bwd(tiles, interpret, saved, dy):
    rows, w, sizes = saved
    kernels = _megablox()
    d_rows = kernels.gmm(dy, w, sizes, rows.dtype, tiles[1],
                         transpose_rhs=True, interpret=interpret)
    d_w = kernels.tgmm(rows.swapaxes(0, 1), dy, sizes, w.dtype, tiles[2],
                       num_actual_groups=w.shape[0], interpret=interpret)
    return d_rows, d_w, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@register_layer("MixtureOfExperts")
class MixtureOfExpertsLayer(LayerImpl):
    """A router over ``num_experts`` experts, ``top_k`` a token, and a
    shared expert of ``shared_width`` if that is not 0 (``moe_param``).
    The layer holds the experts ``[experts_held_lo, experts_held_hi)``: it
    routes over all of them, computes the held experts' part for the tokens
    routed to them, adds the shared expert unweighted, and leaves out what
    the absent experts would add.  No token is dropped.  Blobs: W_router;
    the held experts' W_gate, W_up ``[held, hidden, width]`` and W_down
    ``[held, width, hidden]``; the shared expert's W_gate, W_up, W_down if
    there is one; with ``select_bias`` a bias ``[num_experts]`` last
    (``select_bias_filler``), added to the scores where the experts are
    chosen and nowhere else.  ``norm_eps`` is added to the sum the chosen
    scores are divided by.  ``scoring`` is ``sigmoid`` (the default) or
    ``softmax`` over every expert, and ``norm_topk`` false weighs a chosen
    expert by its score as it is, not over the chosen scores' sum.
    ``router_column_norm``, if
    given, scales each column of the filled router to that length.
    ``detach_router`` keeps the scores' gradient from the layer's input
    (the router's own weights still get theirs): for a layer that holds a
    share of the experts and is sent only that share of the gradient."""

    def init(self, rng, lp, bottom_shapes):
        p, g = lp.sub("moe_param"), moe_geometry(lp)
        hidden, held = bottom_shapes[0][-1], g["hi"] - g["lo"]
        width, shared = int(p.get("expert_width", 0)), g["shared"]
        wf = _filler(p)
        shapes = [(held, hidden, width), (held, hidden, width),
                  (held, width, hidden)]
        if shared:
            shapes += [(hidden, shared), (hidden, shared), (shared, hidden)]
        r = jax.random.split(rng, 7)
        router = fill(r[0], _filler(p, "router_filler"),
                      (hidden, g["experts"]))
        norm = float(p.get("router_column_norm", 0.0))
        if norm:
            # every expert's column as long as the next one's: a longer
            # column's scores spread wider and reach a token's top picks
            # more often, which at hidden 2048 is 7% of load from the
            # filler alone
            router = router * (norm / jnp.linalg.norm(router, axis=0))
        blobs = [router] + [fill(ri, wf, s) for ri, s in zip(r[1:], shapes)]
        if g["select_bias"]:
            blobs.append(fill(jax.random.fold_in(rng, 7),
                              _filler(p, "select_bias_filler"),
                              (g["experts"],)))
        return blobs

    def apply(self, lp, params, bottoms, train, rng):
        g = moe_geometry(lp)
        shape = bottoms[0].shape
        path = moe_lowering(moe_row_bound(math.prod(shape[:-1]), g),
                            shape[-1], params[1].shape[-1],
                            jnp.result_type(bottoms[0], params[1]).itemsize)
        x = bottoms[0].reshape(-1, shape[-1])
        wr, eg, eu, ed, *rest = params
        bias = rest[-1] if g["select_bias"] else None
        # routed once a step: the route is outside what the backward pass
        # computes again, and its few MB are what is kept of it
        with jax.named_scope("moe_route"):
            token, w, sized, _, _ = moe_route(x, wr, g, bias)

        def experts(x, token, w, sized, eg, eu, ed, *shared):
            with jax.named_scope("moe_route"):
                rows = x[token]
            with jax.named_scope("moe_experts"):
                y = _grouped(_swiglu(_grouped(rows, eg, sized, path),
                                     _grouped(rows, eu, sized, path)),
                             ed, sized, path)
            also = None
            if shared:
                sg, su, sd = shared
                also = _swiglu(x @ sg, x @ su) @ sd
            with jax.named_scope("moe_route"):
                return _combine(y, w, token, also, x.shape[0])

        out = jax.checkpoint(experts)(x, token, w, sized, eg, eu, ed,
                                      *(rest[:3] if g["shared"] else ()))
        return [out.reshape(shape)]


def moe_load(net, params, inputs) -> dict:
    """What each expert layer of ``net`` was sent on ``inputs``:
    ``{layer: {"rows": [per held expert], "dropped": n}}`` from a
    training-mode forward, one sequence at a time so that it fits beside a
    training step's state.  ``dropped`` is what the layer leaves out when it
    routes all of ``inputs`` at once, as a step does: a token's experts are
    its own choice, so the batch's rows are the sum of its sequences', and
    the row bound is the one of all its tokens (``moe_row_bound``).  Also
    counted in ``moe_rows_total{layer}`` and ``moe_dropped_total``."""
    nodes = [n for n in net.nodes if n.lp.type == "MixtureOfExperts"]

    @jax.jit
    def load(params, one):
        blobs = net.apply_all(params, one, train=True)
        out = {}
        for n in nodes:
            x, g, own = blobs[n.bottoms[0]], moe_geometry(n.lp), params[
                n.lp.name]
            out[n.lp.name] = moe_route(
                x.reshape(-1, x.shape[-1]), own[0].astype(x.dtype), g,
                own[-1] if g["select_bias"] else None)[3]
        return out

    sequences = len(next(iter(inputs.values())))
    got = [load(params, {k: v[i:i + 1] for k, v in inputs.items()})
           for i in range(sequences)]
    total = jax.tree_util.tree_map(lambda *xs: np.sum(xs, axis=0), *got)
    tokens = math.prod(np.shape(next(iter(inputs.values()))))
    reg = telemetry.get_registry()
    out = {}
    for n in nodes:
        rows = total[n.lp.name]
        dropped = max(0, int(rows.sum()) - moe_row_bound(
            tokens, moe_geometry(n.lp)))
        reg.counter("moe_rows_total",
                    "rows the held experts were sent").inc(
                        int(rows.sum()), layer=n.lp.name)
        reg.counter("moe_dropped_total",
                    "rows an expert layer left out").inc(dropped)
        out[n.lp.name] = {"rows": rows.tolist(), "dropped": dropped}
    return out


# -- head and loss ------------------------------------------------------------

@register_layer("LMHeadLoss")
class LMHeadLossLayer(LayerImpl):
    """The output head and its loss in one layer, a sequence at a time, so
    that a step never holds every position's logits: bottoms are the
    hidden states and the token ids; the loss is the mean softmax
    cross-entropy of position t's logits (``x W``, float32 out of the
    product on) against token t+1.  A second top, if named, is the logits.
    Blob: W ``[hidden, vocab]``, no bias; with ``lm_head_param.transposed``
    it is stored ``[vocab, hidden]``, the shape of an ``Embed`` table, so
    that the two can be one blob (``ParamSpec.name``: a tied head).

    The product runs in the net's compute dtype like any other layer's and
    the softmax in float32 here, so the layer does not ask for the loss
    layers' float32 casts: ``is_loss`` is false, and the net's builder
    gives the top its ``loss_weight: 1``."""

    def min_bottoms(self) -> int:
        return 2

    def is_loss(self) -> bool:
        return False

    def out_shapes(self, lp, bottom_shapes):
        vocab = int(lp.sub("lm_head_param").get("vocab", 0))
        logits = [tuple(bottom_shapes[0][:-1]) + (vocab,)]
        return [()] + (logits if len(lp.top) > 1 else [])

    def top_has_batch_axis(self, lp, top_index: int) -> bool:
        return top_index > 0

    def init(self, rng, lp, bottom_shapes):
        p = lp.sub("lm_head_param")
        shape = (bottom_shapes[0][-1], int(p.get("vocab", 0)))
        if p.get("transposed", False):
            shape = shape[::-1]
        return [fill(rng, _filler(p), shape)]

    def apply(self, lp, params, bottoms, train, rng):
        (w,) = params
        hidden, tokens = bottoms
        want_logits = len(lp.top) > 1
        if lp.sub("lm_head_param").get("transposed", False):
            w = w.T

        def one(x, ids):
            logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
            logp = jax.nn.log_softmax(logits[:-1], axis=-1)
            nll = -jnp.take_along_axis(
                logp, ids[1:, None].astype(jnp.int32), axis=-1)
            return jnp.sum(nll), (logits if want_logits else ())

        total, logits = _per_sequence(one, hidden, tokens)
        n, s = tokens.shape
        loss = jnp.sum(total) / (n * (s - 1))
        return [loss] + ([logits] if want_logits else [])
