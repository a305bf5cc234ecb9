"""The Mamba-2 mixer's scan and causal convolution of one sequence as
Pallas TPU kernels, one a pass.

``scan(x, dt, a, b, c, chunk, blocks)`` is ``y_t = s_t C_t`` with ``s_t =
exp(Δ_t A) s_{t-1} + Δ_t x_t ⊗ B_t`` (``ops/ssm.py``'s ``ssd_scan`` adds
``D x``), in the chunked form, with the chunk's work kept in VMEM:

- the forward kernel walks the chunks in order over a grid of ``(groups,
  blocks of chunks)``, a block one group's heads over ``blocks.fwd``
  chunks, and carries each head's state ``[P, N]`` in a float32 scratch.
  For each chunk it makes ``cs``, the running sum of ``Δ A`` within it (a
  product with a triangular matrix of ones at full float32 precision),
  ``C Bᵀ`` once for the group's heads, and for each head the decays
  ``exp(cs_i - cs_j)`` of ``j <= i`` in float32; then ``y = (C Bᵀ ∘ L ∘ Δ)
  x + exp(cs) ∘ (C · state)`` and ``state <- exp(cs_last) state + Bᵀ
  (exp(cs_last - cs) ∘ Δ ∘ x)``.  It writes ``y [H, P, S]`` float32 and,
  in the pass a backward one follows, the state each chunk enters with,
  ``[H, chunks, P, N]`` float32, the one residual the backward pass needs
  beside the inputs;
- the backward kernel walks the chunks in reverse over the same grid
  (``blocks.bwd`` chunks a block), carries the state's cotangent in
  float32, and writes ``dx``, ``dB`` and ``dC`` (summed over a group's heads
  inside the block), the cotangent of Δ where it scales the inputs and the
  decays' own, and that of ``Δ A`` (a reverse running sum within the chunk
  of the running sum's cotangent); the gradients of Δ and A are then two
  products with ``A`` and Δ in XLA.

Layouts are the mixer's: ``x`` and ``y`` ``[H, P, S]`` with the positions on
the lanes, ``B`` and ``C`` ``[G, S, N]`` with the state on the lanes, Δ ``[H,
S]`` (``[G, H / G, S]`` at the call, a bitcast).  The products take the
compute dtype (``x``'s) with float32 accumulation; the decays, running sums
and carried states stay in float32.

``conv_act(u, taps, bias, positions_last, blocks)`` is ``silu(conv(u) +
bias)`` (``ops/ssm.py``'s ``_conv_act``, a depthwise causal convolution of
``K`` taps over the positions) in the layout the mixer keeps: x ``[H, P,
S]`` (as ``[H P, S]``, a bitcast) with the positions on the lanes, B and C
``[G, S, N]`` with them on the sublanes.  One kernel body serves both, the
positions' axis a static argument:

- the forward kernel reads a block of u in the compute dtype and the taps
  and bias in float32, walks it in tiles of a few vregs, forms each tile's
  window by rolling it (with the tile before it) along the positions,
  and writes ``y`` in u's dtype;
- the backward kernel reads u and dy, forms the pre-activation again over
  each tile and the tile after it, and writes du (the window transposed:
  the cotangent rolled the other way) and, for each block, the taps' and
  the bias's gradients summed over its positions, which XLA adds up.

Where a block is not the whole sequence, the tiles before and after it come
from the neighbouring blocks through block specs of their own (``_halo``
zeroes them at the sequence's ends).  Float32 inside, nothing float32 in
HBM.  ``_INTERPRET`` runs every kernel in Pallas' interpreter, which the CPU
tests patch.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False      # tests patch this: the kernels in Pallas' interpreter
# Mosaic's scoped VMEM on a v5e is 16 MB; what a block may ask of it by
# ``_scan_vmem``
_VMEM_MOST = 16 * 2**20
# chunks a block at most: a grid step costs about 0.3 us, and on a v5e the
# forward kernel ran as fast at 4 chunks a block as at 8, the backward at 2
# as at 4
_CHUNKS_MOST = 8
_NT = (((1,), (1,)), ((), ()))          # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))          # aᵀ @ b
_HIGHEST = jax.lax.Precision.HIGHEST


class ScanBlocks(typing.NamedTuple):
    """Chunks a block of the forward and of the backward kernel."""
    fwd: int
    bwd: int

    def label(self) -> str:
        return f"fwd {self.fwd} bwd {self.bwd}"


def _scan_vmem(kernel: str, chunks: int, heads: int, head_dim: int,
               state: int, chunk: int, itemsize: int) -> int:
    """Bytes of scoped VMEM one step of a kernel holds: ``heads`` (one
    group's) over ``chunks`` chunks of ``chunk`` positions, operands of
    ``itemsize`` bytes.  Its blocks double-buffered (x, Δ and ``Δ A``, B
    and C in; forward: y and the entering states out; backward: the
    entering states and dy in, dx, dB, dC and the two cotangents of Δ
    out), the carried state, and one chunk's temporaries: ``[heads, P,
    chunk]`` float32 arrays and ``[chunk, chunk]`` float32 matrices."""
    f32 = 4
    hp = heads * head_dim
    rows = hp * chunk * itemsize + 2 * chunk * state * itemsize \
        + 2 * max(heads, 8) * chunk * f32
    if kernel == "fwd":
        rows += hp * chunk * f32 + hp * state * f32
        temps = 4 * hp * chunk * f32 + 6 * chunk * chunk * f32
    else:
        rows += 2 * (hp * chunk * itemsize + chunk * state * itemsize
                     + max(heads, 8) * chunk * f32) \
            + hp * state * f32 + hp * chunk * f32
        temps = 8 * hp * chunk * f32 + 10 * chunk * chunk * f32
    return 2 * chunks * rows + hp * state * f32 + temps


def scan_blocks(positions: int, heads: int, head_dim: int, groups: int,
                state: int, chunk: int, itemsize: int) -> ScanBlocks | None:
    """The chunks a block of each kernel for one sequence of ``positions``
    (padded to whole chunks): the most, up to ``_CHUNKS_MOST``, that divide
    the chunks and whose step fits ``_VMEM_MOST`` by ``_scan_vmem``; None
    where not one chunk fits."""
    chunks = -(-positions // chunk)
    per_group = heads // groups

    def most(kernel):
        fits = [k for k in range(1, min(chunks, _CHUNKS_MOST) + 1)
                if chunks % k == 0 and _scan_vmem(
                    kernel, k, per_group, head_dim, state, chunk,
                    itemsize) <= _VMEM_MOST]
        return max(fits, default=None)

    fwd, bwd = most("fwd"), most("bwd")
    return ScanBlocks(fwd, bwd) if fwd and bwd else None


def _carried(decay, state, fresh):
    """The state a chunk hands on: what it entered with, decayed over the
    chunk, plus what the chunk added (and, walked backward, the same for
    the state's cotangent)."""
    return decay * state + fresh


def _triangles(chunk: int):
    """``seen[i, j]`` (position ``j`` reaches position ``i``: ``j <= i``),
    and the same as float32 ``lower`` and its transpose ``upper``."""
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seen = i >= j
    return seen, seen.astype(jnp.float32), (i <= j).astype(jnp.float32)


def _dot(a, b, dims=None, exact=False):
    """``a @ b`` (or the contraction ``dims``) accumulated in float32; at
    full float32 precision where ``exact``."""
    return jax.lax.dot_general(
        a, b, dims or (((a.ndim - 1,), (0,)), ((), ())),
        precision=_HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _chunk_common(dt_ref, da_ref, b_ref, c_ref, at, lower, upper):
    """One chunk's Δ, the running sum ``cs`` of ``Δ A`` as rows ``[heads,
    L]`` and as columns ``[L, heads]``, B, C and ``C Bᵀ``."""
    dt, da = dt_ref[0, :, at], da_ref[0, :, at]
    cs = _dot(da, upper, exact=True)
    cs_col = _dot(lower, da, _NT, exact=True)
    bk, ck = b_ref[0, at, :], c_ref[0, at, :]
    return dt, cs, cs_col, bk, ck, _dot(ck, bk, _NT)


def _decay(seen, cs, cs_col, h):
    """``exp(cs_i - cs_j)`` for ``j <= i``, 0 above: head ``h``'s decays
    within the chunk, ``[L, L]`` float32."""
    return jnp.exp(jnp.where(seen, cs_col[:, h:h + 1] - cs[h:h + 1, :],
                             -jnp.inf))


def _fwd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, *rest,
                chunk: int, chunks: int):
    *s_ref, state = rest
    heads, p, _ = x_ref.shape
    n = b_ref.shape[-1]
    cd, f32 = x_ref.dtype, jnp.float32
    seen, lower, upper = _triangles(chunk)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def one(k, carry):
        at = pl.ds(pl.multiple_of(k * chunk, chunk), chunk)
        dt, cs, cs_col, bk, ck, cb = _chunk_common(
            dt_ref, da_ref, b_ref, c_ref, at, lower, upper)
        s_in = state[...]
        if s_ref:
            s_ref[0][:, k] = s_in
        x = x_ref[:, :, at]
        y = _dot(s_in.reshape(heads * p, n).astype(cd), ck, _NT).reshape(
            heads, p, chunk) * jnp.exp(cs)[:, None, :]
        for h in range(heads):
            m = (cb * _decay(seen, cs, cs_col, h) * dt[h:h + 1, :])
            y_ref[h, :, at] = y[h] + _dot(x[h], m.astype(cd), _NT)
        last = cs[:, chunk - 1:]
        xw = (x.astype(f32) * (jnp.exp(last - cs) * dt)[:, None, :]).astype(cd)
        state[...] = _carried(
            jnp.exp(last)[:, :, None], s_in,
            _dot(xw.reshape(heads * p, chunk), bk).reshape(heads, p, n))
        return carry

    jax.lax.fori_loop(0, chunks, one, 0)


def _bwd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, s_ref, dy_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dda_ref, dstate,
                *, chunk: int, chunks: int):
    heads, p, _ = x_ref.shape
    n = b_ref.shape[-1]
    cd, f32 = x_ref.dtype, jnp.float32
    seen, lower, upper = _triangles(chunk)
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    def one(r, carry):
        k = chunks - 1 - r
        at = pl.ds(pl.multiple_of(k * chunk, chunk), chunk)
        dt, cs, cs_col, bk, ck, cb = _chunk_common(
            dt_ref, da_ref, b_ref, c_ref, at, lower, upper)
        s_in = s_ref[:, k]                                 # [heads, P, N]
        ds_out = dstate[...]
        x = x_ref[:, :, at]
        dy = dy_ref[:, :, at]                              # float32
        e_cs = jnp.exp(cs)
        last = cs[:, chunk - 1:]
        wd = jnp.exp(last - cs)
        w = wd * dt
        flat = lambda t: t.reshape(heads * p, t.shape[-1])
        s_cd, ds_cd = flat(s_in).astype(cd), flat(ds_out).astype(cd)
        # the state a chunk enters with, read out by C
        ys = _dot(s_cd, ck, _NT).reshape(heads, p, chunk)
        dye = dy * e_cs[:, None, :]
        dcs = jnp.sum(dy * ys, axis=1) * e_cs
        dc = _dot(flat(dye).astype(cd), s_cd, _TN)
        # what the chunk adds to the state it hands on
        sb = _dot(ds_cd, bk, _NT).reshape(heads, p, chunk)
        xf = x.astype(f32)
        dw = jnp.sum(xf * sb, axis=1)
        dx = sb * w[:, None, :]
        xw = flat(xf * w[:, None, :]).astype(cd)
        db = _dot(xw, ds_cd, _TN)
        ddt = dw * wd
        dcs = dcs - dw * w
        ends = (jnp.sum(dw * w, axis=1, keepdims=True) + jnp.exp(last)
                * jnp.sum(jnp.sum(ds_out * s_in, axis=2), axis=1,
                          keepdims=True))
        dcs = dcs + jnp.where(lane == chunk - 1, ends, 0.0)
        # within the chunk: with ``t = dM ∘ M``, ``cs_i`` gets t's sum over
        # ``j`` and ``cs_j`` minus its sum over ``i``; the two cancel in A's
        # gradient, so both are taken from the one float32 ``t``
        dcb = jnp.zeros((chunk, chunk), f32)
        dcs_col = jnp.zeros((chunk, heads), f32)
        for h in range(heads):
            decay = _decay(seen, cs, cs_col, h)
            dyh = dy[h].astype(cd)
            m = (cb * decay * dt[h:h + 1, :]).astype(cd)
            dx_ref[h, :, at] = (dx[h] + _dot(dyh, m)).astype(dx_ref.dtype)
            dm = _dot(dyh, x[h], _TN)                     # [L, L]
            q = dm * cb * decay
            t = q * dt[h:h + 1, :]
            dcb = dcb + dm * decay * dt[h:h + 1, :]
            ddt = ddt + jnp.where(row == h, jnp.sum(q, axis=0,
                                                    keepdims=True), 0.0)
            dcs = dcs - jnp.where(row == h, jnp.sum(t, axis=0,
                                                    keepdims=True), 0.0)
            dcs_col = dcs_col + jnp.where(col == h, jnp.sum(
                t, axis=1, keepdims=True), 0.0)
        dcb = dcb.astype(cd)
        dc = dc + _dot(dcb, bk)
        db = db + _dot(dcb, ck, _TN)
        db_ref[0, at, :] = db.astype(db_ref.dtype)
        dc_ref[0, at, :] = dc.astype(dc_ref.dtype)
        ddt_ref[0, :, at] = ddt
        dda_ref[0, :, at] = (_dot(dcs, lower, exact=True)
                             + _dot(dcs_col, lower, _TN, exact=True))
        dstate[...] = _carried(jnp.exp(last)[:, :, None], ds_out,
                               _dot(flat(dye).astype(cd), ck).reshape(
                                   heads, p, n))
        return carry

    jax.lax.fori_loop(0, chunks, one, 0)


def _specs(heads, p, n, chunk, k, nb, reverse):
    """Block specs over the grid ``(groups, blocks)``: x-like ``[H, P, S]``,
    Δ-like ``[G, H / G, S]``, B-like ``[G, S, N]``, states ``[H, chunks, P,
    N]``; blocks in reverse order where ``reverse``."""
    blk = (lambda j: nb - 1 - j) if reverse else (lambda j: j)
    t = k * chunk
    return dict(
        x=pl.BlockSpec((heads, p, t), lambda g, j: (g, 0, blk(j))),
        dt=pl.BlockSpec((1, heads, t), lambda g, j: (g, 0, blk(j))),
        b=pl.BlockSpec((1, t, n), lambda g, j: (g, blk(j), 0)),
        s=pl.BlockSpec((heads, k, p, n), lambda g, j: (g, blk(j), 0, 0)))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _forward(x, dt, da, b, c, chunk, k, keep_states=True):
    """``y [H, P, S]`` float32 and, where ``keep_states``, the state each
    chunk enters with ``[H, chunks, P, N]`` float32."""
    heads, p, s = x.shape
    groups, _, n = b.shape
    hg, nc = heads // groups, s // chunk
    nb = nc // k
    sp = _specs(hg, p, n, chunk, k, nb, False)
    out_shape = [jax.ShapeDtypeStruct((heads, p, s), jnp.float32)]
    out_specs = [sp["x"]]
    if keep_states:
        out_shape.append(jax.ShapeDtypeStruct((heads, nc, p, n), jnp.float32))
        out_specs.append(sp["s"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=k),
        out_shape=tuple(out_shape),
        grid=(groups, nb),
        in_specs=[sp["x"], sp["dt"], sp["dt"], sp["b"], sp["b"]],
        out_specs=tuple(out_specs),
        scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)],
        compiler_params=_params(), interpret=_INTERPRET,
        name="ssd_scan_fwd",
    )(x, dt.reshape(groups, hg, s), da.reshape(groups, hg, s), b, c)


def _backward(x, dt, da, b, c, states, dy, chunk, k):
    heads, p, s = x.shape
    groups, _, n = b.shape
    hg, nc = heads // groups, s // chunk
    nb = nc // k
    sp = _specs(hg, p, n, chunk, k, nb, True)
    dx, db, dc, ddt, dda = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=k),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct((groups, hg, s), jnp.float32),
                   jax.ShapeDtypeStruct((groups, hg, s), jnp.float32)),
        grid=(groups, nb),
        in_specs=[sp["x"], sp["dt"], sp["dt"], sp["b"], sp["b"], sp["s"],
                  sp["x"]],
        out_specs=(sp["x"], sp["b"], sp["b"], sp["dt"], sp["dt"]),
        scratch_shapes=[pltpu.VMEM((hg, p, n), jnp.float32)],
        compiler_params=_params(), interpret=_INTERPRET,
        name="ssd_scan_bwd",
    )(x, dt.reshape(groups, hg, s), da.reshape(groups, hg, s), b, c,
      states, dy)
    return dx, db, dc, ddt.reshape(heads, s), dda.reshape(heads, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def scan(x, dt, a, b, c, chunk: int, blocks: ScanBlocks):
    """``y [H, P, S]`` float32 of ``x [H, P, S]``, Δ ``dt [H, S]`` and
    ``a [H]`` float32, ``b``, ``c [G, S, N]``; ``S`` whole chunks."""
    return _forward(x, dt, dt * a[:, None], b, c, chunk, blocks.fwd,
                    keep_states=False)[0]


def _scan_fwd(x, dt, a, b, c, chunk, blocks):
    y, states = _forward(x, dt, dt * a[:, None], b, c, chunk, blocks.fwd)
    return y, (x, dt, a, b, c, states)


def _scan_bwd(chunk, blocks, saved, dy):
    x, dt, a, b, c, states = saved
    dx, db, dc, ddt, dda = _backward(x, dt, dt * a[:, None], b, c, states,
                                     dy, chunk, blocks.bwd)
    return (dx, ddt + dda * a[:, None], jnp.sum(dda * dt, axis=1), db, dc)


scan.defvjp(_scan_fwd, _scan_bwd)


# -- the causal convolution, its bias and the SiLU ---------------------------

# f32 elements of one inner step's tile where the positions ride the lanes
# and where they ride the sublanes: a step of the kernels walks a block in
# such tiles, so what it holds at once is a few tiles whatever the block.
# Mosaic's static schedule for a described v5e, in bundles a vreg of output
# (forward, backward): on the lanes 14.5, 44.5 at 4 vregs a tile; 10.4, 26.6
# at 8; 8.5, 22.8 at 16; 7.4, 18.8 at 32; 6.8, 18.4 at 64; on the sublanes
# 9.8, 19.5 at 4; 7.3, 15.1 at 8; 5.9, 14.7 at 16; 6.8, 15.9 at 32
_TILE_LANES = 32 * 1024
_TILE_SUBLANES = 16 * 1024


class ConvBlocks(typing.NamedTuple):
    """``(channels, positions)`` a block of the forward and of the backward
    kernel of the causal convolution."""
    fwd: tuple[int, int]
    bwd: tuple[int, int]

    def label(self) -> str:
        return "fwd {}x{} bwd {}x{}".format(*self.fwd, *self.bwd)


def _halo_width(positions_last: bool, itemsize: int) -> int:
    """Positions in the tile beside a block that the window reaches into:
    one lane row where the positions ride the lanes, one packed sublane
    tile where they ride the sublanes."""
    return 128 if positions_last else 32 // itemsize


def _taps_rows(kernel: int, positions_last: bool) -> int:
    """Rows of the packed taps-and-bias operand across the channels (the
    sublanes' form keeps taps and bias as rows of a tile of 8)."""
    return kernel + 1 if positions_last else -(-(kernel + 1) // 8) * 8


def _conv_tiles(rows: int, positions: int, positions_last: bool):
    """One inner step's ``(channels, positions)`` of a block: a packed
    sublane tile of channels (16 rows, or 8) and ``_TILE_LANES`` over that
    lanes of positions, or every channel (the lanes) and
    ``_TILE_SUBLANES`` over them sublanes of positions."""
    if positions_last:
        rg = 8 if rows % 16 else 16
        return rg, min(positions, max(128, _TILE_LANES // rg))
    return rows, min(positions, max(16, _TILE_SUBLANES // max(rows, 128)))


def _conv_vmem(kernel: str, rows: int, positions: int, taps: int,
               positions_last: bool, itemsize: int) -> int:
    """Bytes of scoped VMEM one step of a kernel holds for a block of
    ``rows`` channels over ``positions``: its blocks double-buffered (the
    forward kernel u and the halo tile before it in, y out; the backward
    one u with the tiles before and after it and dy with the tile after it
    in, du and the taps' partial sums out), the packed taps, and an inner
    step's temporaries, about twenty float32 tiles."""
    f32, lane = 4, 128
    halo = _halo_width(positions_last, itemsize)
    if positions_last:
        tile = rows * halo * itemsize
        packed = rows * lane * f32                 # taps lane-padded
    else:
        tile = halo * max(rows, lane) * itemsize
        packed = _taps_rows(taps, False) * max(rows, lane) * f32
    block = rows * positions * itemsize
    rg, w = _conv_tiles(rows, positions, positions_last)
    temps = 20 * (rg * (w + 2 * halo) if positions_last
                  else (w + 2 * halo) * max(rows, lane)) * f32
    if kernel == "fwd":
        held = 2 * block + tile + packed
    else:
        held = 3 * block + 3 * tile + 2 * packed
    return 2 * held + temps


def conv_blocks(positions: int, rows: int, kernel: int, itemsize: int,
                positions_last: bool) -> ConvBlocks | None:
    """The blocks of the convolution's two kernels over one sequence of
    ``positions`` and ``rows`` channels (``[rows, positions]`` with the
    positions on the lanes, or ``[positions, rows]`` on the sublanes, one
    group a block), taps of ``kernel``, operands of ``itemsize`` bytes: the
    whole sequence a block where it fits ``_VMEM_MOST`` by ``_conv_vmem``,
    else the longest run of positions that does, and of the channels the
    most; on the lanes a block of channels is whole packed sublane tiles.
    None where not one block fits, or the taps reach past the tile beside a
    block."""
    halo = _halo_width(positions_last, itemsize)
    if positions % halo or kernel - 1 > halo:
        return None
    sub = 32 // itemsize
    chans = ([r for r in range(sub, rows + 1, sub) if rows % r == 0]
             if positions_last else [rows])
    runs = [t for t in range(positions, 0, -halo) if positions % t == 0]

    def most(kind):
        for t in runs:
            fits = [r for r in chans if _conv_vmem(
                kind, r, t, kernel, positions_last, itemsize) <= _VMEM_MOST
                and t % _conv_tiles(r, t, positions_last)[1] == 0]
            if fits:
                return max(fits), t
        return None

    fwd, bwd = most("fwd"), most("bwd")
    return ConvBlocks(fwd, bwd) if fwd and bwd else None


def _halo(tile, edge):
    """The tile beside a block, read from the neighbouring block; zeros
    where the block is at the sequence's edge, where the window reaches
    past it."""
    return jnp.where(edge, jnp.zeros_like(tile), tile)


def _at(ax, chans, pos):
    """An index of a block: ``[channels, positions]`` where ``ax`` is 1,
    ``[positions, channels]`` where it is 0."""
    return (chans, pos) if ax else (pos, chans)


def _cut(t, start, size, ax):
    return jax.lax.slice_in_dim(t, start, start + size, axis=ax)


def _taps(w, kernel, ax):
    """Half the taps ``0 .. kernel - 1`` and half the bias of the packed
    operand, each broadcast over one vreg of positions (128 lanes, 8
    sublanes), so a step's products repeat whole vregs and broadcast
    nothing.  Halved (exactly) because the kernels form ``h = acc / 2``:
    ``silu(acc) = h (1 + tanh h)``, by the EUP's tanh and no division."""
    if ax:
        return [jnp.broadcast_to(0.5 * w[:, i:i + 1], (w.shape[0], 128))
                for i in range(kernel + 1)]
    return [jnp.broadcast_to(0.5 * w[i:i + 1, :], (8, w.shape[1]))
            for i in range(kernel + 1)]


def _fold(t, size, ax):
    """``t`` summed over its positions into ``size`` of them: on the lanes
    at once, across the lanes (the XLU has room there, the registers
    not); on the sublanes into a vreg of partial sums, whole vregs added,
    summed across the sublanes once a block."""
    if size == 1:
        return jnp.sum(t, axis=ax, keepdims=True)
    return sum(_cut(t, k, size, ax) for k in range(0, t.shape[ax], size))


def _spread(t, like, ax):
    """A tap broadcast over one vreg of positions, repeated to ``like``'s
    positions."""
    n = like.shape[ax] // t.shape[ax]
    return t if n == 1 else jnp.concatenate([t] * n, axis=ax)


def _shifted(ext, halo, size, kernel, ax):
    """``ext`` (a halo tile, then the positions) shifted by ``j`` toward
    later positions, ``j`` of ``0 .. kernel - 1``, past the halo: the
    windows of taps ``kernel - 1 - j``."""
    return [_cut(ext if j == 0 else pltpu.roll(ext, j, ax), halo, size, ax)
            for j in range(kernel)]


def _conv_fwd_kernel(u_ref, prev_ref, w_ref, y_ref, *, ax: int, kernel: int,
                     tiles: tuple[int, int]):
    rg, wd = tiles
    halo = prev_ref.shape[ax]
    chans, run = u_ref.shape[::1 if ax else -1]
    nc = run // wd
    f32 = jnp.float32
    first = pl.program_id(1) == 0

    def rows(k, carry):
        rs = pl.ds(pl.multiple_of(k * rg, rg), rg) if ax else slice(None)
        taps = _taps(w_ref[rs, :], kernel, ax)
        edge = _halo(prev_ref[rs, :].astype(f32), first)

        def step(c, carry):
            p0 = pl.multiple_of(c * wd, wd)
            back = pl.multiple_of(jnp.maximum(p0 - halo, 0), halo)
            prev = jnp.where(c == 0, edge, u_ref[_at(ax, rs, pl.ds(
                back, halo))].astype(f32))
            cur = u_ref[_at(ax, rs, pl.ds(p0, wd))].astype(f32)
            ext = jnp.concatenate([prev, cur], axis=ax)
            h = _spread(taps[kernel], cur, ax)
            for j, s in enumerate(_shifted(ext, halo, wd, kernel, ax)):
                h = h + s * _spread(taps[kernel - 1 - j], s, ax)
            y_ref[_at(ax, rs, pl.ds(p0, wd))] = (
                h + h * jnp.tanh(h)).astype(y_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, nc, step, carry)

    jax.lax.fori_loop(0, chans // rg if ax else 1, rows, 0)


def _conv_bwd_kernel(u_ref, prev_ref, next_ref, dy_ref, dnext_ref, w_ref,
                     du_ref, dw_ref, *, ax: int, kernel: int,
                     tiles: tuple[int, int]):
    rg, wd = tiles
    halo = prev_ref.shape[ax]
    chans, run = u_ref.shape[::1 if ax else -1]
    nc, n = run // wd, wd + halo
    f32 = jnp.float32
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1

    def rows(k, carry):
        rs = pl.ds(pl.multiple_of(k * rg, rg), rg) if ax else slice(None)
        taps = _taps(w_ref[rs, :], kernel, ax)
        edge = _halo(prev_ref[rs, :].astype(f32), first)
        tail = _halo(next_ref[rs, :].astype(f32), last)
        dtail = _halo(dnext_ref[rs, :].astype(f32), last)

        def step(c, sums):
            p0 = pl.multiple_of(c * wd, wd)
            at = lambda start: _at(ax, rs, pl.ds(pl.multiple_of(
                start, halo), halo))
            here = _at(ax, rs, pl.ds(p0, wd))
            end = jnp.minimum(p0 + wd, run - halo)
            prev = jnp.where(c == 0, edge, u_ref[at(
                jnp.maximum(p0 - halo, 0))].astype(f32))
            nxt = jnp.where(c == nc - 1, tail, u_ref[at(end)].astype(f32))
            dnxt = jnp.where(c == nc - 1, dtail, dy_ref[at(end)].astype(f32))
            # the pre-activation over the step's positions and the tile
            # after them, whose cotangent reaches back into these
            ext = jnp.concatenate(
                [prev, u_ref[here].astype(f32), nxt], axis=ax)
            wins = _shifted(ext, halo, n, kernel, ax)
            h = _spread(taps[kernel], wins[0], ax)
            for j, s in enumerate(wins):
                h = h + s * _spread(taps[kernel - 1 - j], s, ax)
            # twice the pre-activation's cotangent: silu'(2h) is
            # (1 + tanh h)(1 + h (1 - tanh h)) / 2, whose half the halved
            # taps carry into du and the sums take after the loop
            th = jnp.tanh(h)
            g = (jnp.concatenate([dy_ref[here].astype(f32), dnxt], axis=ax)
                 * (1.0 + th) * (1.0 + h * (1.0 - th)))
            # the transposed window: position t takes g[t + j] by tap
            # kernel - 1 - j
            own = _cut(g, 0, wd, ax)
            du = own * _spread(taps[kernel - 1], own, ax)
            for j in range(1, kernel):
                du = du + _cut(pltpu.roll(g, n - j, ax), 0, wd, ax) * _spread(
                    taps[kernel - 1 - j], own, ax)
            du_ref[here] = du.astype(du_ref.dtype)
            # tap i's window is wins[kernel - 1 - i]; the bias's is 1
            terms = [_cut(x, 0, wd, ax) * own for x in wins[::-1]] + [own]
            return tuple(s + _fold(t, s.shape[ax], ax)
                         for s, t in zip(sums, terms))

        zero = jnp.zeros((rg, 1) if ax else (8, chans), f32)
        sums = [0.5 * jnp.sum(s, axis=ax, keepdims=True) for s in
                jax.lax.fori_loop(0, nc, step, (zero,) * (kernel + 1))]
        # taps 0 .. kernel - 1, then the bias, across the packed operand
        shape = (rg, dw_ref.shape[1]) if ax else dw_ref.shape
        pick = jax.lax.broadcasted_iota(jnp.int32, shape, ax)
        packed = jnp.zeros(shape, f32)
        for i, s in enumerate(sums):
            packed = jnp.where(pick == i, s, packed)
        dw_ref[rs, :] = packed
        return carry

    jax.lax.fori_loop(0, chans // rg if ax else 1, rows, 0)


def _conv_specs(shape, block, halo, positions_last):
    """Block specs over the grid ``(channel blocks, position blocks)`` (the
    groups, one a block, where the positions ride the sublanes): a block
    of u-like operands, and the tiles before and after it (at the
    sequence's ends the block's own first or last tile, which the kernels
    replace by zeros)."""
    chans, run = block
    tiles, ends = run // halo, shape[1] // halo - 1
    if positions_last:
        main = pl.BlockSpec((chans, run), lambda r, t: (r, t))
        tile = lambda p: pl.BlockSpec((chans, halo), lambda r, t: (r, p(t)))
    else:
        main = pl.BlockSpec((None, run, shape[2]), lambda g, t: (g, t, 0))
        tile = lambda p: pl.BlockSpec((None, halo, shape[2]),
                                      lambda g, t: (g, p(t), 0))
    return (main, tile(lambda t: jnp.maximum(t * tiles - 1, 0)),
            tile(lambda t: jnp.minimum((t + 1) * tiles, ends)))


def _conv_call(kind, u, w, kernel, positions_last, block, dy=None):
    """One pass of the convolution's kernels over ``u`` (``[C, S]`` with
    the positions on the lanes, ``[G, S, N]`` on the sublanes) with the
    packed taps and bias ``w`` (``[C, kernel + 1]``; ``[G, rows, N]``) in
    blocks of ``block``: ``y``, or ``du`` and the taps' and bias's sums
    over each block's positions, packed as ``w`` (``[blocks, C, kernel +
    1]``; ``[G, blocks, rows, N]``)."""
    halo = _halo_width(positions_last, jnp.dtype(u.dtype).itemsize)
    chans, run = block
    main, before, after = _conv_specs(u.shape, block, halo, positions_last)
    if positions_last:
        grid = (u.shape[0] // chans, u.shape[1] // run)
        wspec = pl.BlockSpec((chans, w.shape[1]), lambda r, t: (r, 0))
        sums = pl.BlockSpec((None, chans, w.shape[1]), lambda r, t: (t, r, 0))
        sums_shape = (grid[1], *w.shape)
    else:
        grid = (u.shape[0], u.shape[1] // run)
        wspec = pl.BlockSpec((None, *w.shape[1:]), lambda g, t: (g, 0, 0))
        sums = pl.BlockSpec((None, None, *w.shape[1:]),
                            lambda g, t: (g, t, 0, 0))
        sums_shape = (grid[0], grid[1], *w.shape[1:])
    body = functools.partial(
        _conv_fwd_kernel if kind == "fwd" else _conv_bwd_kernel,
        ax=int(positions_last), kernel=kernel,
        tiles=_conv_tiles(chans if positions_last else u.shape[2], run,
                          positions_last))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"))
    if kind == "fwd":
        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
            grid=grid, in_specs=[main, before, wspec], out_specs=main,
            compiler_params=params, interpret=_INTERPRET,
            name="ssm_conv_fwd")(u, u, w)
    return pl.pallas_call(
        body, out_shape=(jax.ShapeDtypeStruct(u.shape, u.dtype),
                         jax.ShapeDtypeStruct(sums_shape, jnp.float32)),
        grid=grid, in_specs=[main, before, after, main, after, wspec],
        out_specs=(main, sums), compiler_params=params,
        interpret=_INTERPRET, name="ssm_conv_bwd")(u, u, u, dy, dy, w)


def _packed(taps, bias, positions_last):
    """Taps ``[..., K]`` and bias ``[...]`` float32 as the kernels read
    them: ``[C, K + 1]`` (a channel's taps, then its bias), or ``[G, rows,
    N]`` (tap rows, then the bias, then zeros to a tile of rows)."""
    kernel = taps.shape[-1]
    if positions_last:
        return jnp.concatenate([taps.reshape(-1, kernel),
                                bias.reshape(-1, 1)], axis=1)
    g, n, _ = taps.shape
    pad = _taps_rows(kernel, False) - kernel - 1
    return jnp.concatenate([taps.swapaxes(1, 2), bias[:, None, :],
                            jnp.zeros((g, pad, n), taps.dtype)], axis=1)


def _flat(u, positions_last):
    return u.reshape(-1, u.shape[-1]) if positions_last else u


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def conv_act(u, taps, bias, positions_last: bool, blocks: ConvBlocks):
    """``silu(conv(u) + bias)`` of one sequence: ``u [..., S]`` with the
    positions on the lanes (``[H, P, S]``), taps ``[..., K]`` and bias
    ``[...]``; or ``u [G, S, N]`` with them on the sublanes, taps ``[G, N,
    K]`` and bias ``[G, N]``.  Taps and bias float32, ``u``'s dtype out;
    float32 inside."""
    return _conv_call("fwd", _flat(u, positions_last),
                      _packed(taps, bias, positions_last), taps.shape[-1],
                      positions_last, blocks.fwd).reshape(u.shape)


def _conv_act_fwd(u, taps, bias, positions_last, blocks):
    return conv_act(u, taps, bias, positions_last, blocks), (u, taps, bias)


def _conv_act_bwd(positions_last, blocks, saved, dy):
    u, taps, bias = saved
    kernel = taps.shape[-1]
    du, sums = _conv_call("bwd", _flat(u, positions_last),
                          _packed(taps, bias, positions_last), kernel,
                          positions_last, blocks.bwd,
                          _flat(dy, positions_last))
    if positions_last:
        sums = jnp.sum(sums, axis=0)
        dtaps, dbias = sums[:, :kernel], sums[:, kernel]
    else:
        sums = jnp.sum(sums, axis=1)
        dtaps, dbias = sums[:, :kernel].swapaxes(1, 2), sums[:, kernel]
    return (du.reshape(u.shape), dtaps.reshape(taps.shape),
            dbias.reshape(bias.shape))


conv_act.defvjp(_conv_act_fwd, _conv_act_bwd)
