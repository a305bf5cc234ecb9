"""Compile-only checks against the TPU's own compiler, with no chip.

libtpu compiles for a v5e that is described and not attached, Mosaic
included, so what the chip's compiler would refuse, or answer with a copy,
shows here at no chip time.  Nothing runs: no result, no time.  Every test
that needs the description is in this file (one process loads libtpu, and
keeps it), and the topology is described inside a fixture, after collection.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEOM = (5, 1e-4, 0.75, 1.0)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_branch(monkeypatch):
    """Trace-time checks of the backend take the chip's branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def lowered_text():
    spec = importlib.util.spec_from_file_location(
        "lowered_text", os.path.join(ROOT, "tools", "lowered_text.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# the cells' LRN shapes (bfloat16 resident, float32 rounds) and the small
# batches a test net and the classify engine bring
_KERNEL_SHAPES = [
    ((1024, 96, 27, 27), "bfloat16"), ((1024, 256, 13, 13), "bfloat16"),
    ((256, 64, 56, 56), "bfloat16"), ((256, 192, 56, 56), "bfloat16"),
    ((512, 96, 27, 27), "float32"), ((512, 256, 13, 13), "float32"),
    ((50, 96, 27, 27), "float32"), ((1, 96, 27, 27), "bfloat16"),
    ((8, 256, 13, 13), "bfloat16"), ((16, 96, 55, 55), "float32"),
    ((10, 192, 56, 56), "bfloat16"),
]


@pytest.mark.parametrize(
    "shape,dtype", _KERNEL_SHAPES,
    ids=["x".join(map(str, s)) + "-" + d for s, d in _KERNEL_SHAPES])
def test_epilogue_kernels_compile_at_real_widths(one_chip, shape, dtype):
    """Mosaic takes the forward, backward and inference kernels at the
    shapes the cells and the small batches run, in either lane choice."""
    from sparknet_tpu.ops.pallas_kernels import relu_lrn_across_channels

    def all_three(x, dy):
        y, vjp = jax.vjp(
            lambda x: relu_lrn_across_channels(x, *GEOM, True), x)
        return y, vjp(dy)[0], relu_lrn_across_channels(x, *GEOM, False)

    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    text = jax.jit(all_three).lower(x, x).compile().as_text()
    for name in ("relu_lrn_fwd", "relu_lrn_bwd", "relu_lrn_infer"):
        assert name in text


def _two_chain_front(batch: int):
    """CaffeNet's front cut down: conv → relu → pool → LRN twice, then an
    inner product and the loss; channels as published, 67x67 images."""
    from sparknet_tpu.models.dsl import (
        convolution_layer, inner_product_layer, layer, lrn_layer, net_param,
        pooling_layer, relu_layer, softmax_with_loss_layer)
    gauss = {"type": "gaussian", "std": 0.01}
    return net_param("front", [
        layer("data", "Input", tops=["data", "label"], input_param={
            "shape": [{"dim": [batch, 3, 67, 67]}, {"dim": [batch]}]}),
        convolution_layer("conv1", "data", "conv1", num_output=96,
                          kernel=11, stride=4, weight_filler=gauss),
        relu_layer("relu1", "conv1"),
        pooling_layer("pool1", "conv1", "pool1", kernel=3, stride=2),
        lrn_layer("norm1", "pool1", "norm1", alpha=1e-4),
        convolution_layer("conv2", "norm1", "conv2", num_output=256,
                          kernel=5, pad=2, group=2, weight_filler=gauss),
        relu_layer("relu2", "conv2"),
        pooling_layer("pool2", "conv2", "pool2", kernel=3, stride=2),
        lrn_layer("norm2", "pool2", "norm2", alpha=1e-4),
        convolution_layer("conv3", "norm2", "conv3", num_output=384,
                          kernel=3, pad=1, weight_filler=gauss),
        relu_layer("relu3", "conv3"),
        inner_product_layer("ip", "conv3", "ip", num_output=10,
                            weight_filler=gauss),
        softmax_with_loss_layer("loss", ["ip", "label"])])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_no_copy_at_the_epilogue_kernels_edges(one_chip, chip_branch,
                                               lowered_text, dtype):
    """At batch 128 the compiled forward and backward of a conv..LRN chain
    hold the kernels once a layer each and no layout copy feeding them,
    reading them, or attributed to them: the kernel's operand is the
    layout its neighbours keep (``tools/lowered_text.py --set edges`` is
    the same check on the cells' whole steps)."""
    from sparknet_tpu.graph import Net
    from sparknet_tpu.proto import NetState, Phase
    net = Net(_two_chain_front(128), NetState(Phase.TRAIN),
              compute_dtype=None if dtype == "float32" else jnp.bfloat16)
    assert [c.scope() for c in net._fuse_plan.chains] == [
        "conv1+relu1+pool1+norm1", "conv2+relu2+pool2+norm2"]
    key = jax.random.PRNGKey(0)

    def struct(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    params = struct(jax.eval_shape(net.init, key))
    ins = {b: jax.ShapeDtypeStruct(s, np.float32, sharding=one_chip)
           for b, s in net.input_blobs.items()}
    grad = jax.jit(jax.grad(lambda p, ins: net.apply(p, ins, rng=key).loss))
    hlo = grad.lower(params, ins).compile().as_text()
    calls, copies = lowered_text.kernel_edge_copies(hlo)
    assert calls == {"relu_lrn_fwd": 2, "relu_lrn_bwd": 2}
    assert copies == []


def test_edge_copies_are_found_where_they_are(lowered_text):
    """The reader itself, on a module's text with the three kinds of copy
    the parent's step had: one feeding a kernel through a bitcast, one
    reading its result, one only attributed to the ``pallas_call``."""
    hlo = """
HloModule m
%fused_copy (p: bf16[8,4,9]) -> bf16[8,4,9] {
  %p = bf16[8,4,9]{0,1,2} parameter(0)
  ROOT %c = bf16[8,4,9]{2,1,0} copy(%p)
}
ENTRY %main (a: bf16[8,4,9]) -> bf16[8,4,9] {
  %a = bf16[8,4,9]{0,1,2} parameter(0)
  %copy.1 = bf16[8,4,9]{2,1,0} copy(%a), metadata={op_name="jit(f)/reshape"}
  %bitcast.1 = bf16[8,4,9]{2,1,0} bitcast(%copy.1)
  %k = bf16[8,4,9]{2,1,0} custom-call(%bitcast.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/relu_lrn_fwd/pallas_call"}
  %fusion.7 = bf16[8,4,9]{2,1,0} fusion(%k), kind=kLoop, calls=%fused_copy
  %copy.3 = bf16[8,4,9]{0,1,2} copy(%a), metadata={op_name="jit(f)/relu_lrn_bwd/pallas_call"}
  ROOT %copy.9 = bf16[8,4,9]{0,1,2} copy(%a), metadata={op_name="jit(f)/conv"}
}
"""
    calls, copies = lowered_text.kernel_edge_copies(hlo)
    assert calls == {"relu_lrn_fwd": 1}
    assert {(c["name"], c["kernel"], c["edge"], c["bytes"])
            for c in copies} == {
        ("copy.1", "relu_lrn_fwd", "in", 576),
        ("fusion.7", "relu_lrn_fwd", "out", 576),
        ("copy.3", "relu_lrn_bwd", "attributed", 576)}


# -- the sequence layers (ops/sequence.py) at Laguna XS.2's widths ------------

_ATTENTION = [("sliding", 64, 512), ("full", 48, 0)]


def _attention_gradient(one_chip, kind, heads):
    """The gradient of one sequence of 8,192 positions through an attention
    layer of the published widths, compiled for the described chip."""
    from sparknet_tpu import models
    from sparknet_tpu.ops import get_layer_impl
    net = models.laguna(1, 1, num_layers=2, vocab=128, experts_held=(0, 8))
    lp = next(l for l in net.layer
              if l.name == ("L1/attn" if kind == "sliding" else "L0/attn"))
    impl = get_layer_impl("Attention")
    shapes = jax.eval_shape(lambda r: impl.init(r, lp, [(1, 8192, 2048)]),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert shapes[0].shape == (2048, heads * 128)
    bf16 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                          sharding=one_chip)

    def loss(params, x):
        return jnp.sum(impl.apply(lp, params, [x], True, None)[0]
                       .astype(jnp.float32))

    return jax.jit(jax.grad(loss, (0, 1))).lower(
        [bf16(s) for s in shapes],
        bf16(jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16))).compile()


@pytest.mark.parametrize("kind,heads,window", _ATTENTION,
                         ids=[k for k, _, _ in _ATTENTION])
def test_attention_layer_compiles_at_real_widths(one_chip, chip_branch,
                                                 lowered_text, kind, heads,
                                                 window):
    """One sequence of 8,192 positions through an attention layer of the
    published widths, forward and backward, at the blocks and the backward
    form ``flash_blocks`` gives: the flash kernels are in the program (the
    forward one, recomputed for the backward pass; ``dkv``; ``dq`` in the
    sliding layer alone, the full layer's fused kernel makes it and JAX
    sums its partials), nothing is copied at their edges but JAX's own
    log-sum-exp, and no score matrix is held."""
    from sparknet_tpu.ops import sequence
    blocks = sequence.flash_blocks(8192, window, 128)
    assert blocks.fused == (kind == "full")
    compiled = _attention_gradient(one_chip, kind, heads)
    text = compiled.as_text()
    calls, copies = lowered_text.kernel_edge_copies(text)
    want = {"splash_mqa_fwd_residuals": 1, "splash_mqa_dkv_no_residuals": 1}
    if not blocks.fused:
        want["splash_mqa_dq_no_residuals"] = 1
    assert calls == want
    assert {c["kernel"] for c in copies} <= {"splash_mqa_fwd_residuals"}
    sums = lowered_text.partial_dq_sums(lowered_text.written_bytes(text)[1])
    assert [s["bytes"] for s in sums] == (
        [8192 * heads * 128 * 2] if blocks.fused else [])
    # a sequence's scores for one head alone would be 268 MB in float32
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


# shapes the cells do not run: what ``flash_blocks`` accepts, Mosaic takes
_FLASH_TILINGS = [(1024, 0, 64), (384, 0, 64), (640, 128, 128),
                  (1280, 256, 64), (4096, 1024, 128), (16384, 0, 128),
                  (2048, 0, 128)]


@pytest.mark.parametrize("positions,window,head_dim", _FLASH_TILINGS,
                         ids=["x".join(map(str, t)) for t in _FLASH_TILINGS])
def test_the_chip_takes_what_flash_blocks_can_tile(one_chip, positions,
                                                   window, head_dim):
    """The core's gradient for two query heads over one key/value head at
    the blocks the rule gives: compiled by Mosaic, fused or split as the
    rule says."""
    from sparknet_tpu.ops import sequence
    blocks = sequence.flash_blocks(positions, window, head_dim)
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(sequence.attn_core(q, k, v, window, "splash")
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        arg(1, 2, positions, head_dim), arg(1, positions, head_dim),
        arg(1, positions, head_dim)).compile().as_text()
    assert "splash_mqa_dkv_no_residuals" in text
    assert ("splash_mqa_dq_no_residuals" in text) == (not blocks.fused)


# what the compiled gradient of one layer may write a sequence outside
# matrix-product fusions, Pallas calls and JAX's sum of a fused backward
# kernel's partial dq: what PR 36's code reaches and a tenth more (the
# parent of PR 36: 2.75 and 3.72 GB)
_ATTENTION_WRITES = [("full", 48, 0.43e9), ("sliding", 64, 0.54e9)]


@pytest.mark.parametrize("kind,heads,most", _ATTENTION_WRITES,
                         ids=[k for k, *_ in _ATTENTION_WRITES])
def test_attention_layer_keeps_one_layout_and_width(
        one_chip, chip_branch, lowered_text, kind, heads, most):
    """Between the projections and the flash kernels every tensor is made
    once, in the kernels' own shape and layout in bfloat16: the compiled
    backward pass of one sequence (the recomputed forward and the
    backward) writes little outside products and kernels (half of it the
    log-sum-exp that JAX's wrapper of the kernels copies), and outside
    that wrapper no slice, concatenate or copy makes a float32 array the
    size of the queries."""
    text = _attention_gradient(one_chip, kind, heads).as_text()
    written, ops = lowered_text.written_bytes(text)
    assert written["kernel"] > 0 and written["product"] > 0
    summed = sum(s["bytes"] for s in lowered_text.partial_dq_sums(ops))
    assert written["other"] - summed <= most
    queries = 8192 * heads * 128 * 4
    assert [o["name"] for o in ops
            if o["opcode"] in ("slice", "concatenate", "copy")
            and o["shape"].startswith("f32") and o["bytes"] == queries
            and "/attn_core/" not in o["op_name"]] == []


def test_written_bytes_sorts_operations_by_class(lowered_text):
    """The reader itself, on a module's text: a loop's body counted once,
    a fusion as one operation, products and kernels apart from the rest."""
    hlo = """
HloModule m
%fused_dot (p: bf16[8,4], q: bf16[4,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  %q = bf16[4,4]{1,0} parameter(1)
  ROOT %c = bf16[8,4]{1,0} convolution(%p, %q), dim_labels=bf_io->bf
}
%fused_add (p: bf16[8,4]) -> (f32[8,4], bf16[8,4]) {
  %p = bf16[8,4]{1,0} parameter(0)
  %c = f32[8,4]{1,0} convert(%p)
  ROOT %t = (f32[8,4]{1,0}, bf16[8,4]{1,0}) tuple(%c, %p)
}
%body (t: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %t = (s32[], bf16[8,4]{1,0}) parameter(0)
  %x = bf16[8,4]{1,0} get-tuple-element(%t), index=1
  %copy.1 = bf16[8,4]{0,1} copy(%x), metadata={op_name="jit(f)/attn_core/x"}
  ROOT %r = (s32[], bf16[8,4]{1,0}) tuple(%t, %x)
}
%cond (t: (s32[], bf16[8,4])) -> pred[] {
  %t = (s32[], bf16[8,4]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}
ENTRY %main (a: bf16[8,4], w: bf16[4,4]) -> bf16[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %w = bf16[4,4]{1,0} parameter(1)
  %fusion.1 = bf16[8,4]{1,0} fusion(%a, %w), kind=kOutput, calls=%fused_dot
  %fusion.2 = (f32[8,4]{1,0}, bf16[8,4]{1,0}) fusion(%fusion.1), kind=kLoop, calls=%fused_add
  %k = bf16[8,4]{1,0} custom-call(%a), custom_call_target="tpu_custom_call"
  %s = (bf16[8,4]{1,0}, bf16[8,4]{1,0}, u32[]) copy-start(%k)
  %z = s32[] constant(0)
  %init = (s32[], bf16[8,4]{1,0}) tuple(%z, %k)
  %while.1 = (s32[], bf16[8,4]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[8,4]{1,0} get-tuple-element(%while.1), index=1
}
"""
    written, ops = lowered_text.written_bytes(hlo)
    assert written == {"product": 64, "other": 192 + 64, "kernel": 64,
                       "async": 132}
    by_name = {o["name"]: o for o in ops}
    assert by_name["fusion.2"]["fused"] == ["convert", "tuple"]
    assert by_name["copy.1"]["op_name"] == "jit(f)/attn_core/x"


def test_partial_dq_sums_are_named_as_the_librarys_own(lowered_text):
    """The reader itself: the reduction inside JAX's wrapper of the flash
    kernels that sums a fused backward kernel's partial dq is listed as
    that, alone or fused, and is no copy at the kernel's edge; a sum
    elsewhere is not listed."""
    hlo = """
HloModule m
%add (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %s = bf16[] add(%a, %b)
}
%fused_sum (p: bf16[4,8,9]) -> bf16[8,9] {
  %p = bf16[4,8,9]{2,1,0} parameter(0)
  %z = bf16[] constant(0)
  ROOT %r = bf16[8,9]{1,0} reduce(%p, %z), dimensions={0}, to_apply=%add
}
ENTRY %main (q: bf16[8,9]) -> bf16[8,9] {
  %q = bf16[8,9]{1,0} parameter(0)
  %k = (bf16[4,8,9]{2,1,0}, bf16[8,9]{1,0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/attn_core/vmap(jit(_splash_attention))/splash_mqa_dkv_no_residuals/pallas_call"}
  %parts = bf16[4,8,9]{2,1,0} get-tuple-element(%k), index=0
  %zero = bf16[] constant(0)
  %reduce.1 = bf16[8,9]{1,0} reduce(%parts, %zero), dimensions={0}, to_apply=%add, metadata={op_name="jit(f)/attn_core/vmap(jit(_splash_attention))/reduce_sum"}
  %fusion.2 = bf16[8,9]{1,0} fusion(%parts), kind=kLoop, calls=%fused_sum, metadata={op_name="jit(g)/attn_core/vmap(jit(_splash_attention))/reduce_sum"}
  ROOT %reduce.3 = bf16[8,9]{1,0} reduce(%parts, %zero), dimensions={0}, to_apply=%add, metadata={op_name="jit(f)/lm_loss/reduce_sum"}
}
"""
    calls, copies = lowered_text.kernel_edge_copies(hlo)
    assert calls == {"splash_mqa_dkv_no_residuals": 1} and copies == []
    sums = lowered_text.partial_dq_sums(lowered_text.written_bytes(hlo)[1])
    assert [(s["name"], s["bytes"]) for s in sums] == [
        ("reduce.1", 144), ("fusion.2", 144)]


def test_expert_layer_compiles_at_real_widths(one_chip, chip_branch):
    """32,768 tokens through an expert layer holding 32 of 256 experts of
    the published widths, forward and backward: the grouped products are
    kernels, sized for a quarter over the even share of rows."""
    from sparknet_tpu import models
    from sparknet_tpu.ops import get_layer_impl, sequence
    net = models.laguna(4, 1, num_layers=2, vocab=128, experts_held=(0, 32))
    lp = next(l for l in net.layer if l.name == "L1/moe")
    assert sequence.moe_row_bound(32768, sequence.moe_geometry(lp)) == 40960
    impl = get_layer_impl("MixtureOfExperts")
    shapes = jax.eval_shape(lambda r: impl.init(r, lp, [(4, 8192, 2048)]),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    assert [s.shape for s in shapes[:4]] == [
        (2048, 256), (32, 2048, 512), (32, 2048, 512), (32, 512, 2048)]
    bf16 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                          sharding=one_chip)

    def loss(params, x):
        return jnp.sum(impl.apply(lp, params, [x], True, None)[0]
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        [bf16(s) for s in shapes],
        bf16(jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16))
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 9      # 3 products x 3 passes


# -- what LFM2-24B-A2B adds, at its published widths ---------------------------

def _lfm2_layer(name, sequences=1):
    from sparknet_tpu import models
    from sparknet_tpu.ops import get_layer_impl
    net = models.lfm2(sequences, 1, layers_kept=[2, 3], vocab=128,
                      experts_held=(0, 8))
    lp = next(l for l in net.layer if l.name == name)
    impl = get_layer_impl(lp.type)
    shapes = jax.eval_shape(
        lambda r: impl.init(r, lp, [(sequences, 8192, 2048)]),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return lp, impl, shapes


def _layer_gradient(one_chip, lp, impl, shapes, sequences=1):
    bf16 = lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16,
                                          sharding=one_chip)

    def loss(params, x):
        return jnp.sum(impl.apply(lp, params, [x], True, None)[0]
                       .astype(jnp.float32))

    return jax.jit(jax.grad(loss, (0, 1))).lower(
        [bf16(s) for s in shapes],
        bf16(jax.ShapeDtypeStruct((sequences, 8192, 2048), jnp.bfloat16))
    ).compile()


def test_attention_at_head_dim_64_takes_the_flash_kernels(one_chip,
                                                          chip_branch,
                                                          lowered_text):
    """32 query heads over 8 key/value heads of 64, q and k normalised, no
    gate: Mosaic takes JAX's flash kernels at half a lane row a head, the
    forward one and the fused backward one at the blocks ``flash_blocks``
    gives a causal mask over 8,192, and no score matrix is in the program
    (one sequence's would be 8.6 GB)."""
    lp, impl, shapes = _lfm2_layer("L2/attn")
    assert [s.shape for s in shapes] == [
        (2048, 2048), (2048, 512), (2048, 512), (2048, 2048), (64,), (64,)]
    compiled = _layer_gradient(one_chip, lp, impl, shapes)
    calls, _ = lowered_text.kernel_edge_copies(compiled.as_text())
    assert calls == {"splash_mqa_fwd_residuals": 1,
                     "splash_mqa_dkv_no_residuals": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


def test_short_conv_cuts_no_third_out_of_the_lanes(one_chip, chip_branch,
                                                   lowered_text):
    """The gated short convolution's backward pass of one sequence: the
    three thirds of ``h W_in`` are one product's output ``[3, positions,
    hidden]`` with hidden on the lanes, nothing slices or concatenates a
    ``[positions, 3 x hidden]`` array, and what is written outside the
    products stays under 0.7 GB a sequence (the least is 0.10 GB: XLA
    keeps float32 intermediates between its three fusions)."""
    lp, impl, shapes = _lfm2_layer("L3/conv")
    assert [s.shape for s in shapes] == [(2048, 3, 2048), (2048, 3),
                                         (2048, 2048)]
    text = _layer_gradient(one_chip, lp, impl, shapes).as_text()
    written, ops = lowered_text.written_bytes(text)
    assert written["product"] > 0 and written["other"] <= 0.7e9
    assert "bf16[3,8192,2048]{2,1,0" in text
    wide = [o["name"] for o in ops if "8192,6144" in o["shape"]]
    assert wide == []


def test_expert_layer_of_lfm2_compiles_at_real_widths(one_chip,
                                                      chip_branch):
    """32,768 tokens through a layer holding 8 of 64 experts of 1536, top-4
    by score plus bias, no shared expert: grouped kernels in tiles that
    divide 1536, sized for a quarter over the even 16,384 rows."""
    from sparknet_tpu.ops import sequence
    lp, impl, shapes = _lfm2_layer("L2/moe", sequences=4)
    assert sequence.moe_row_bound(32768, sequence.moe_geometry(lp)) == 20480
    assert [s.shape for s in shapes] == [
        (2048, 64), (8, 2048, 1536), (8, 2048, 1536), (8, 1536, 2048),
        (64,)]
    text = _layer_gradient(one_chip, lp, impl, shapes, 4).as_text()
    assert text.count("tpu_custom_call") >= 9      # 3 products x 3 passes


# -- what DeepSeek-V2-Lite adds, at its published widths ------------------------

def _deepseek_layer(name, sequences=1):
    from sparknet_tpu import models
    from sparknet_tpu.ops import get_layer_impl
    net = models.deepseek_v2(sequences, 1, layers_kept=[0, 1], vocab=128,
                             experts_held=(0, 8))
    lp = next(l for l in net.layer if l.name == name)
    impl = get_layer_impl(lp.type)
    shapes = jax.eval_shape(
        lambda r: impl.init(r, lp, [(sequences, 8192, 2048)]),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return lp, impl, shapes


def test_latent_attention_takes_the_flash_kernels_at_192_and_128(
        one_chip, chip_branch, lowered_text):
    """One sequence of 8,192 positions through a latent attention layer of
    the published widths, forward and backward: Mosaic takes JAX's flash
    kernels at q/k heads of 192 and v heads of 128, the forward one and the
    fused backward one at the blocks ``flash_blocks`` gives, nothing is
    copied at their edges but JAX's own log-sum-exp, and no score matrix
    is held."""
    from sparknet_tpu.ops import sequence
    lp, impl, shapes = _deepseek_layer("L0/attn")
    assert [s.shape for s in shapes] == [
        (2048, 16 * 192), (2048, 512), (2048, 64), (512,), (512, 16 * 256),
        (16 * 128, 2048)]
    assert sequence.flash_blocks(8192, 0, 192, 128).dkv == (512, 2048, 512)
    compiled = _layer_gradient(one_chip, lp, impl, shapes)
    calls, copies = lowered_text.kernel_edge_copies(compiled.as_text())
    assert calls == {"splash_mqa_fwd_residuals": 1,
                     "splash_mqa_dkv_no_residuals": 1}
    assert {c["kernel"] for c in copies} <= {"splash_mqa_fwd_residuals"}
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


@pytest.mark.parametrize("block_q,fits", [(512, True), (1024, False)])
def test_the_fused_kernel_at_192_fits_what_the_rule_allows(
        one_chip, monkeypatch, block_q, fits):
    """The fused backward kernel over key/value blocks of 2,048 at 16 heads
    of 192/128: Mosaic takes a query block of 512 and refuses one of 1,024
    for its scoped VMEM (17.9 of 16 MB), which the heads of 128 take."""
    from sparknet_tpu.ops import sequence
    blocks = sequence.FlashBlocks((1024, 1024, 512), (block_q, 2048, 512),
                                  None)
    assert (sequence._fused_rows(block_q, 2048, 192, 128)
            <= sequence._FUSED_ROWS_MOST) == fits
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                              sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(sequence.attn_core(q, k, v, 0, "splash")
                       .astype(jnp.float32))

    monkeypatch.setattr(sequence, "flash_blocks", lambda *a, **k: blocks)
    sequence._splash_kernel.cache_clear()
    try:
        grad = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
            arg(16, 1, 8192, 192), arg(16, 8192, 192), arg(16, 8192, 128))
        if fits:
            assert "splash_mqa_dkv_no_residuals" in grad.compile().as_text()
        else:
            with pytest.raises(Exception, match="vmem"):
                grad.compile()
    finally:
        sequence._splash_kernel.cache_clear()


def test_expert_layer_of_deepseek_compiles_at_real_widths(one_chip,
                                                          chip_branch):
    """32,768 tokens through a layer holding 8 of 64 experts of 1408, top-6
    by a softmax, two shared experts of 1408 as one of 2816: grouped
    kernels in tiles that divide 1408, sized for a quarter over the even
    24,576 rows."""
    from sparknet_tpu.ops import sequence
    lp, impl, shapes = _deepseek_layer("L1/moe", sequences=4)
    assert sequence.moe_row_bound(32768, sequence.moe_geometry(lp)) == 30720
    assert [s.shape for s in shapes] == [
        (2048, 64), (8, 2048, 1408), (8, 2048, 1408), (8, 1408, 2048),
        (2048, 2816), (2048, 2816), (2816, 2048)]
    text = _layer_gradient(one_chip, lp, impl, shapes, 4).as_text()
    assert text.count("tpu_custom_call") >= 9      # 3 products x 3 passes


# -- the grouped products of float32 operands ------------------------------------

# the token cells' rows (the row bound), hidden and expert width
_EXPERT_WIDTHS = {"deepseek": (30720, 2048, 1408),
                  "lfm2": (20480, 2048, 1536), "laguna": (40960, 2048, 512)}


def _up_and_down(one_chip, m, hidden, width, dtype):
    """``rows @ up`` then ``@ down`` by ``_grouped``'s kernels over 8
    groups, the value and the three gradients, lowered for the described
    chip."""
    from sparknet_tpu.ops import sequence
    arg = lambda *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(rows, up, down, sizes):
        h = sequence._grouped(rows, up, sizes, "gmm")
        return jnp.sum(sequence._grouped(h, down, sizes, "gmm")
                       .astype(jnp.float32))

    return jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        arg(m, hidden), arg(8, hidden, width), arg(8, width, hidden),
        jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("cell", sorted(_EXPERT_WIDTHS))
def test_float32_grouped_kernels_compile_at_the_cells_widths(one_chip, cell):
    """A float32 expert layer (a ``Net`` with no ``compute_dtype``) at a
    token cell's rows and widths: Mosaic takes each of the six kernels of
    its two products at the tiles ``gmm_tiles`` gives four-byte operands."""
    text = _up_and_down(one_chip, *_EXPERT_WIDTHS[cell],
                        jnp.float32).compile().as_text()
    assert text.count("tpu_custom_call") >= 6      # 2 products x 3 kernels


def test_bfloat16_tiles_are_refused_at_float32(one_chip, monkeypatch):
    """DeepSeek's bfloat16 tiles given float32 operands ask for more scoped
    VMEM than the chip has (the forward ``gmm`` at 512x1024x1408 about 23
    MiB): why the rule reads the operands' width."""
    from sparknet_tpu.ops import sequence
    tiles = sequence.gmm_tiles
    monkeypatch.setattr(sequence, "gmm_tiles",
                        lambda m, k, n, itemsize: tiles(m, k, n, 2))
    with pytest.raises(Exception, match="vmem"):
        _up_and_down(one_chip, *_EXPERT_WIDTHS["deepseek"],
                     jnp.float32).compile()


def test_the_deepseek_cell_step_compiles_and_fits(monkeypatch, lowered_text):
    """The cell's whole step (4 sequences of 8,192 ids, Adam, bfloat16) as
    ``tools/lowered_text.py`` lowers it from shapes, compiled for the
    described chip: it fits the chip's 16 GB with its 10.2 GB of state
    (float32 weights and Adam's two moments are the arguments), every
    latent layer takes the forward kernel twice (once recomputed) and the
    fused backward kernel once, and nothing is copied at the kernels'
    edges but JAX's own log-sum-exp."""
    monkeypatch.setattr(jax, "default_backend", jax.default_backend)
    (_, (lowered, _)), = lowered_text.cell_lowered(
        True, ["deepseek_v2_lite_train_8k"]).items()
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert 7.5e9 < memory.argument_size_in_bytes < 7.8e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
    text = compiled.as_text()
    calls, copies = lowered_text.kernel_edge_copies(text)
    assert calls == {"splash_mqa_fwd_residuals": 12,
                     "splash_mqa_dkv_no_residuals": 6}
    assert {c["kernel"] for c in copies} <= {"splash_mqa_fwd_residuals"}
