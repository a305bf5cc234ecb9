"""The sequence layer types (``ops/sequence.py``) against the benchmark's
plain reference (``benchmark/lib/reference_lm.py``), forward and gradients,
at small sizes on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.lib import reference_lm as ref
from sparknet_tpu.models.dsl import layer
from sparknet_tpu.ops import get_layer_impl
from sparknet_tpu.ops import sequence as seq

HIDDEN, HEAD_DIM, KV, WINDOW = 32, 8, 2, 8
YARN = {"rope_theta": 500000.0, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 8, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
PLAIN = {"rope_type": "default", "rope_theta": 10000.0,
         "partial_rotary_factor": 1}
_GAUSS = {"type": "gaussian", "std": 0.3}


def highest(fn):
    return ref.highest(fn)


def init_and_apply(lp, x_shape, key=0):
    impl = get_layer_impl(lp.type)
    params = impl.init(jax.random.PRNGKey(key), lp, [x_shape])
    return impl, params


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(got - want).max()) <= tol * scale


def attention_lp(heads, window, rope):
    yarn = rope["rope_type"] == "yarn"
    p = {"num_heads": heads, "num_kv_heads": KV, "head_dim": HEAD_DIM,
         "window": window,
         "rotary_dim": int(HEAD_DIM * rope["partial_rotary_factor"]),
         "rope_theta": rope["rope_theta"], "weight_filler": _GAUSS}
    if yarn:
        p.update(yarn_factor=rope["factor"],
                 yarn_original_length=rope[
                     "original_max_position_embeddings"],
                 yarn_beta_fast=rope["beta_fast"],
                 yarn_beta_slow=rope["beta_slow"],
                 rope_attention_factor=rope["attention_factor"])
    return layer("attn", "Attention", ["x"], ["y"], attention_param=p)


# (kind, query heads a kv head, positions, rope): full and sliding, 6 and
# 8 query heads a key/value head, sequences shorter than, equal to and
# longer than the window of 8, partial rotary with YaRN and whole rotary
ATTENTION_CASES = [
    ("full", 6, 12, YARN), ("full", 8, 12, YARN),
    ("sliding", 6, 5, PLAIN), ("sliding", 6, 8, PLAIN),
    ("sliding", 6, 20, PLAIN), ("sliding", 8, 5, PLAIN),
    ("sliding", 8, 8, PLAIN), ("sliding", 8, 20, PLAIN),
    ("sliding", 6, 20, YARN), ("full", 8, 12, PLAIN),
]


@pytest.mark.parametrize("kind,group,positions,rope", ATTENTION_CASES)
def test_attention_against_reference(kind, group, positions, rope):
    heads = group * KV
    lp = attention_lp(heads, WINDOW if kind == "sliding" else 0, rope)
    impl, params = init_and_apply(lp, (2, positions, HIDDEN))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, positions, HIDDEN))
    m = {"head_dim": HEAD_DIM, "kv_heads": KV, "window": WINDOW,
         "rope": {kind: rope}}
    spec = {"kind": kind, "heads": heads}

    def system(p, x):
        return impl.apply(lp, p, [x], True, None)[0]

    def reference(p, x):
        return jnp.stack([ref.attention(xi, p, spec, m) for xi in x])

    close(system(params, x), highest(reference)(params, x))
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, positions, HIDDEN))
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   argnums=(0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                            argnums=(0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 1e-4)


@pytest.mark.parametrize("rope", [YARN, PLAIN], ids=["yarn", "default"])
def test_rope_frequencies_against_reference(rope):
    dim = int(HEAD_DIM * rope["partial_rotary_factor"])
    got = seq.rope_inv_freq(
        dim, rope["rope_theta"], rope.get("factor", 0.0),
        rope.get("original_max_position_embeddings", 0),
        rope.get("beta_fast", 32.0), rope.get("beta_slow", 1.0))
    np.testing.assert_allclose(got, ref.inv_freq(rope, HEAD_DIM), rtol=1e-12)


def rope_before(x, inv_freq, factor, scale=1.0):
    """The rotation as it was written before PR 36, kept as the oracle:
    ``x [positions, heads, head_dim]``, the head sliced at the halves and
    concatenated again, in float32."""
    half = inv_freq.shape[0]
    pos = jnp.arange(x.shape[0], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2, rest = (x32[..., :half], x32[..., half:2 * half],
                    x32[..., 2 * half:])
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return (out * scale).astype(x.dtype)


def _rope_case(head_dim, partial, yarn):
    rotary = head_dim // 2 if partial else head_dim
    inv_freq = (seq.rope_inv_freq(rotary, 500000.0, 64.0, 8, 64.0, 1.0)
                if yarn else seq.rope_inv_freq(rotary, 10000.0))
    return inv_freq, (YARN["attention_factor"] if yarn else 1.0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("scaled", [False, True], ids=["unscaled", "scaled"])
@pytest.mark.parametrize("yarn", [False, True], ids=["plain", "yarn"])
@pytest.mark.parametrize("partial", [False, True], ids=["whole", "partial"])
@pytest.mark.parametrize("head_dim", [16, 128])
def test_rope_is_the_rotation_it_was(head_dim, partial, yarn, scaled, dtype):
    """The lane-keeping rotation (cos and sin tables of the head's width
    and the half turn as a product with a matrix of 0 and ±1) gives the
    values the sliced one gave: equal in float32, within one unit of the
    last place in bfloat16."""
    inv_freq, factor = _rope_case(head_dim, partial, yarn)
    scale = head_dim ** -0.5 if scaled else 1.0
    x = (3.0 * jax.random.normal(jax.random.PRNGKey(20), (40, 3, head_dim))
         ).astype(dtype)
    want = np.asarray(rope_before(x, inv_freq, factor, scale)
                      .astype(jnp.float32)).transpose(1, 0, 2)
    got = np.asarray(seq.apply_rope(x.transpose(1, 0, 2), inv_freq, factor,
                                    scale).astype(jnp.float32))
    if dtype == "float32":
        assert np.array_equal(got, want)
    else:
        assert np.all(np.abs(got - want) <= 2.0 ** -8 * np.abs(want))


@pytest.mark.parametrize("partial", [False, True], ids=["whole", "partial"])
@pytest.mark.parametrize("head_dim", [16, 128])
def test_rope_gradient_is_the_turn_back(head_dim, partial):
    """The backward pass is written out (the rotation by the negative
    angle on the cotangent): it is the sliced formula's own gradient."""
    inv_freq, factor = _rope_case(head_dim, partial, True)
    x = jax.random.normal(jax.random.PRNGKey(21), (40, 3, head_dim))
    cot = jax.random.normal(jax.random.PRNGKey(22), (3, 40, head_dim))
    got = jax.grad(lambda x: jnp.sum(seq.apply_rope(
        x.transpose(1, 0, 2), inv_freq, factor, 0.25) * cot))(x)
    want = jax.grad(lambda x: jnp.sum(rope_before(
        x, inv_freq, factor, 0.25).transpose(1, 0, 2) * cot))(x)
    close(got, want, 1e-6)


def test_yarn_blend_at_the_published_sizes():
    """Full layers: 64 rotated dimensions, theta 500,000, factor 64 over
    an original length of 4096: the fastest pairs keep their frequency,
    the slowest are interpolated 64-fold, the ramp lies between."""
    f = seq.rope_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    plain = seq.rope_inv_freq(64, 500000.0)
    assert f.shape == (32,)
    ratio = plain / f
    assert ratio[0] == 1.0 and ratio[-1] == pytest.approx(64.0)
    # the ramp runs from pair 5 (64 turns in 4096 positions) to pair 16
    assert ratio[5] == 1.0 and ratio[16] == pytest.approx(64.0)
    assert np.all(np.diff(ratio) >= 0) and 1.0 < ratio[10] < 64.0


def test_rms_norm_against_reference():
    lp = layer("n", "RMSNorm", ["x"], ["y"], rms_norm_param={"eps": 1e-6})
    impl, (w,) = init_and_apply(lp, (2, 5, HIDDEN))
    assert np.all(np.asarray(w) == 1.0)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (HIDDEN,))
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(4), (2, 5, HIDDEN))
    system = lambda x, w: impl.apply(lp, [w], [x], True, None)[0]
    close(system(x, w), ref.norm(x, w, 1e-6))
    got = jax.grad(lambda x, w: jnp.sum(jnp.sin(system(x, w))), (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(jnp.sin(ref.norm(x, w, 1e-6))),
                    (0, 1))(x, w)
    close(got[0], want[0], 1e-4)
    close(got[1], want[1], 1e-4)


def test_gated_mlp_against_reference():
    lp = layer("m", "GatedMLP", ["x"], ["y"],
               gated_mlp_param={"width": 48, "weight_filler": _GAUSS})
    impl, params = init_and_apply(lp, (2, 7, HIDDEN))
    assert [p.shape for p in params] == [(32, 48), (32, 48), (48, 32)]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, HIDDEN))
    system = lambda p, x: impl.apply(lp, p, [x], True, None)[0]
    reference = lambda p, x: ref.mlp(x, *p)
    close(system(params, x), highest(reference)(params, x))
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) ** 2), (0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) ** 2),
                            (0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 1e-4)


# -- the router ---------------------------------------------------------------

GEOM = {"experts": 16, "top_k": 8, "lo": 0, "hi": 16, "scaling": 2.5}


def test_router_takes_the_eight_largest_of_sixteen():
    x = jax.random.normal(jax.random.PRNGKey(6), (64, HIDDEN))
    wr = 0.3 * jax.random.normal(jax.random.PRNGKey(7), (HIDDEN, 16))
    token, w, sized, sent, dropped = seq.moe_route(x, wr, GEOM)
    assert int(dropped) == 0 and int(sent.sum()) == 64 * 8
    assert np.array_equal(np.asarray(sized), np.asarray(sent))
    weight, chosen = highest(lambda x, wr: ref.route(
        x, wr, {"top_k": 8, "scaling": 2.5}))(x, wr)
    # every token's weights sum to the routed scaling factor
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, rtol=1e-6)
    per_token = np.zeros((64, 16))
    expert = np.repeat(np.arange(16), np.asarray(sent))
    np.add.at(per_token, (np.asarray(token), expert), np.asarray(w))
    want = np.zeros((64, 16))
    np.put_along_axis(want, np.asarray(chosen), np.asarray(weight), axis=-1)
    np.testing.assert_allclose(per_token, want, rtol=1e-5, atol=1e-7)


def test_router_breaks_ties_towards_the_lower_index():
    """Identical columns of the router give identical scores: program and
    reference both take the lower indices."""
    x = jax.random.normal(jax.random.PRNGKey(8), (16, HIDDEN))
    col = jax.random.normal(jax.random.PRNGKey(9), (HIDDEN, 1))
    wr = jnp.tile(col, (1, 16))
    token, w, _, sent, _ = seq.moe_route(x, wr, GEOM)
    assert np.asarray(sent).tolist() == [16] * 8 + [0] * 8
    np.testing.assert_allclose(np.asarray(w), 2.5 / 8, rtol=1e-6)
    _, chosen = ref.route(x, wr, {"top_k": 8, "scaling": 2.5})
    assert np.array_equal(np.asarray(chosen),
                          np.tile(np.arange(8), (16, 1)))


def test_router_counts_only_the_held_experts():
    x = jax.random.normal(jax.random.PRNGKey(10), (64, HIDDEN))
    wr = 0.3 * jax.random.normal(jax.random.PRNGKey(11), (HIDDEN, 16))
    _, chosen = ref.route(x, wr, {"top_k": 8, "scaling": 2.5})
    g = {**GEOM, "lo": 4, "hi": 8}
    token, w, sized, sent, dropped = seq.moe_route(x, wr, g)
    want = [(np.asarray(chosen) == e).sum() for e in range(4, 8)]
    assert np.asarray(sent).tolist() == want and int(dropped) == 0
    # the rows past the last one sent carry weight 0 and go to the last
    # held expert, so the products are sized alike whatever was chosen
    n = int(sent.sum())
    assert int(sized.sum()) == seq.moe_row_bound(64, g) == len(np.asarray(w))
    assert np.all(np.asarray(w)[n:] == 0) and np.all(np.asarray(w)[:n] > 0)


def test_row_bound_counts_what_it_leaves_out():
    """A router that sends every token to every held expert passes the
    bound of a quarter over the even share; the rows left out are
    counted, which is what makes a run not correct."""
    g = {"experts": 16, "top_k": 4, "lo": 0, "hi": 4, "scaling": 1.0}
    assert seq.moe_row_bound(512, g) == 1024          # 640 in whole tiles
    assert seq.moe_row_bound(8, g) == 32              # never above T * held
    x = jnp.ones((512, HIDDEN))
    wr = jnp.concatenate([jnp.ones((HIDDEN, 4)), -jnp.ones((HIDDEN, 12))], 1)
    _, w, sized, sent, dropped = seq.moe_route(x, wr, g)
    assert np.asarray(sent).tolist() == [512] * 4
    assert int(dropped) == 1024 and int(sized.sum()) == 1024
    assert np.asarray(sized).tolist() == [512, 512, 0, 0]


# -- the expert layer -----------------------------------------------------------

def moe_lp(lo, hi, experts=16, top_k=8, detach=False):
    return layer("moe", "MixtureOfExperts", ["x"], ["y"], moe_param={
        "num_experts": experts, "top_k": top_k, "experts_held_lo": lo,
        "experts_held_hi": hi, "expert_width": 16, "shared_width": 24,
        "routed_scaling": 2.5, "weight_filler": _GAUSS,
        "router_filler": _GAUSS, "detach_router": detach})


@pytest.mark.parametrize("lo,hi,detach", [(0, 4, False), (4, 8, False),
                                          (0, 16, False), (4, 8, True)])
def test_expert_layer_against_reference(lo, hi, detach):
    """``detach`` stops the scores' gradient at the router's input, in
    program and reference alike; the router's own gradient stays."""
    lp = moe_lp(lo, hi, detach=detach)
    impl, params = init_and_apply(lp, (2, 24, HIDDEN))
    assert params[0].shape == (HIDDEN, 16)
    assert params[1].shape == (hi - lo, HIDDEN, 16)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 24, HIDDEN))
    m = {"top_k": 8, "held": (lo, hi), "scaling": 2.5,
         "train_router": not detach}
    system = lambda p, x: impl.apply(lp, p, [x], True, None)[0]
    reference = lambda p, x: jnp.stack([ref.moe(xi, p, m) for xi in x])
    close(system(params, x), highest(reference)(params, x))
    cot = jax.random.normal(jax.random.PRNGKey(13), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   (0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                            (0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 1e-4)
    assert float(jnp.abs(got[0][0]).max()) > 0      # the router's gradient


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The parts all 8 shares of the experts give, the shared expert
    counted once, are the uncut reference's layer output."""
    whole = moe_lp(0, 16)
    impl, params = init_and_apply(whole, (1, 40, HIDDEN))
    wr, eg, eu, ed, *shared = params
    x = jax.random.normal(jax.random.PRNGKey(14), (1, 40, HIDDEN))
    m = {"top_k": 8, "held": (0, 16), "scaling": 2.5}
    uncut = highest(lambda p, x: ref.moe(x, p, m))(params, x[0])
    shared_part = highest(lambda x: ref.mlp(x, *shared))(x[0])
    total = np.zeros_like(np.asarray(uncut))
    for share in range(8):
        lo, hi = 2 * share, 2 * share + 2
        part = impl.apply(moe_lp(lo, hi),
                          [wr, eg[lo:hi], eu[lo:hi], ed[lo:hi], *shared],
                          [x], True, None)[0][0]
        total += np.asarray(part) - np.asarray(shared_part)
    close(total + np.asarray(shared_part), uncut, 1e-5)


@pytest.mark.parametrize("seed", range(5))
def test_held_experts_load_stays_in_a_binomial_band(seed):
    """Seeded weights at the router's filler the configuration assumes
    (gaussian 0.006, every column scaled to one length) and inputs of unit
    scale: the rows a held expert gets lie within five standard deviations
    of its binomial mean.  Without the scaling a column a tenth longer
    than the mean draws a fifth more rows at this width."""
    tokens, hidden, experts, top_k, held = 4096, 64, 32, 4, 8
    lp = layer("moe", "MixtureOfExperts", ["x"], ["y"], moe_param={
        "num_experts": experts, "top_k": top_k, "experts_held_lo": 0,
        "experts_held_hi": held, "expert_width": 8, "shared_width": 8,
        "router_filler": {"type": "gaussian", "std": 0.006},
        "router_column_norm": 0.006 * hidden ** 0.5,
        "weight_filler": _GAUSS})
    kx, kw = jax.random.split(jax.random.PRNGKey(100 + seed))
    wr = get_layer_impl(lp.type).init(kw, lp, [(1, tokens, hidden)])[0]
    np.testing.assert_allclose(np.linalg.norm(np.asarray(wr), axis=0),
                               0.048, rtol=1e-5)
    x = jax.random.normal(kx, (tokens, hidden))
    _, _, _, sent, dropped = seq.moe_route(x, wr, seq.moe_geometry(lp))
    p = top_k / experts
    mean, sd = tokens * p, (tokens * p * (1 - p)) ** 0.5
    assert int(dropped) == 0
    assert np.all(np.abs(np.asarray(sent) - mean) < 5 * sd)
    assert abs(int(sent.sum()) - held * mean) < 5 * sd * held ** 0.5


# -- head and loss --------------------------------------------------------------

@pytest.mark.parametrize("with_logits", [False, True])
def test_head_loss_against_reference(with_logits):
    vocab = 40
    tops = ["loss", "logits"] if with_logits else ["loss"]
    lp = layer("head", "LMHeadLoss", ["x", "tokens"], tops,
               lm_head_param={"vocab": vocab, "weight_filler": _GAUSS})
    impl = get_layer_impl("LMHeadLoss")
    assert impl.out_shapes(lp, [(2, 9, HIDDEN), (2, 9)]) == (
        [(), (2, 9, vocab)] if with_logits else [()])
    (w,) = impl.init(jax.random.PRNGKey(15), lp, [(2, 9, HIDDEN), (2, 9)])
    x = jax.random.normal(jax.random.PRNGKey(16), (2, 9, HIDDEN))
    tokens = jax.random.randint(jax.random.PRNGKey(17), (2, 9), 0, vocab)

    def reference(w, x):
        logp = jax.nn.log_softmax(x @ w, axis=-1)[:, :-1]
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return jnp.mean(nll)

    out = impl.apply(lp, [w], [x, tokens], True, None)
    close(out[0], highest(reference)(w, x))
    if with_logits:
        close(out[1], highest(lambda w, x: x @ w)(w, x))
    got = jax.grad(lambda w, x: impl.apply(lp, [w], [x, tokens], True,
                                           None)[0], (0, 1))(w, x)
    want = highest(jax.grad(reference, (0, 1)))(w, x)
    close(got[0], want[0], 1e-4)
    close(got[1], want[1], 1e-4)


# -- what LFM2 adds (reference: benchmark/lib/reference_lfm2.py) -----------------

from benchmark.lib import reference_lfm2 as ref2  # noqa: E402


def short_conv_lp(kernel=3):
    return layer("conv", "ShortConv", ["x"], ["y"], short_conv_param={
        "kernel": kernel, "weight_filler": _GAUSS, "kernel_filler": _GAUSS})


@pytest.mark.parametrize("positions", [1, 2, 3, 17])
def test_short_conv_against_reference(positions):
    """Forward and gradients, sequences shorter than, as long as and longer
    than the kernel: the first two positions see zeros before them."""
    lp = short_conv_lp()
    impl, params = init_and_apply(lp, (2, positions, HIDDEN))
    assert [p.shape for p in params] == [(HIDDEN, 3, HIDDEN), (HIDDEN, 3),
                                         (HIDDEN, HIDDEN)]
    x = jax.random.normal(jax.random.PRNGKey(30), (2, positions, HIDDEN))
    system = lambda p, x: impl.apply(lp, p, [x], True, None)[0]
    reference = lambda p, x: jnp.stack([ref2.short_conv(xi, p) for xi in x])
    close(system(params, x), highest(reference)(params, x))
    cot = jax.random.normal(jax.random.PRNGKey(31), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   (0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                            (0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 1e-4)


@pytest.mark.parametrize("kernel", [3, 4])
def test_short_conv_taps_against_a_loop(kernel):
    """The order of the thirds (B, C, x) and of the taps, against a loop
    over positions written from the equations: tap ``kernel - 1`` on the
    position itself, tap 0 on the one ``kernel - 1`` before, zeros before
    the sequence."""
    lp = short_conv_lp(kernel)
    impl, (w_in, taps, w_out) = init_and_apply(lp, (1, 6, HIDDEN))
    x = jax.random.normal(jax.random.PRNGKey(32), (1, 6, HIDDEN))
    got = np.asarray(impl.apply(lp, [w_in, taps, w_out], [x], True,
                                None)[0][0])
    h, wi, t, wo = (np.asarray(a, np.float64) for a in
                    (x[0], w_in, taps, w_out))
    thirds = h @ wi.reshape(HIDDEN, 3 * HIDDEN)
    b, c, z = (thirds[:, i * HIDDEN:(i + 1) * HIDDEN] for i in range(3))
    u = b * z
    want = np.zeros((6, HIDDEN))
    for pos in range(6):
        conv = np.zeros(HIDDEN)
        for back in range(kernel):
            if pos - back >= 0:
                conv += t[:, kernel - 1 - back] * u[pos - back]
        want[pos] = (c[pos] * conv) @ wo
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def plain_attention_lp(heads, kv, d):
    return layer("attn", "Attention", ["x"], ["y"], attention_param={
        "num_heads": heads, "num_kv_heads": kv, "head_dim": d,
        "rope_theta": 1e6, "gate": False, "qk_norm": True,
        "qk_norm_eps": 1e-5, "weight_filler": _GAUSS})


@pytest.mark.parametrize("heads,kv,d,positions", [(4, 2, 64, 12),
                                                  (8, 2, 64, 300),
                                                  (4, 4, 16, 9)])
def test_ungated_normalised_attention_against_reference(heads, kv, d,
                                                        positions):
    """``gate: false`` builds no ``W_g``; ``qk_norm`` normalises each head
    of q and of k by one weight of ``head_dim`` before rotary; at
    LFM2's head size of 64 and past one block of the reference's queries."""
    lp = plain_attention_lp(heads, kv, d)
    impl, params = init_and_apply(lp, (2, positions, HIDDEN))
    assert [p.shape for p in params] == [
        (HIDDEN, heads * d), (HIDDEN, kv * d), (HIDDEN, kv * d),
        (heads * d, HIDDEN), (d,), (d,)]
    assert params[4] is not params[5]       # a step donates each buffer
    kq, kk = jax.random.split(jax.random.PRNGKey(33))
    params[4] = 1.0 + 0.2 * jax.random.normal(kq, (d,))
    params[5] = 1.0 + 0.2 * jax.random.normal(kk, (d,))
    x = jax.random.normal(jax.random.PRNGKey(34), (2, positions, HIDDEN))
    m = {"heads": heads, "kv_heads": kv, "theta": 1e6, "eps": 1e-5}
    system = lambda p, x: impl.apply(lp, p, [x], True, None)[0]
    reference = lambda p, x: jnp.stack([ref2.attention(xi, p, m)
                                        for xi in x])
    close(system(params, x), highest(reference)(params, x), 5e-5)
    cot = jax.random.normal(jax.random.PRNGKey(35), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   (0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                            (0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 2e-4)


@pytest.mark.parametrize("positions,head_dim,heads,want", [
    (8192, 128, 48, "splash"), (8192, 64, 32, "splash"),
    (1024, 64, 32, "splash"), (96, 64, 32, "xla"), (8192, 96, 32, None),
    (8000, 64, 32, None), (1280, 64, 32, "splash"), (8192, 128, 64, "splash")])
def test_attention_lowering_on_a_chip(monkeypatch, positions, head_dim,
                                      heads, want):
    """On a TPU heads of 64 take the flash kernels like heads of 128 where
    ``flash_blocks`` can tile the positions (whole lane rows of 128), small
    sequences take the masked scores, and a sequence whose scores would
    not fit is refused: the masked scores are never taken silently."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    window = 512 if heads == 64 else 0
    if want is None:
        assert seq.flash_blocks(positions, window, head_dim) is None
        with pytest.raises(ValueError, match="do not fit the chip"):
            seq.attn_lowering(positions, head_dim, heads, window)
    else:
        assert seq.attn_lowering(positions, head_dim, heads, window) == want
        assert (seq.flash_blocks(positions, window, head_dim) is not None) \
            == (want == "splash")


# the three shapes the token cells run, the test nets' 1,024 positions at
# heads of 64, shapes the rule was not fitted on, and the edges of its
# bound on the partial dq: (positions, window, head_dim) -> fused?
_FLASH_SHAPES = [
    ((8192, 0, 128), True), ((8192, 512, 128), False),
    ((8192, 0, 64), True), ((1024, 0, 64), True), ((1024, 512, 64), False),
    ((4096, 1024, 128), False), ((4096, 0, 64), True),
    ((16384, 0, 128), False), ((16384, 2048, 128), False),
    ((384, 0, 64), True), ((640, 0, 128), False), ((1280, 256, 64), False),
    ((2048, 0, 128), True), ((512, 256, 64), False)]


@pytest.mark.parametrize("shape,fused", _FLASH_SHAPES,
                         ids=["x".join(map(str, s)) for s, _ in _FLASH_SHAPES])
def test_flash_blocks_follow_the_mask_and_the_positions(shape, fused):
    """Every block is a multiple of 128 that divides the positions, each
    compute block divides its memory block, the fused backward kernel is
    taken exactly where the partial dq it writes stays within the bound
    on its copies and never under a window, and a fused layer names no dq
    blocks; JAX's ``BlockSizes`` takes what the rule gives."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    positions, window, head_dim = shape
    blocks = seq.flash_blocks(*shape)
    given = blocks.fwd + blocks.dkv + (blocks.dq or ())
    assert all(b % 128 == 0 and positions % b == 0 for b in given)
    assert blocks.fwd[1] % blocks.fwd[2] == 0
    assert blocks.dkv[1] % blocks.dkv[2] == 0
    copies = positions // blocks.dkv[1]
    assert blocks.fused == fused == (blocks.dq is None)
    if window:
        assert not blocks.fused
    if blocks.fused:
        assert copies <= seq._FUSED_DQ_COPIES
    elif not window:
        # no key/value block the fused kernel can take brings the copies
        # under the bound
        assert all(positions // kv > seq._FUSED_DQ_COPIES
                   for kv in (128, 256, 512, 1024, 2048)
                   if kv <= seq._FUSED_KV_MOST and positions % kv == 0)
    sizes = seq._block_sizes(blocks)
    assert isinstance(sizes, sk.BlockSizes) and sizes.has_backward_blocks
    assert sizes.use_fused_bwd_kernel == fused
    assert (sizes.block_q_dq, sizes.block_kv_dq) == (blocks.dq or (None,
                                                                   None))
    labels = blocks.labels(window)
    assert labels["mask"] == ("window" if window else "causal")
    assert labels["backward"] == ("fused" if fused else "split")
    assert ("dq" in labels["blocks"]) == (not fused)


def test_flash_blocks_of_the_cells():
    """What the sweep on the chip found (PERF.md section 6, PR 38): a
    causal mask over 8,192 wants blocks of 1,024 round compute blocks of
    512 and the fused backward kernel over key/value blocks of 2,048 (4
    partial dq); a window of 512 its blocks of 512, split; a head of 64
    the same as one of 128."""
    causal = seq.FlashBlocks((1024, 1024, 512), (1024, 2048, 512), None)
    assert seq.flash_blocks(8192, 0, 128) == causal
    assert seq.flash_blocks(8192, 0, 64) == causal
    assert seq.flash_blocks(8192, 512, 128) == seq.FlashBlocks(
        (512, 512, 512), (512, 512, 512), (512, 512))


def test_a_window_wastes_least_at_its_own_size_once_steps_are_priced():
    """The count the rule prices: a query block of 512 under a window of
    512 sees two key blocks, one of 256 three, one of 128 five; the
    causal mask over 8,192 leaves 136 blocks of 512 and 36 of 1,024."""
    rows = lambda b: seq._blocks_seen(8192, 512, b) / (8192 // b)
    assert [round(rows(b), 2) for b in (128, 256, 512, 1024)] == [
        4.84, 2.91, 1.94, 1.88]
    assert seq._blocks_seen(8192, 0, 512) == 136
    assert seq._blocks_seen(8192, 0, 1024) == 36


def test_the_kernel_cache_tells_heads_and_masks_apart(monkeypatch):
    """One kernel object a (positions, group, window, head_dim): a head of
    64 and one of 128 at the same three do not share one."""
    made = []
    monkeypatch.setattr(seq, "_block_sizes",
                        lambda blocks: made.append(blocks) or None)
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)
    monkeypatch.setattr(sk, "make_splash_mqa_single_device",
                        lambda mask, block_sizes, interpret: object())
    seq._splash_kernel.cache_clear()
    try:
        a = seq._splash_kernel(1024, 2, 0, 64, False)
        assert seq._splash_kernel(1024, 2, 0, 64, False) is a
        assert seq._splash_kernel(1024, 2, 0, 128, False) is not a
        assert seq._splash_kernel(1024, 2, 512, 64, False) is not a
        assert seq._splash_kernel(1024, 2, 0, 64, True) is not a
        assert len(made) == 4
    finally:
        seq._splash_kernel.cache_clear()


def test_the_lowering_counter_says_what_engaged(monkeypatch):
    """``attn_lowering_total``'s sample of a splash trace carries the mask
    kind, the backward form and the blocks beside the path; an ``xla``
    trace carries the path alone."""
    from sparknet_tpu.utils import telemetry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seq.attn_lowering(8192, 128, 48, 0)
    seq.attn_lowering(8192, 128, 64, 512)
    seq.attn_lowering(96, 64, 32, 0)
    samples = [s["labels"] for s in telemetry.get_registry().snapshot()[
        "attn_lowering_total"]["samples"]]
    assert {"path": "splash", "mask": "causal", "backward": "fused",
            "blocks": "fwd 1024x1024x512 dkv 1024x2048x512"} in samples
    assert {"path": "splash", "mask": "window", "backward": "split",
            "blocks": "fwd 512x512x512 dkv 512x512x512 dq 512x512"
            } in samples
    assert {"path": "xla"} in samples


# (positions, window, head_dim, kv heads, group): both head sizes, both
# masks, both backward forms, memory blocks of one and of two compute
# blocks, several blocks a side, several partial dq
_FLASH_NUMERICS = [(2048, 0, 64, 1, 1), (2048, 1536, 128, 1, 1),
                   (1024, 0, 64, 1, 2), (1024, 512, 128, 1, 2),
                   (512, 0, 128, 2, 2), (512, 256, 64, 2, 1),
                   (384, 0, 64, 1, 2), (640, 128, 128, 1, 1)]


@pytest.mark.parametrize(
    "positions,window,head_dim,kv,group", _FLASH_NUMERICS,
    ids=["x".join(map(str, c)) for c in _FLASH_NUMERICS])
def test_flash_kernels_at_the_rules_blocks_against_the_masked_scores(
        monkeypatch, positions, window, head_dim, kv, group):
    """``attn_core(path="splash")`` with the ``BlockSizes`` the rule gives,
    in Pallas' interpreter, against the masked scores: the output and the
    three gradients, bfloat16 operands as the cells feed them.  The fused
    kernel rounds each partial dq to bfloat16 before their sum; that
    stays within what bfloat16 storage of dq already is."""
    monkeypatch.setattr(seq, "_INTERPRET", True)
    blocks = seq.flash_blocks(positions, window, head_dim)
    assert blocks.fused == (not window)
    r = jax.random.split(jax.random.PRNGKey(positions + head_dim), 4)
    q = (jax.random.normal(r[0], (kv, group, positions, head_dim))
         * head_dim ** -0.5).astype(jnp.bfloat16)
    k = jax.random.normal(r[1], (kv, positions, head_dim)).astype(
        jnp.bfloat16)
    v = jax.random.normal(r[2], (kv, positions, head_dim)).astype(
        jnp.bfloat16)
    cot = jax.random.normal(r[3], q.shape).astype(jnp.bfloat16)

    def both(path):
        return jax.jit(lambda q, k, v: jax.vjp(
            lambda *a: seq.attn_core(*a, window, path), q, k, v)[1](cot) + (
                seq.attn_core(q, k, v, window, path),))(q, k, v)

    for name, got, want in zip(("dq", "dk", "dv", "out"), both("splash"),
                               both("xla")):
        got, want = (np.asarray(t, np.float32) for t in (got, want))
        assert got.shape == want.shape
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 8e-3, (name, err)


def test_attention_lowering_off_the_chip_is_the_masked_scores():
    assert seq.attn_lowering(8192, 64, 32) == "xla"


# the token cells' expert layers: rows (the row bound), hidden, expert width
_GMM_CELLS = {"deepseek": (30720, 2048, 1408), "lfm2": (20480, 2048, 1536),
              "laguna": (40960, 2048, 512)}
# the tiles the rule gives: (cell, product, kernel).  A sweep of every
# kernel at every (tk, tn) on a v5e (PERF.md, Findings) found each of them
# fastest but LFM2's two tgmm tilings, its second there (1.1 and 1.7% behind
# 2048x512 and 512x2048, which read more and take fewer steps)
_GMM_CHOSEN = {
    **{(c, p, "fwd"): t for c, t in (("deepseek", (512, 1024, 1408)),
                                     ("lfm2", (512, 2048, 768)),
                                     ("laguna", (512, 2048, 512)))
       for p in ("gate", "up")},
    **{(c, p, "dlhs"): t for c, t in (("deepseek", (512, 1408, 1024)),
                                      ("lfm2", (512, 1536, 1024)),
                                      ("laguna", (512, 512, 2048)))
       for p in ("gate", "up")},
    **{(c, p, "tgmm"): t for c, t in (("deepseek", (512, 512, 1408)),
                                      ("lfm2", (512, 1024, 768)),
                                      ("laguna", (512, 2048, 512)))
       for p in ("gate", "up")},
    ("deepseek", "down", "fwd"): (512, 1408, 1024),
    ("deepseek", "down", "dlhs"): (512, 1024, 1408),
    ("deepseek", "down", "tgmm"): (512, 1408, 512),
    ("lfm2", "down", "fwd"): (512, 1536, 1024),
    ("lfm2", "down", "dlhs"): (512, 2048, 768),
    ("lfm2", "down", "tgmm"): (512, 768, 1024),
    ("laguna", "down", "fwd"): (512, 512, 2048),
    ("laguna", "down", "dlhs"): (512, 2048, 512),
    ("laguna", "down", "tgmm"): (512, 512, 2048),
}


@pytest.mark.parametrize(
    "cell,product,kernel", sorted(_GMM_CHOSEN),
    ids=["-".join(c) for c in sorted(_GMM_CHOSEN)])
def test_grouped_product_tiles_divide_the_width(cell, product, kernel):
    """Each kernel of each product of a token cell's expert layer takes
    tiles that divide its own contraction and output in whole lane rows,
    that fit its VMEM by ``_gmm_vmem`` at the cells' bfloat16, and that the
    sweep measured."""
    tm, tk, tn = _own_tiles(cell, product, kernel, 2)
    assert (tm, tk, tn) == _GMM_CHOSEN[cell, product, kernel]


def _own_tiles(cell, product, kernel, itemsize):
    """The tiles ``gmm_tiles`` gives one kernel of a cell's expert layer,
    checked to divide that kernel's own problem and to fit its VMEM."""
    m, hidden, width = _GMM_CELLS[cell]
    k, n = (width, hidden) if product == "down" else (hidden, width)
    tiles = dict(zip(("fwd", "dlhs", "tgmm"),
                     seq.gmm_tiles(m, k, n, itemsize)))
    tm, tk, tn = tiles[kernel]
    own_k, own_n = (n, k) if kernel == "dlhs" else (k, n)
    assert m % tm == 0 and tk % 128 == 0 and tn % 128 == 0
    assert own_k % tk == 0 and own_n % tn == 0
    assert (seq._gmm_vmem("tgmm" if kernel == "tgmm" else "gmm", tm, tk, tn,
                          itemsize) <= seq._GMM_VMEM_MOST)
    return tm, tk, tn


# float32 operands (a ``Net`` or ``Solver`` with no ``compute_dtype``)
# double every block: the rule narrows the tiles to fit the same budget
_GMM_CHOSEN_F32 = {
    ("deepseek", "gate_up"): ((512, 256, 1408), (512, 128, 2048),
                              (512, 256, 1408)),
    ("deepseek", "down"): ((512, 128, 2048), (512, 256, 1408),
                           (512, 1408, 256)),
    ("lfm2", "gate_up"): ((512, 256, 1536), (512, 128, 2048),
                          (512, 1024, 512)),
    ("lfm2", "down"): ((512, 128, 2048), (512, 256, 1536), (512, 512, 1024)),
    ("laguna", "gate_up"): ((512, 1024, 512), (512, 512, 1024),
                            (512, 1024, 512)),
    ("laguna", "down"): ((512, 512, 1024), (512, 1024, 512),
                         (512, 512, 1024)),
}


@pytest.mark.parametrize(
    "cell,product,kernel",
    [(c, p, kernel) for c, p in _GMM_CHOSEN_F32
     for kernel in ("fwd", "dlhs", "tgmm")],
    ids=lambda v: v)
def test_float32_grouped_tiles_fit_the_same_budget(cell, product, kernel):
    """At float32 each kernel of a token cell's expert layer takes tiles
    that divide its own problem and fit ``_GMM_VMEM_MOST`` with its blocks
    at four bytes; the bfloat16 tiles would not fit there."""
    tiles = _own_tiles(cell, product, kernel, 4)
    i = ("fwd", "dlhs", "tgmm").index(kernel)
    assert tiles == _GMM_CHOSEN_F32[cell, product][i]
    bf16 = _GMM_CHOSEN[cell, "gate" if product == "gate_up" else "down",
                       kernel]
    if bf16 != tiles:
        assert (seq._gmm_vmem("tgmm" if kernel == "tgmm" else "gmm", *bf16, 4)
                > seq._GMM_VMEM_MOST)


# (dtype, a VMEM budget at which its three kernels tile three ways, how
# far the kernels may be from ragged_dot: both round to the dtype once)
_GMM_INTERPRETED = [("bfloat16", 1_600_000, 2e-3),
                    ("float32", 3_200_000, 1e-6)]


@pytest.mark.parametrize("dtype,most,tol", _GMM_INTERPRETED,
                         ids=[d for d, _, _ in _GMM_INTERPRETED])
def test_grouped_kernels_at_their_own_tiles_against_ragged_dot(
        monkeypatch, dtype, most, tol):
    """``_grouped(path="gmm")`` in Pallas' interpreter against
    ``lax.ragged_dot``: the product, the rows' and the weights' gradients,
    one group empty and two that start inside a tile.  Under the budget
    the three kernels tile their own problems three ways, so each tiling
    reaches its own kernel: a ``tk`` or ``tn`` given to the wrong one
    divides nothing there or misreads a block."""
    monkeypatch.setattr(seq, "_INTERPRET", True)
    monkeypatch.setattr(seq, "_GMM_VMEM_MOST", most)
    m, k, n = 1024, 256, 384
    dtype = jnp.dtype(dtype)
    assert seq.gmm_tiles(m, k, n, dtype.itemsize) == (
        (512, 256, 128), (512, 128, 256), (512, 128, 384))
    r = jax.random.split(jax.random.PRNGKey(0), 3)
    rows = jax.random.normal(r[0], (m, k)).astype(dtype)
    w = (jax.random.normal(r[1], (4, k, n)) * 0.1).astype(dtype)
    dy = jax.random.normal(r[2], (m, n)).astype(dtype)
    sizes = jnp.array([300, 0, 500, 224], jnp.int32)

    def both(product):
        y, vjp = jax.vjp(lambda a, b: product(a, b, sizes), rows, w)
        return (y, *vjp(dy))

    got = jax.jit(lambda: both(
        lambda a, b, s: seq._grouped(a, b, s, "gmm")))()
    want = jax.jit(lambda: both(jax.lax.ragged_dot))()
    for name, g, h in zip(("out", "d_rows", "d_w"), got, want):
        g, h = np.asarray(g, np.float32), np.asarray(h, np.float32)
        assert g.shape == h.shape
        assert np.linalg.norm(g - h) / np.linalg.norm(h) < tol, name


def test_the_expert_lowering_counter_carries_the_tiles(monkeypatch):
    """On a TPU ``moe_lowering_total``'s sample says which tiles each
    kernel of the two products took, as ``gmm_tiles`` gives them."""
    from sparknet_tpu.utils import telemetry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert seq.moe_lowering(30720, 2048, 1408, 2) == "gmm"
    x = lambda t: "x".join(map(str, t))
    want = {"path": "gmm"}
    for product, (k, n) in (("gate_up", (2048, 1408)),
                            ("down", (1408, 2048))):
        fwd, dlhs, tgmm = seq.gmm_tiles(30720, k, n, 2)
        want[product] = f"fwd {x(fwd)} dlhs {x(dlhs)} tgmm {x(tgmm)}"
    samples = [s["labels"] for s in telemetry.get_registry().snapshot()[
        "moe_lowering_total"]["samples"]]
    assert want in samples


BIAS_GEOM = {"experts": 8, "top_k": 2, "lo": 0, "hi": 8, "scaling": 1.0,
             "eps": 1e-6}


def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    """Every token scores expert e at sigmoid(e / 4): without a bias it
    takes experts 7 and 6; a bias that lifts expert 0 over them makes it
    take 0 and 7, and the weights are the scores', not score plus bias."""
    x = jnp.ones((4, HIDDEN))
    wr = jnp.tile(jnp.arange(8.0)[None, :] / (4 * HIDDEN), (HIDDEN, 1))
    scores = np.asarray(jax.nn.sigmoid(jnp.arange(8.0) / 4))
    bias = jnp.zeros(8).at[0].set(0.5)
    assert scores[0] + 0.5 > scores[7] and scores[0] < scores[6]
    _, w_plain, _, sent_plain, _ = seq.moe_route(x, wr, BIAS_GEOM)
    assert np.asarray(sent_plain).tolist() == [0] * 6 + [4, 4]
    token, w, _, sent, dropped = seq.moe_route(x, wr, BIAS_GEOM, bias)
    assert np.asarray(sent).tolist() == [4] + [0] * 6 + [4]
    assert int(dropped) == 0
    total = scores[0] + scores[7] + 1e-6
    np.testing.assert_allclose(np.asarray(w)[:4], scores[0] / total,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w)[4:], scores[7] / total,
                               rtol=1e-6)
    # the reference makes the same choice and gives the same weights
    weight, chosen = ref2.route(x, wr, bias, {"top_k": 2, "scaling": 1.0})
    assert np.asarray(chosen).tolist() == [[0, 7]] * 4
    np.testing.assert_allclose(np.asarray(weight)[0],
                               [scores[0] / total, scores[7] / total],
                               rtol=1e-6)


def bias_moe_lp(lo, hi, experts=16, top_k=4):
    return layer("moe", "MixtureOfExperts", ["x"], ["y"], moe_param={
        "num_experts": experts, "top_k": top_k, "experts_held_lo": lo,
        "experts_held_hi": hi, "expert_width": 16, "shared_width": 0,
        "routed_scaling": 1.0, "norm_eps": 1e-6, "select_bias": True,
        "select_bias_filler": {"type": "gaussian", "std": 0.1},
        "weight_filler": _GAUSS, "router_filler": _GAUSS,
        "detach_router": True})


@pytest.mark.parametrize("lo,hi", [(0, 4), (4, 8), (0, 16)])
def test_biased_expert_layer_without_shared_expert(lo, hi):
    """No shared expert builds no blob for one; the bias is the last blob,
    changes which experts a token takes (so it is not all zero here) and
    gets no gradient."""
    lp = bias_moe_lp(lo, hi)
    impl, params = init_and_apply(lp, (2, 24, HIDDEN))
    assert [p.shape for p in params] == [
        (HIDDEN, 16), (hi - lo, HIDDEN, 16), (hi - lo, HIDDEN, 16),
        (hi - lo, 16, HIDDEN), (16,)]
    x = jax.random.normal(jax.random.PRNGKey(36), (2, 24, HIDDEN))
    m = {"top_k": 4, "held": (lo, hi), "scaling": 1.0,
         "train_router": False}
    _, with_bias = ref2.route(x[0], params[0], params[4], m)
    _, without = ref2.route(x[0], params[0], jnp.zeros(16), m)
    assert not np.array_equal(np.asarray(with_bias), np.asarray(without))
    system = lambda p, x: impl.apply(lp, p, [x], True, None)[0]
    reference = lambda p, x: jnp.stack([ref2.moe(xi, p, m) for xi in x])
    close(system(params, x), highest(reference)(params, x))
    cot = jax.random.normal(jax.random.PRNGKey(37), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   (0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                            (0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 1e-4)
    assert float(jnp.abs(got[0][4]).max()) == 0.0


def test_the_eight_biased_shares_add_up_to_the_uncut_layer():
    """The share test for a layer that chooses by a bias and has no shared
    expert: the parts the 8 shares of 2 experts give add up to the uncut
    reference's layer output."""
    impl, params = init_and_apply(bias_moe_lp(0, 16), (1, 40, HIDDEN))
    wr, eg, eu, ed, bias = params
    x = jax.random.normal(jax.random.PRNGKey(38), (1, 40, HIDDEN))
    m = {"top_k": 4, "held": (0, 16), "scaling": 1.0}
    uncut = highest(lambda p, x: ref2.moe(x, p, m))(params, x[0])
    total = np.zeros_like(np.asarray(uncut))
    for share in range(8):
        lo, hi = 2 * share, 2 * share + 2
        total += np.asarray(impl.apply(
            bias_moe_lp(lo, hi),
            [wr, eg[lo:hi], eu[lo:hi], ed[lo:hi], bias], [x], True,
            None)[0][0])
    close(total, uncut, 1e-5)


# -- the route and the layer against the form they had ----------------------

def oracle_route(x, w_router, g, bias=None):
    """``moe_route`` as it stood before PR 40: an ``argsort``, a
    ``bincount``, ``top_k``'s own values (``take_along_axis`` under a
    bias) and scalar gathers by ``order``."""
    tokens, held, k = x.shape[0], g["hi"] - g["lo"], g["top_k"]
    if g.get("detached"):
        x = jax.lax.stop_gradient(x)
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if bias is None:
        top_s, top_i = jax.lax.top_k(jax.nn.sigmoid(logits), k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, top_i = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
        top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    total = jnp.sum(top_s, axis=-1, keepdims=True)
    if g.get("eps"):
        total = total + g["eps"]
    weight = top_s / total * g["scaling"]
    here = (top_i >= g["lo"]) & (top_i < g["hi"])
    key = jnp.where(here, top_i - g["lo"], held).reshape(-1)
    rows = seq.moe_row_bound(tokens, g)
    order = jnp.argsort(key, stable=True)[:rows]
    sent = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(sent), rows)
    sized = jnp.diff(ends, prepend=0)
    sized = sized.at[-1].add(rows - ends[-1])
    w = jnp.where(key[order] < held, weight.reshape(-1)[order], 0.0)
    return order // k, w, sized, sent, jnp.sum(sent) - ends[-1]


def oracle_layer(g, params, x):
    """The whole layer in that form: ``x[token]``, the grouped products,
    ``zeros.at[token].add``, the shared expert added last."""
    wr, eg, eu, ed, *rest = params
    flat = x.reshape(-1, x.shape[-1])
    token, w, sized, _, _ = oracle_route(
        flat, wr, g, rest[-1] if g["select_bias"] else None)
    rows = flat[token]
    y = jax.lax.ragged_dot(
        seq._swiglu(jax.lax.ragged_dot(rows, eg, sized),
                    jax.lax.ragged_dot(rows, eu, sized)), ed, sized)
    routed = jnp.zeros(flat.shape, jnp.float32).at[token].add(
        y.astype(jnp.float32) * w[:, None])
    if g["shared"]:
        sg, su, sd = rest[:3]
        routed = routed + (seq._swiglu(flat @ sg, flat @ su) @ sd)
    return routed.astype(x.dtype).reshape(x.shape)


def _route_case(name):
    """(moe_param, tokens, what to do to the filled router) of a case."""
    laguna = {"num_experts": 256, "top_k": 8, "experts_held_lo": 0,
              "experts_held_hi": 32, "expert_width": 16, "shared_width": 24,
              "routed_scaling": 2.5}
    lfm2 = {"num_experts": 64, "top_k": 4, "experts_held_lo": 8,
            "experts_held_hi": 16, "expert_width": 16, "shared_width": 0,
            "routed_scaling": 1.0, "norm_eps": 1e-6, "select_bias": True,
            "select_bias_filler": {"type": "gaussian", "std": 0.1}}
    small = {"num_experts": 16, "top_k": 4, "experts_held_lo": 0,
             "experts_held_hi": 4, "expert_width": 16, "shared_width": 24,
             "routed_scaling": 1.0}
    ones = 0.02 * jnp.ones((HIDDEN, 1))     # logits of about a half
    return {
        "laguna": (laguna, 96, lambda wr: wr),
        "lfm2": (lfm2, 96, lambda wr: wr),
        # every column the same: every score ties, the lower index wins
        "ties": (laguna, 48, lambda wr: jnp.tile(wr[:, :1], (1, 256))),
        # every token takes all four held experts: 2,048 picks, 1,024 rows
        "bound": (small, 512, lambda wr: jnp.concatenate(
            [jnp.tile(ones, (1, 4)), -jnp.tile(ones, (1, 12))], 1)),
        # of the held experts only number 2 is ever taken
        "collapsed": (small, 64, lambda wr: jnp.concatenate(
            [-ones, -ones, ones, -ones, jnp.tile(ones, (1, 3)),
             -jnp.tile(ones, (1, 9))], 1)),
    }[name]


@pytest.mark.parametrize("detach", [False, True],
                         ids=["attached", "detached"])
@pytest.mark.parametrize("case", ["laguna", "lfm2", "ties", "bound",
                                  "collapsed"])
def test_route_and_layer_are_what_they_were(case, detach):
    """``moe_route`` and the whole layer against the oracle above: rows,
    counts and what the bound leaves out exactly, weights, output and every
    gradient to float32 rounding; the bias gets none."""
    param, tokens, edit = _route_case(case)
    lp = layer("moe", "MixtureOfExperts", ["x"], ["y"], moe_param={
        **param, "weight_filler": _GAUSS, "router_filler": _GAUSS,
        "detach_router": detach})
    g = seq.moe_geometry(lp)
    impl, params = init_and_apply(lp, (2, tokens // 2, HIDDEN), key=3)
    params[0] = edit(params[0])
    bias = params[-1] if g["select_bias"] else None
    x = jax.random.normal(jax.random.PRNGKey(50), (2, tokens // 2, HIDDEN))
    if case in ("bound", "collapsed"):
        x = jnp.abs(x)                # the sign of x . 1 is the column's
    flat = x.reshape(-1, HIDDEN)

    got = seq.moe_route(flat, params[0], g, bias)
    want = oracle_route(flat, params[0], g, bias)
    for name, a, b in zip(("token", "w", "sized", "sent", "dropped"), got,
                          want):
        if name == "w":
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=0)
        else:
            assert np.array_equal(np.asarray(a), np.asarray(b)), name
    dropped, sent = int(got[4]), np.asarray(got[3])
    assert (dropped > 0) == (case == "bound")
    if case == "collapsed":
        assert sent.tolist() == [0, 0, tokens, 0]
    if case == "ties":
        assert sent.tolist() == [tokens] * 8 + [0] * 24

    system = lambda p, x: impl.apply(lp, p, [x], True, None)[0]
    oracle = lambda p, x: oracle_layer(g, p, x)
    close(system(params, x), oracle(params, x), 1e-6)
    cot = jax.random.normal(jax.random.PRNGKey(51), x.shape)
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   (0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(oracle(p, x) * cot),
                    (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(a, b, 1e-5)
    assert float(jnp.abs(got[0][0]).max()) > 0      # the router's gradient
    if bias is not None:
        assert float(jnp.abs(got[0][-1]).max()) == 0.0


def test_transposed_head_is_the_head_on_the_transpose():
    """``transposed`` stores the head ``[vocab, hidden]``, an ``Embed``
    table's shape: loss, logits and gradient are the plain head's on the
    transposed matrix."""
    vocab = 40
    param = {"vocab": vocab, "weight_filler": _GAUSS}
    plain = layer("head", "LMHeadLoss", ["x", "tokens"], ["loss", "logits"],
                  lm_head_param=param)
    tied = layer("head", "LMHeadLoss", ["x", "tokens"], ["loss", "logits"],
                 lm_head_param={**param, "transposed": True})
    impl = get_layer_impl("LMHeadLoss")
    (w,) = impl.init(jax.random.PRNGKey(39), tied, [(2, 9, HIDDEN), (2, 9)])
    assert w.shape == (vocab, HIDDEN)
    x = jax.random.normal(jax.random.PRNGKey(40), (2, 9, HIDDEN))
    tokens = jax.random.randint(jax.random.PRNGKey(41), (2, 9), 0, vocab)
    got = impl.apply(tied, [w], [x, tokens], True, None)
    want = impl.apply(plain, [w.T], [x, tokens], True, None)
    close(got[0], want[0], 1e-6)
    close(got[1], want[1], 1e-6)
    g_tied = jax.grad(lambda w: impl.apply(tied, [w], [x, tokens], True,
                                           None)[0])(w)
    g_plain = jax.grad(lambda w: impl.apply(plain, [w], [x, tokens], True,
                                            None)[0])(w.T)
    close(g_tied, g_plain.T, 1e-5)


# -- multi-head latent attention (DeepSeek-V2) ---------------------------------

def latent_lp(heads, rank, nope, rope, v, scale=None):
    p = {"num_heads": heads, "kv_lora_rank": rank, "qk_nope_head_dim": nope,
         "qk_rope_head_dim": rope, "v_head_dim": v, "rope_theta": 10000.0,
         "yarn_factor": 40.0, "yarn_original_length": 16,
         "kv_norm_eps": 1e-6, "weight_filler": _GAUSS}
    if scale is not None:
        p["softmax_scale"] = scale
    return layer("mla", "LatentAttention", ["x"], ["y"],
                 latent_attention_param=p)


# (heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
# positions, softmax_scale): value heads narrower, as wide and wider than
# the query/key heads, one and several heads, a given scale and the default
LATENT_CASES = [(2, 16, 8, 8, 8, 12, 0.3), (4, 16, 16, 8, 8, 20, None),
                (2, 32, 8, 16, 16, 7, 0.2), (3, 8, 8, 4, 12, 16, None),
                (1, 16, 16, 16, 32, 9, 0.5)]


@pytest.mark.parametrize("heads,rank,nope,rope,v,positions,scale",
                         LATENT_CASES)
def test_latent_attention_against_reference(heads, rank, nope, rope, v,
                                            positions, scale):
    """The layer on the XLA path (masked scores) against the reference's
    published equations, forward and the gradients of every blob and of
    the input."""
    from benchmark.lib import reference_deepseek_v2 as mla_ref
    lp = latent_lp(heads, rank, nope, rope, v, scale)
    impl, params = init_and_apply(lp, (2, positions, HIDDEN))
    params[3] = params[3] * jnp.linspace(0.5, 1.5, rank)    # γ_kv, not 1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, positions, HIDDEN))
    m = {"heads": heads, "nope": nope, "rope_dim": rope, "v": v,
         "eps": 1e-6,
         "rope": {"rope_type": "yarn", "partial_rotary_factor": 1,
                  "rope_theta": 10000.0, "factor": 40.0, "beta_fast": 32.0,
                  "beta_slow": 1.0, "original_max_position_embeddings": 16},
         "tau": (nope + rope) ** -0.5 if scale is None else scale}

    def system(p, x):
        return impl.apply(lp, p, [x], True, None)[0]

    def reference(p, x):
        return jnp.stack([mla_ref.mla(xi, p, m) for xi in x])

    close(system(params, x), highest(reference)(params, x))
    cot = jax.random.normal(jax.random.PRNGKey(2), (2, positions, HIDDEN))
    got = jax.grad(lambda p, x: jnp.sum(system(p, x) * cot),
                   argnums=(0, 1))(params, x)
    want = highest(jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                            argnums=(0, 1)))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        close(g, w, 1e-4)


@pytest.mark.parametrize("head_dim,offset,rotary", [(24, 16, 8), (192, 128, 64),
                                                    (16, 0, 16), (20, 4, 8)])
def test_rope_turns_the_dimensions_from_its_offset(head_dim, offset, rotary):
    """``offset`` leaves the head's first dimensions as they are and turns
    the next ``rotary`` as the sliced formula does; the scale rides along
    over the whole head, and the gradient is the turn back."""
    inv_freq = seq.rope_inv_freq(rotary, 10000.0, 40.0, 16, 32.0, 1.0)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(23), (40, 3, head_dim))

    def want_fn(x):
        lead, rest = x[..., :offset], x[..., offset:]
        return jnp.concatenate([lead * 0.5, rope_before(rest, inv_freq, 1.0,
                                                        0.5)], -1)

    got = seq.apply_rope(x.transpose(1, 0, 2), inv_freq, 1.0, 0.5, offset)
    close(got, want_fn(x).transpose(1, 0, 2), 1e-6)
    cot = jax.random.normal(jax.random.PRNGKey(24), (3, 40, head_dim))
    g = jax.grad(lambda x: jnp.sum(seq.apply_rope(
        x.transpose(1, 0, 2), inv_freq, 1.0, 0.5, offset) * cot))(x)
    w = jax.grad(lambda x: jnp.sum(want_fn(x).transpose(1, 0, 2) * cot))(x)
    close(g, w, 1e-6)


# (positions, window, q/k head, v head) -> (fused, the fused kernel's query
# block): the latent layer's heads of 192/128 halve the query block of the
# fused kernel, the cells' heads keep theirs
_UNEQUAL_HEADS = [((8192, 0, 192, 128), (True, 512)),
                  ((8192, 0, 128, 128), (True, 1024)),
                  ((8192, 0, 64, 64), (True, 1024)),
                  ((8192, 512, 192, 128), (False, 512)),
                  ((2048, 0, 192, 128), (True, 1024)),
                  ((16384, 0, 192, 128), (False, 1024))]


@pytest.mark.parametrize("shape,want", _UNEQUAL_HEADS,
                         ids=["x".join(map(str, s)) for s, _ in _UNEQUAL_HEADS])
def test_flash_blocks_at_unequal_heads(shape, want):
    """The fused kernel's query block is halved until one step's rows fit
    its VMEM (``_fused_rows`` under ``_FUSED_ROWS_MOST``), its compute
    block with it; the other blocks are what the heads of 128 take."""
    blocks = seq.flash_blocks(*shape)
    positions, window, d, dv = shape
    assert (blocks.fused, blocks.dkv[0]) == want
    assert blocks.fwd == seq.flash_blocks(positions, window, 128).fwd
    if blocks.fused:
        assert seq._fused_rows(*blocks.dkv[:2], d, dv) <= seq._FUSED_ROWS_MOST
        assert blocks.dkv[2] == min(blocks.dkv[0], seq._FLASH_COMPUTE)
    assert seq.flash_blocks(positions, window, d, dv) == seq.flash_blocks(
        positions, window, d, dv if dv != d else None)


def test_fused_rows_are_counted_at_lane_padded_widths():
    """The three readings the bound sits between (a v5e's compiler, 16
    heads): the cells' blocks at 128, the refused 1,024 x 2,048 at 192/128
    and the 512 x 2,048 that compiles; a head of 64 fills a lane row."""
    assert seq._fused_rows(1024, 2048, 128, 128) == 1_441_792
    assert seq._fused_rows(1024, 2048, 192, 128) == 2_228_224
    assert seq._fused_rows(512, 2048, 192, 128) == 1_900_544
    assert seq._fused_rows(1024, 2048, 64, 64) == 1_441_792
    assert (seq._fused_rows(512, 2048, 192, 128) < seq._FUSED_ROWS_MOST
            < seq._fused_rows(1024, 2048, 192, 128))


def test_the_lowering_counter_names_unequal_heads(monkeypatch):
    """A sample of the latent layer's core carries both head sizes; the
    grouped-query layers' samples are as they were."""
    from sparknet_tpu.utils import telemetry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert seq.attn_lowering(8192, 192, 16, head_dim_v=128) == "splash"
    seq.attn_lowering(8192, 128, 48, 0, head_dim_v=128)
    samples = [s["labels"] for s in telemetry.get_registry().snapshot()[
        "attn_lowering_total"]["samples"]]
    assert {"path": "splash", "mask": "causal", "backward": "fused",
            "blocks": "fwd 1024x1024x512 dkv 512x2048x512",
            "head_dim": "192", "v_head_dim": "128"} in samples
    assert {"path": "splash", "mask": "causal", "backward": "fused",
            "blocks": "fwd 1024x1024x512 dkv 1024x2048x512"} in samples


@pytest.mark.parametrize("positions,kv", [(512, 2), (1024, 1)])
def test_flash_kernels_at_unequal_heads_against_the_masked_scores(
        monkeypatch, positions, kv):
    """``attn_core(path="splash")`` with q/k heads of 192 and v heads of
    128, in Pallas' interpreter, against the masked scores: the output and
    the three gradients, bfloat16 operands."""
    monkeypatch.setattr(seq, "_INTERPRET", True)
    seq._splash_kernel.cache_clear()
    r = jax.random.split(jax.random.PRNGKey(positions), 4)
    q = (jax.random.normal(r[0], (kv, 1, positions, 192))
         * 192 ** -0.5).astype(jnp.bfloat16)
    k = jax.random.normal(r[1], (kv, positions, 192)).astype(jnp.bfloat16)
    v = jax.random.normal(r[2], (kv, positions, 128)).astype(jnp.bfloat16)
    cot = jax.random.normal(r[3], (kv, 1, positions, 128)).astype(
        jnp.bfloat16)

    def both(path):
        return jax.jit(lambda q, k, v: jax.vjp(
            lambda *a: seq.attn_core(*a, 0, path), q, k, v)[1](cot) + (
                seq.attn_core(q, k, v, 0, path),))(q, k, v)

    for name, got, want in zip(("dq", "dk", "dv", "out"), both("splash"),
                               both("xla")):
        got, want = (np.asarray(t, np.float32) for t in (got, want))
        assert got.shape == want.shape
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 8e-3, (name, err)
    seq._splash_kernel.cache_clear()


# (scoring, norm_topk): the four routers the two options make
_SCORINGS = [("softmax", False), ("softmax", True), ("sigmoid", False),
             ("sigmoid", True)]


@pytest.mark.parametrize("scoring,norm_topk", _SCORINGS)
def test_router_scoring_and_normalisation(scoring, norm_topk):
    """Scores are the softmax over every expert or the sigmoid, the top 6
    of 64 are taken either way (the same experts: both are increasing in
    the logit), and the weights are the chosen scores, over their sum
    where ``norm_topk``."""
    g = {"experts": 64, "top_k": 6, "lo": 8, "hi": 16, "scaling": 1.0,
         "eps": 0.0, "scoring": scoring, "norm_topk": norm_topk}
    x = jax.random.normal(jax.random.PRNGKey(30), (64, 32))
    wr = 0.3 * jax.random.normal(jax.random.PRNGKey(31), (32, 64))
    token, w, sized, sent, dropped = seq.moe_route(x, wr, g)
    logits = jnp.dot(x, wr, precision="highest")
    score = (jax.nn.softmax(logits, -1) if scoring == "softmax"
             else jax.nn.sigmoid(logits))
    chosen = jnp.argsort(-logits, axis=-1, stable=True)[:, :6]
    top = jnp.take_along_axis(score, chosen, -1)
    weight = top / jnp.sum(top, -1, keepdims=True) if norm_topk else top
    held = (chosen >= 8) & (chosen < 16)
    want = sorted(float(v) for v in np.asarray(weight)[np.asarray(held)])
    got = sorted(float(v) for v in np.asarray(w) if v != 0)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert int(np.sum(sent)) == int(np.sum(held)) and int(dropped) == 0



# -- the state-space mixer, experts without a gate, attention without rotary --

from benchmark.lib import reference_nemotron_h as ref_h  # noqa: E402
from sparknet_tpu.ops import ssm, ssm_kernels  # noqa: E402


def _scan_inputs(positions, heads=4, p=8, groups=2, n=16, key=0):
    """x [H, P, S], Δ [H, S], A [H], B and C [G, S, N], D [H] in float32:
    decays from 0.2 to 0.98 a position, so a state lasts chunks."""
    r = jax.random.split(jax.random.PRNGKey(key), 6)
    x = jax.random.normal(r[0], (heads, p, positions))
    dt = jax.nn.softplus(jax.random.normal(r[1], (heads, positions)) - 1.0)
    a = -jnp.exp(jax.random.normal(r[2], (heads,)) * 0.5 - 1.0)
    b = jax.random.normal(r[3], (groups, positions, n)) * 0.5
    c = jax.random.normal(r[4], (groups, positions, n)) * 0.5
    d = jax.random.normal(r[5], (heads,))
    return x, dt, a, b, c, d


def _scan_errors(inputs, chunk, path="chunked"):
    """Relative errors of the chunked scan (by ``path``: XLA's products, or
    the Pallas kernels in Pallas' interpreter) against the sequential
    recurrence: the value, then the gradient of each of the six inputs
    under one cotangent, then the value of the scan called alone (the
    kernels' forward pass without the states it keeps for the backward
    one)."""
    def recurrence(x, dt, a, b, c, d):
        return ref_h.recurrence(x.transpose(2, 0, 1), dt.T, a,
                                b.transpose(1, 0, 2), c.transpose(1, 0, 2),
                                d).transpose(1, 2, 0)

    cot = jax.random.normal(jax.random.PRNGKey(9), inputs[0].shape)
    out = []
    for fn in (lambda *t: ssm.ssd_scan(*t, chunk, path), recurrence):
        y, vjp = jax.vjp(fn, *inputs)
        out.append((y, *vjp(cot)))
    got, want = out
    got = (*got, ssm.ssd_scan(*inputs, chunk, path))
    want = (*want, want[0])
    rel = lambda g, w: float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
    return [rel(g, w) for g, w in zip(got, want)]


@pytest.fixture
def interpreted(monkeypatch):
    """The scan's Pallas kernels in Pallas' interpreter."""
    monkeypatch.setattr(ssm_kernels, "_INTERPRET", True)


@pytest.mark.parametrize("path", ["chunked", "pallas"])
@pytest.mark.parametrize("positions,chunk,heads,groups",
                         [(64, 16, 4, 2), (70, 16, 4, 2), (9, 16, 4, 2),
                          (256, 16, 8, 2)],
                         ids=["4-chunks", "padded-last", "one-short",
                              "two-blocks-of-8-chunks"])
def test_chunked_scan_is_the_sequential_recurrence(positions, chunk, heads,
                                                   groups, path, interpreted):
    """``ssd_scan`` against the recurrence position by position
    (``reference_nemotron_h.recurrence``): the value and the gradients of
    x, Δ, A, B, C and D, over several chunks and at lengths that are not a
    multiple of the chunk (the last chunk padded with Δ = 0), by XLA's
    products and by the Pallas kernels alike; the last case holds four
    heads a group and, in the kernels, two blocks of eight chunks
    (``ssm_kernels.scan_blocks``), so the state is carried within a block
    and across blocks."""
    if path == "pallas" and positions == 256:
        assert ssm_kernels.scan_blocks(positions, heads, 8, groups, 16,
                                       chunk, 4) == (8, 8)
    with jax.default_matmul_precision("highest"):
        errors = _scan_errors(
            _scan_inputs(positions, heads=heads, groups=groups), chunk, path)
    assert max(errors) < 2e-5, errors


@pytest.mark.parametrize("path", ["chunked", "pallas"])
def test_a_scan_that_passes_no_state_between_chunks_is_told_apart(
        monkeypatch, path, interpreted):
    """The same comparison refuses a chunked scan whose chunks each start
    from no state (XLA's products without ``_pass_states``, the kernels
    with the state they carry zeroed at every chunk): the value and every
    gradient but D's are off by far more than the limit above."""
    if path == "pallas":
        monkeypatch.setattr(ssm_kernels, "_carried",
                            lambda decay, state, fresh: jnp.zeros_like(fresh))
    else:
        monkeypatch.setattr(ssm, "_pass_states",
                            lambda states, ends, starts: jnp.zeros(
                                states.shape, jnp.float32))
    with jax.default_matmul_precision("highest"):
        errors = _scan_errors(_scan_inputs(70), 16, path)
    assert errors[0] > 0.05 and min(errors[1:6]) > 0.01, errors


def test_the_kernels_in_bfloat16_are_as_near_the_recurrence_as_xla(
        interpreted):
    """x, B and C in bfloat16, as the mixer hands them over: the kernels'
    value and six gradients are as near the float32 recurrence as XLA's
    chunked form's, within half as much again (both about 3e-3).  A's
    gradient sums the decays' cotangents over every position, and two sums
    that cancel must come from one rounded matrix: where the kernels took
    them from two different products its error read 0.29 here."""
    def recurrence(x, dt, a, b, c, d):
        return ref_h.recurrence(x.transpose(2, 0, 1), dt.T, a,
                                b.transpose(1, 0, 2), c.transpose(1, 0, 2),
                                d).transpose(1, 2, 0)

    inputs = _scan_inputs(256, heads=8, groups=2)
    low = [t.astype(jnp.bfloat16) if i in (0, 3, 4) else t
           for i, t in enumerate(inputs)]
    cot = jax.random.normal(jax.random.PRNGKey(9), inputs[0].shape)
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(recurrence, *inputs)
        want = (y, *vjp(cot))
    errors = {}
    for path in ("chunked", "pallas"):
        y, vjp = jax.vjp(lambda *t: ssm.ssd_scan(*t, 16, path), *low)
        errors[path] = np.array([
            float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                  / jnp.linalg.norm(w)) for g, w in zip((y, *vjp(cot)), want)])
    assert np.all(errors["pallas"] <= 1.5 * errors["chunked"]), errors
    assert errors["chunked"].max() < 0.01, errors


def _mixer(positions, chunk=128, state=128):
    """One ``StateSpaceMixer`` of 8 heads of 8 over 2 groups and the shapes
    of its parameters, for one sequence of ``positions``."""
    from sparknet_tpu import models
    from sparknet_tpu.ops import get_layer_impl
    net = models.nemotron_h(1, 1, seq_len=positions, layers_kept=[0],
                            vocab=64, hidden=32, mamba_heads=8,
                            mamba_head_dim=8, groups=2, state=state,
                            chunk=chunk, num_experts=8, experts_held=(0, 8),
                            top_k=2, expert_width=16, shared_width=16)
    lp = next(l for l in net.layer if l.name == "L0/mixer")
    impl = get_layer_impl(lp.type)
    shapes = jax.eval_shape(lambda r: impl.init(r, lp, [(1, positions, 32)]),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return lp, impl, shapes


def test_the_scans_lowering_is_counted_with_its_blocks(monkeypatch):
    """A trace of the mixer counts its scan in ``ssm_lowering_total``:
    ``chunked`` on the CPU; on a TPU (the selection steered to its branch)
    ``pallas`` with the kernels' ``blocks``, where the chunk and the state
    are whole lane rows, and ``chunked`` where they are not."""
    from sparknet_tpu.utils import telemetry

    def traced(positions, **geometry):
        lp, impl, shapes = _mixer(positions, **geometry)
        jax.eval_shape(lambda p, x: impl.apply(lp, p, [x], True, None),
                       shapes, jax.ShapeDtypeStruct((1, positions, 32),
                                                    jnp.bfloat16))
        samples = telemetry.get_registry().snapshot()[
            "ssm_lowering_total"]["samples"]
        return [s["labels"] for s in samples]

    telemetry.reset()
    assert traced(1024) == [{"path": "chunked", "chunk": "128",
                             "heads": "8", "state": "128"}]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    telemetry.reset()
    assert traced(1024) == [{"path": "pallas", "chunk": "128", "heads": "8",
                             "state": "128", "blocks": "fwd 8 bwd 8"}]
    telemetry.reset()
    assert traced(1024, chunk=64) == [{"path": "chunked", "chunk": "64",
                                       "heads": "8", "state": "128"}]


def _conv_inputs(positions_last, positions, chans, dtype, kernel=4,
                 groups=2, key=0):
    """u in ``dtype`` as the mixer hands it over (``[H, 8, S]`` with the
    positions on the lanes, ``[G, S, N]`` on the sublanes), the taps and
    their bias float32 as PyTorch's default fills them, and a cotangent."""
    r = jax.random.split(jax.random.PRNGKey(key), 4)
    lead = (chans // 8, 8) if positions_last else (groups, chans)
    shape = (*lead, positions) if positions_last else (
        groups, positions, chans)
    u = jax.random.normal(r[0], shape).astype(dtype)
    taps = jax.random.uniform(r[1], (*lead, kernel), minval=-0.5,
                              maxval=0.5)
    bias = jax.random.uniform(r[2], lead, minval=-0.5, maxval=0.5)
    return (u, taps, bias), jax.random.normal(r[3], shape).astype(dtype)


def _conv_errors(positions_last, inputs, cot, blocks):
    """The kernels (in Pallas' interpreter) against XLA's ``_conv_act``:
    the value and the gradients of u, the taps and the bias, each the
    largest difference over the largest magnitude."""
    out = []
    for path in ("xla", "pallas"):
        y, vjp = jax.vjp(lambda *t: ssm.conv_act(*t, positions_last, path,
                                                 blocks), *inputs)
        out.append([np.asarray(t, np.float32) for t in (y, *vjp(cot))])
    return [float(np.abs(g - w).max() / np.abs(w).max())
            for w, g in zip(*out)]


# (positions on the lanes, positions, channels, blocks of the forward and
# backward kernels or None for ``conv_blocks``' own: the whole sequence)
_CONV_CASES = [
    (True, 512, 32, None), (True, 1024, 32, ((16, 256), (16, 512))),
    (False, 256, 16, None), (False, 256, 128, ((128, 64), (128, 128))),
]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "positions_last,positions,chans,blocks", _CONV_CASES,
    ids=["lanes-one-block", "lanes-blocks", "sublanes-one-block",
         "sublanes-blocks"])
def test_the_conv_kernels_are_xlas_conv_act(positions_last, positions,
                                            chans, blocks, dtype,
                                            interpreted):
    """The causal convolution's kernels, forward and backward, against
    ``_conv_act`` with the positions on the lanes (x) and on the sublanes
    (B and C), in float32 and in bfloat16, over one block and over several
    blocks of channels and positions (the taps reaching into the block
    before, the cotangent into the block after): the value and u's
    gradient within a rounding of u's dtype, the taps' and bias's float32
    gradients (sums over the positions, by block in the kernel) within
    float32's."""
    dtype = jnp.dtype(dtype)
    inputs, cot = _conv_inputs(positions_last, positions, chans, dtype)
    if blocks is None:
        blocks = ssm_kernels.conv_blocks(positions, chans, 4, dtype.itemsize,
                                         positions_last)
        assert blocks.fwd[1] == blocks.bwd[1] == positions
    else:
        blocks = ssm_kernels.ConvBlocks(*blocks)
    errors = _conv_errors(positions_last, inputs, cot, blocks)
    near = 1e-5 if dtype == jnp.float32 else 2.0 ** -7
    assert max(errors[:2]) < near and max(errors[2:]) < 1e-5, errors


@pytest.mark.parametrize("positions_last,positions,chans,blocks",
                         [c for c in _CONV_CASES if c[3]],
                         ids=["lanes-blocks", "sublanes-blocks"])
def test_a_conv_kernel_that_drops_the_halo_is_told_apart(
        monkeypatch, positions_last, positions, chans, blocks, interpreted):
    """The same comparison refuses kernels that read zeros in place of the
    tile beside a block (as at the sequence's ends) between blocks of
    positions: the value, u's gradient and the taps' are far off."""
    monkeypatch.setattr(ssm_kernels, "_halo",
                        lambda tile, edge: jnp.zeros_like(tile))
    inputs, cot = _conv_inputs(positions_last, positions, chans,
                               jnp.float32)
    errors = _conv_errors(positions_last, inputs, cot,
                          ssm_kernels.ConvBlocks(*blocks))
    assert min(errors[:3]) > 0.05, errors


def test_the_convs_lowering_is_counted_with_its_blocks(monkeypatch):
    """A trace of the mixer counts its convolution in
    ``ssm_conv_lowering_total``: ``xla`` on the CPU; on a TPU (the
    selection steered to its branch) ``pallas`` with the kernels' blocks of
    x and of B and C, where the positions are whole lane rows, and ``xla``
    where they are not."""
    from sparknet_tpu.utils import telemetry

    def traced(positions):
        lp, impl, shapes = _mixer(positions)
        jax.eval_shape(lambda p, x: impl.apply(lp, p, [x], True, None),
                       shapes, jax.ShapeDtypeStruct((1, positions, 32),
                                                    jnp.bfloat16))
        samples = telemetry.get_registry().snapshot()[
            "ssm_conv_lowering_total"]["samples"]
        return [s["labels"] for s in samples]

    telemetry.reset()
    assert traced(1024) == [{"path": "xla"}]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    telemetry.reset()
    assert traced(1024) == [{
        "path": "pallas",
        "blocks": "x fwd 64x1024 bwd 64x1024 bc fwd 128x1024 bwd 128x1024"}]
    telemetry.reset()
    assert traced(1000) == [{"path": "xla"}]


def test_the_mixer_through_the_kernels_is_the_mixer_through_xla(
        monkeypatch, interpreted):
    """One mixer of a sequence of 256 on the TPU's branch, the scan's and
    the convolution's kernels in Pallas' interpreter, against the same
    mixer on XLA's forms: the value and the gradients of the input, the
    taps and their bias, float32 throughout."""
    lp, impl, shapes = _mixer(256)
    params = impl.init(jax.random.PRNGKey(2), lp, [(1, 256, 32)])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 256, 32))

    def grads():
        y, vjp = jax.vjp(lambda p, x: impl.apply(lp, p, [x], True, None)[0],
                         params, x)
        dp, dx = vjp(jax.random.normal(jax.random.PRNGKey(4), y.shape))
        return [y, dx, dp[5], dp[6]]

    from sparknet_tpu.utils import telemetry
    with jax.default_matmul_precision("highest"):
        want = grads()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        telemetry.reset()
        got = grads()
    assert [s["labels"]["path"] for s in telemetry.get_registry().snapshot()[
        "ssm_conv_lowering_total"]["samples"]] == ["pallas"]
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max() / jnp.abs(w).max()) < 1e-4


def test_gated_group_norm_normalises_each_group_of_heads():
    """``RMSNorm_grouped(y * silu(z)) * w`` over the published channel
    order ``h * P + p`` in groups of ``H * P / G`` channels; one norm over
    every channel is another result."""
    heads, p, s, groups = 8, 4, 6, 2
    r = jax.random.split(jax.random.PRNGKey(3), 3)
    y = jax.random.normal(r[0], (heads, p, s)) * jnp.arange(
        1, heads + 1)[:, None, None]
    z = jax.random.normal(r[1], (heads, p, s))
    w = jax.random.normal(r[2], (heads * p,))

    def plain(groups):
        u = np.asarray(y * jax.nn.silu(z), np.float64).transpose(2, 0, 1)
        u = u.reshape(s, groups, -1)
        u = u / np.sqrt((u * u).mean(-1, keepdims=True) + 1e-5)
        u = u.reshape(s, heads * p) * np.asarray(w, np.float64)
        return u.reshape(s, heads, p).transpose(1, 2, 0)

    got = ssm.gated_group_norm(y, z, w, groups, 1e-5)
    np.testing.assert_allclose(got, plain(groups), rtol=1e-5, atol=1e-6)
    assert float(np.abs(got - plain(1)).max()) > 0.1


@pytest.mark.parametrize("positions_last", [False, True])
def test_causal_taps_against_a_loop(positions_last):
    """Tap ``kernel - 1`` on the position itself and ``kernel - 1 - j`` on
    the one ``j`` before, zeros before the sequence, whichever axis the
    positions take."""
    r = jax.random.split(jax.random.PRNGKey(4), 2)
    u = jax.random.normal(r[0], (3, 12, 5))               # [lead, S, W]
    w = jax.random.normal(r[1], (3, 5, 4))                # [lead, W, K]
    want = np.zeros(u.shape)
    un, wn = np.asarray(u, np.float64), np.asarray(w, np.float64)
    for t in range(12):
        for j in range(4):
            if t - j >= 0:
                want[:, t] += un[:, t - j] * wn[:, :, 3 - j]
    got = (seq.causal_taps(u.swapaxes(1, 2), w, True).swapaxes(1, 2)
           if positions_last else seq.causal_taps(u, w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _relu2_layer(lo, hi, shared=12):
    return layer("moe", "MixtureOfExperts", ["x"], ["y"], moe_param={
        "num_experts": 8, "top_k": 2, "experts_held_lo": lo,
        "experts_held_hi": hi, "expert_width": 16, "shared_width": shared,
        "activation": "relu2", "routed_scaling": 2.5, "norm_eps": 1e-20,
        "select_bias": True, "select_bias_filler": _GAUSS,
        "weight_filler": _GAUSS, "router_filler": _GAUSS})


@pytest.mark.parametrize("lo,hi,shared", [(0, 8, 12), (2, 6, 12), (4, 8, 0)])
def test_experts_without_a_gate_against_a_dense_loop(lo, hi, shared):
    """``relu(x W_up)^2 W_down`` an expert, the held ones weighed by their
    sigmoid over the chosen sigmoids' sum times 2.5, chosen by score plus
    bias, and the shared expert unweighted, against a loop over the tokens
    in float64."""
    lp = _relu2_layer(lo, hi, shared)
    impl, params = init_and_apply(lp, (1, 10, HIDDEN), key=5)
    n = 6 if shared else 4
    assert len(params) == n and params[1].shape == (hi - lo, HIDDEN, 16)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 10, HIDDEN))
    got = impl.apply(lp, params, [x], True, None)[0][0]
    p = [np.asarray(b, np.float64) for b in params]
    xs = np.asarray(x[0], np.float64)
    relu2 = lambda v: np.maximum(v, 0.0) ** 2
    want = np.zeros_like(xs)
    for t in range(10):
        s = 1 / (1 + np.exp(-(xs[t] @ p[0])))
        chosen = np.argsort(-(s + p[-1]), kind="stable")[:2]
        for e in chosen:
            if lo <= e < hi:
                w = s[e] / (s[chosen].sum() + 1e-20) * 2.5
                want[t] += w * relu2(xs[t] @ p[1][e - lo]) @ p[2][e - lo]
        if shared:
            want[t] += relu2(xs[t] @ p[3]) @ p[4]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("itemsize", [2, 4])
@pytest.mark.parametrize("product", ["up", "down"])
def test_grouped_tiles_at_a_width_of_no_lane_aligned_divisor(product,
                                                             itemsize):
    """At Nemotron-H's experts of 1,856 (14.5 lane rows) each kernel takes
    a tile that is the whole dimension or lane-aligned with a masked
    remainder, within ``_gmm_vmem``'s budget counting the float32 copies a
    masked contraction makes; at bfloat16 the tiles are pinned."""
    m = 24576
    k, n = (2688, 1856) if product == "up" else (1856, 2688)
    tiles = seq.gmm_tiles(m, k, n, itemsize)
    for kernel, (tm, tk, tn) in zip(("gmm", "dlhs", "tgmm"), tiles):
        own_k, own_n = (n, k) if kernel == "dlhs" else (k, n)
        assert tm == 512 and m % tm == 0
        for t, d in ((tk, own_k), (tn, own_n)):
            assert t == d or (t % 128 == 0 and t < d)
            if d % 128 == 0:
                assert d % t == 0
        vmem = seq._gmm_vmem("tgmm" if kernel == "tgmm" else "gmm", tm, tk,
                             tn, itemsize, masked=own_k % tk != 0)
        assert vmem <= seq._GMM_VMEM_MOST
    if itemsize == 2:
        assert tiles == {
            "up": ((512, 2688, 512), (512, 1856, 896), (512, 896, 1024)),
            "down": ((512, 1856, 896), (512, 2688, 512), (512, 1024, 896)),
        }[product]


def test_the_expert_lowering_takes_the_kernels_at_1856(monkeypatch):
    """On a TPU an expert layer of width 1,856 takes the grouped kernels,
    not ``ragged_dot``, and the counter's sample carries its tiles."""
    from sparknet_tpu.utils import telemetry
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert seq.moe_lowering(24576, 2688, 1856, 2) == "gmm"
    samples = [s["labels"] for s in telemetry.get_registry().snapshot()[
        "moe_lowering_total"]["samples"]]
    assert {"path": "gmm",
            "gate_up": "fwd 512x2688x512 dlhs 512x1856x896 tgmm 512x896x1024",
            "down": "fwd 512x1856x896 dlhs 512x2688x512 tgmm 512x1024x896",
            } in samples


def test_attention_without_rotary_turns_nothing():
    """``rotary_dim: 0``: no product with a turning matrix (the projections
    and the core's two products alone), the score scale still on q, and the
    result the reference's attention with no rotary."""
    p = {"num_heads": 8, "num_kv_heads": KV, "head_dim": HEAD_DIM,
         "rotary_dim": 0, "gate": False, "weight_filler": _GAUSS}
    lp = layer("attn", "Attention", ["x"], ["y"], attention_param=p)
    impl, params = init_and_apply(lp, (1, 12, HIDDEN), key=7)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 12, HIDDEN))
    run = lambda lp: impl.apply(lp, params, [x], True, None)[0]
    products = lambda lp: str(jax.make_jaxpr(lambda: run(lp))()).count(
        "dot_general")
    turned = layer("attn", "Attention", ["x"], ["y"], attention_param={
        **p, "rotary_dim": HEAD_DIM})
    assert products(lp) == 6 and products(turned) == 8
    m = {"heads": 8, "kv_heads": KV, "head_dim": HEAD_DIM}
    want = highest(lambda p: ref_h.attention(x[0], p, m))(params)
    close(run(lp)[0], want, 2e-5)


def test_a_layer_sizes_its_rows_by_its_own_margin():
    """``row_margin`` sizes the products' rows over the even share, in whole
    tiles; without it the bound is a quarter over, as every other cell's."""
    g = {"experts": 128, "top_k": 6, "lo": 0, "hi": 8}
    assert seq.moe_row_bound(32768, g) == 15360           # 1.25 x 12,288
    assert seq.moe_row_bound(32768, {**g, "row_margin": 2.0}) == 24576
    lp = _relu2_layer(0, 8)
    assert seq.moe_geometry(lp)["row_margin"] == 1.25
    lp.params["moe_param"].set("row_margin", 2.0)
    assert seq.moe_geometry(lp)["row_margin"] == 2.0
