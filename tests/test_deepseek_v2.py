"""The DeepSeek-V2 builder (``models/deepseek_v2.py``) through ``Net`` and
``Solver.step`` against the benchmark's plain reference
(``reference_deepseek_v2.py``), each of its mechanisms pinned by a number
(the softmax scale with YaRN's ``m^2``, the one rotary key every head
shares, ``γ_kv`` on the latent alone, a softmax router over every expert
with unnormalised weights, two shared experts as one of twice the width),
the expert shares against the uncut layer, the configuration file against
the published values, and the comparison that decides the cell's
``correct`` as its driver makes it."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.drivers import solver_seq
from benchmark.lib import check_lm, harness, mla_flops
from benchmark.lib import reference_deepseek_v2 as ref
from sparknet_tpu import models
from sparknet_tpu.graph.net import Net
from sparknet_tpu.ops import get_layer_impl, sequence
from sparknet_tpu.proto import load_solver_prototxt_with_net
from sparknet_tpu.proto.caffe_pb import NetState, Phase
from sparknet_tpu.solvers import Solver
from test_laguna import adam_reference          # Caffe's Adam in float64

CONFIG = os.path.join(REPO, "benchmark", "configs", "deepseek_v2_lite.json")
DATA = os.path.join(REPO, "benchmark", "tests", "data")
TINY = os.path.join(DATA, "deepseek_tiny.json")
SOLVER = ('type: "Adam"\nbase_lr: 0.0003\nmomentum: 0.9\nmomentum2: 0.95\n'
          'delta: 1e-8\nclip_gradients: 1.0\nlr_policy: "fixed"\n')

# the catalog row `DeepSeek-V2-Lite` (deepseek-ai/DeepSeek-V2-Lite
# config.json), every key but the three the cut changes
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
    "norm_topk_prob": False, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_key_value_heads": 16,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy",
    "v_head_dim": 128,
}
CUT = {"num_hidden_layers": (27, 6), "n_routed_experts": (64, 8),
       "vocab_size": (102400, 12800)}
# YaRN's m = 0.1 * 0.707 * ln 40 + 1 and the scale 192^-1/2 * m^2
TAU = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2


def load(path):
    with open(path) as f:
        return json.load(f)


def tiny(sequences=2, positions=16, **over):
    cfg = load(TINY)
    return cfg, models.deepseek_v2(sequences, 1, seq_len=positions,
                                   **{**cfg["builder_args"], **over})


def train(net_param):
    return net_param.filtered(NetState(Phase.TRAIN))


def with_param(net_param, type_, sub, **values):
    """The net with ``values`` set in ``sub`` of every layer of ``type_``:
    a variant of the program that the comparison has to refuse."""
    for lp in net_param.layer:
        if lp.type == type_:
            for k, v in values.items():
                lp.params[sub].set(k, v)
    return net_param


def errors_against_reference(net_param, cfg, seed=3, positions=24,
                             sharpen=1.0):
    """(largest logit error over the largest logit, largest gradient error
    over that leaf's largest reference entry) of a float32 net on seeded
    weights and tokens, against the reference.  ``sharpen`` multiplies
    every latent layer's ``W_q`` and ``W_ukv``: at toy widths and the
    fillers' 0.02 the scores are all near 0 and every softmax near
    uniform, whatever scales them."""
    params = Net(train(tiny(2, positions)[1]), NetState(Phase.TRAIN)).init(
        jax.random.PRNGKey(seed))
    params = {k: [b * sharpen if k.endswith("/attn") and i in (0, 4) else b
                  for i, b in enumerate(v)] for k, v in params.items()}
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(seed + 1), 2,
                                    positions, 64)
    logits, _ = check_lm.system_forward(train(net_param), params,
                                        tokens[:1])
    grads = jax.grad(lambda p: Net(net_param, NetState(Phase.TRAIN)).apply(
        p, {"tokens": tokens}, train=True).loss)(params)
    m = ref.model(cfg)
    want_logits = ref.highest(lambda p, t: ref.logits(p, t, m))(params,
                                                                tokens[0])
    want = ref.highest(jax.grad(lambda p: ref.loss(p, tokens, m)))(params)
    logit_err = float(jnp.abs(logits - want_logits).max()
                      / jnp.abs(want_logits).max())
    grad_err = max(float(jnp.abs(g - w).max() / (jnp.abs(w).max() + 1e-12))
                   for name in params for g, w in zip(grads[name],
                                                      want[name]))
    return logit_err, grad_err


def test_configuration_holds_the_published_widths():
    cfg = load(CONFIG)
    for key, want in PUBLISHED.items():
        assert cfg[key] == want, key
    assert sorted(cfg["reduced"]) == sorted(CUT)
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["layers_kept"] == cfg["builder_args"]["layers_kept"] == [
        0, 1, 2, 3, 4, 5]
    assert dep["experts_held"] == [0, 8] and dep["vocab_rows"] == [0, 12800]
    assert {"weights", "rotary_pairs", "kv_a_proj_with_mqa",
            "shared_experts", "no_auxiliary_loss", "router_frozen", "loss",
            "sequence"} <= set(cfg["assumed"])
    assert cfg["modules"] == {"reference": "reference_deepseek_v2",
                              "operations": "mla_flops"}
    assert cfg["builder"] == "deepseek_v2"


def test_builder_builds_what_the_configuration_states():
    """The parameter arithmetic of the cut, part by part."""
    cfg = load(CONFIG)
    net = train(models.deepseek_v2(4, 1, **cfg["builder_args"]))
    mla_flops.check_as_built(cfg, net)
    by_name = {lp.name: mla_flops.parameters(lp, g)
               for lp, g in mla_flops.layers(net)}
    assert by_name["L0/attn"] == by_name["L5/attn"] == 13_763_072
    assert by_name["L0/mlp"] == 3 * 2048 * 10944
    assert by_name["L0/attn"] + by_name["L0/mlp"] + 4096 == 81_007_104
    assert by_name["L1/attn"] + by_name["L1/moe"] + 4096 == 100_405_760
    assert by_name["embed"] == by_name["lm_loss"] == 26_214_400
    assert cfg["as_built"]["parameters"] == sum(by_name.values()) == \
        635_466_752
    with pytest.raises(SystemExit, match="not the one"):
        mla_flops.check_as_built(cfg, train(models.deepseek_v2(
            4, 1, **{**cfg["builder_args"], "kv_lora_rank": 256})))


def test_published_depth_is_the_builders_default():
    """The builder's defaults are the published model: 27 layers, the first
    dense, 64 experts of which 6 a token, 102,400 ids, 15.7 billion
    parameters."""
    net = train(models.deepseek_v2(1, 1))
    rows = {r[0]: r for r in mla_flops.as_built(net)["layers"]}
    for i in range(27):
        assert rows[f"L{i}/attn"][2:] == [2048, 16, 512, 128, 64, 128,
                                          pytest.approx(TAU)]
        assert (f"L{i}/mlp" in rows) == (i == 0)
        assert (f"L{i}/moe" in rows) == (i > 0)
    assert rows["L0/mlp"][2:] == [2048, 10944]
    assert rows["L26/moe"][2:] == [2048, 1408, 64, 6, 64, 2816, 0,
                                   "softmax", 0]
    assert rows["embed"][2:] == rows["lm_loss"][2:] == [2048, 102400]
    assert 15.70e9 < mla_flops.as_built(net)["parameters"] < 15.72e9


def test_logits_loss_and_gradients_against_the_reference():
    cfg, net_param = tiny(2, 24)
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(3))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(4), 2, 24, 64)
    loss, grads = jax.value_and_grad(
        lambda p: net.apply(p, {"tokens": tokens}, train=True).loss)(params)
    m = ref.model(cfg)
    assert [(l["name"], l["ffn"]) for l in m["layers"]] == [
        ("L0", "mlp"), ("L1", "moe"), ("L3", "moe")]
    want_loss, want = ref.highest(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, m)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for name in params:
        for g, w in zip(grads[name], want[name]):
            scale = float(jnp.abs(w).max())
            assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-9, name
    logit_err, _ = errors_against_reference(net_param, cfg)
    assert logit_err < 1e-5


def test_the_softmax_scale_is_yarns_m_squared():
    """τ = 192^-1/2 · m², 0.114721 at the published widths; a program that
    scales by d^-1/2 alone is off against the reference by far more than
    the float32 limits."""
    net = models.deepseek_v2(1, 1, layers_kept=[0])
    lp = next(l for l in net.layer if l.type == "LatentAttention")
    assert lp.sub("latent_attention_param").get("softmax_scale") == \
        pytest.approx(TAU, rel=1e-12)
    assert round(TAU, 6) == 0.114721
    cfg, net_param = tiny(2, 24)
    assert ref.model(cfg)["tau"] == pytest.approx(16 ** -0.5 * (
        0.1 * 0.707 * math.log(40) + 1) ** 2)
    plain = with_param(tiny(2, 24)[1], "LatentAttention",
                       "latent_attention_param", softmax_scale=16 ** -0.5)
    logit_err, grad_err = errors_against_reference(plain, cfg, sharpen=8.0)
    assert max(errors_against_reference(tiny(2, 24)[1], cfg,
                                        sharpen=8.0)) < 1e-4
    tol = cfg["check"]["tolerance"]["float32"]
    assert logit_err > 10 * tol["logits"] and grad_err > 10 * tol["grads"][0]


def _latent_layer(**over):
    cfg = load(TINY)
    net = models.deepseek_v2(1, 1, seq_len=24, **{
        **cfg["builder_args"], "layers_kept": [0], **over})
    lp = next(l for l in net.layer if l.type == "LatentAttention")
    impl = get_layer_impl("LatentAttention")
    # W_q, W_kr and W_ukv at five times the filler's spread: scores far
    # from 0, so that the softmax is far from uniform at toy widths
    params = [b * 5 if i in (0, 2, 4) else b for i, b in enumerate(
        impl.init(jax.random.PRNGKey(5), lp, [(1, 24, 32)]))]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, 32), jnp.float32)
    out = lambda p: impl.apply(lp, p, [x], True, None)[0][0]
    m = ref.model({**cfg, "builder_args": {**cfg["builder_args"], **over}})
    want = lambda p: ref.highest(lambda p: ref.mla(x[0], p, m))(p)
    return params, out, want


def test_one_rotary_key_is_shared_by_every_head():
    """``W_kr`` is one key of ``qk_rope_head_dim``, not one a head: with
    every head's own key (``k_nope``) taken away the scores are the shared
    key's alone, every head's output moves when it is, and program and
    reference agree either way."""
    params, out, want = _latent_layer()
    wq, wdkv, wkr, gamma, wukv, wo = params
    assert wkr.shape == (32, 8)
    # the query heads' rotary halves only, and no per-head key
    heads_q = 2 * wq.reshape(32, 2, 16)
    only_rope = [heads_q.at[:, :, :8].set(0).reshape(32, 32), wdkv,
                 2 * wkr, gamma, wukv.reshape(16, 2, 16).at[:, :, :8].set(0)
                 .reshape(16, 32), wo]
    without = [*only_rope[:2], jnp.zeros_like(wkr), *only_rope[3:]]
    for p in (only_rope, without):
        np.testing.assert_allclose(out(p), want(p), rtol=2e-5, atol=2e-6)
    # the output of each head (its 8 columns of W_o's input) moves
    per_head = lambda p: jnp.einsum(
        "sh,hk->sk", out(p), jnp.linalg.pinv(wo)).reshape(24, 2, 8)
    moved = jnp.abs(per_head(only_rope) - per_head(without)).max(axis=(0, 2))
    assert float(moved.min()) > 1e-2


def test_gamma_kv_scales_the_latent_alone():
    """``γ_kv`` multiplies the normalised latent and nothing else: doubling
    it gives the output of doubling ``W_ukv`` (the keys' own halves and the
    values), not of doubling the rotary key too, and its gradient is the
    reference's."""
    params, out, want = _latent_layer()
    wq, wdkv, wkr, gamma, wukv, wo = params
    doubled = out([wq, wdkv, wkr, 2 * gamma, wukv, wo])
    np.testing.assert_allclose(doubled, out([wq, wdkv, wkr, gamma, 2 * wukv,
                                             wo]), rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(doubled - out([wq, wdkv, 2 * wkr, gamma, 2 * wukv,
                                        wo])).max()) > 1e-3
    gamma = gamma * jnp.linspace(0.5, 1.5, gamma.shape[0])
    p = [wq, wdkv, wkr, gamma, wukv, wo]
    g = jax.grad(lambda g: jnp.sum(out([*p[:3], g, *p[4:]]) ** 2))(gamma)
    w = jax.grad(lambda g: jnp.sum(want([*p[:3], g, *p[4:]]) ** 2))(gamma)
    np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)


def test_softmax_over_every_expert_weighs_the_chosen_unnormalised():
    """The router's weights are ``softmax(x W_r)`` over all the experts at
    the chosen ones, not divided by their sum: a token's six weigh less
    than 1 in all; a program that renormalises them, or scores by a
    sigmoid, is off against the reference by far more than the limits."""
    g = {"experts": 64, "top_k": 6, "lo": 0, "hi": 64, "scaling": 1.0,
         "eps": 0.0, "scoring": "softmax", "norm_topk": False}
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 32))
    wr = jax.random.normal(jax.random.PRNGKey(1), (32, 64)) * 0.3
    token, w, _, sent, dropped = sequence.moe_route(x, wr, g)
    p = jax.nn.softmax(jnp.dot(x, wr, precision="highest"), axis=-1)
    top = jnp.sort(p, axis=-1)[:, ::-1][:, :6]
    got = np.zeros((96, 6))
    rank = {}
    for t, weight in zip(np.asarray(token), np.asarray(w)):
        got[t, rank.setdefault(t, 0)] = weight
        rank[t] += 1
    np.testing.assert_allclose(np.sort(got, axis=-1)[:, ::-1], top,
                               rtol=1e-5)
    assert int(np.sum(sent)) == 96 * 6 and int(dropped) == 0
    assert float(np.max(np.sum(got, axis=-1))) < 0.9
    # the expert layer alone: the program is the reference, and each
    # variant is off by a tenth of the layer's output or more
    cfg, net_param = tiny(1, 24)
    impl = get_layer_impl("MixtureOfExperts")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 32))
    m = ref.model(cfg)
    for variant in ({}, {"norm_topk": True}, {"scoring": "sigmoid"}):
        lp = next(l for l in with_param(tiny(1, 24)[1], "MixtureOfExperts",
                                        "moe_param", **variant).layer
                  if l.name == "L1/moe")
        params = impl.init(jax.random.PRNGKey(3), lp, [(1, 24, 32)])
        got = impl.apply(lp, params, [x], True, None)[0][0]
        want = ref.highest(lambda p: ref.moe(x[0], p, m))(params)
        err = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
        assert (err < 1e-5) if not variant else (err > 0.1), (variant, err)


def test_two_shared_experts_are_one_of_twice_the_width():
    """The layer's one shared blob of 2 x 16 is the sum of two gated MLPs
    of 16 made from its halves, as the reference computes it."""
    cfg, net_param = tiny(1, 24)
    lp = next(l for l in net_param.layer if l.name == "L1/moe")
    assert sequence.moe_geometry(lp)["shared"] == 2 * 16
    impl = get_layer_impl("MixtureOfExperts")
    params = impl.init(jax.random.PRNGKey(7), lp, [(1, 24, 32)])
    sg, su, sd = params[4:7]
    x = jax.random.normal(jax.random.PRNGKey(8), (24, 32))
    one = ref.mlp(x, sg, su, sd)
    two = (ref.mlp(x, sg[:, :16], su[:, :16], sd[:16])
           + ref.mlp(x, sg[:, 16:], su[:, 16:], sd[16:]))
    np.testing.assert_allclose(one, two, rtol=1e-5, atol=1e-6)
    # the layer less the same layer without its shared experts is they
    routed_only = [*params[:6], jnp.zeros_like(sd)]
    out = lambda p: impl.apply(lp, p, [x[None]], True, None)[0][0]
    np.testing.assert_allclose(out(params) - out(routed_only), two,
                               rtol=1e-4, atol=1e-5)


def test_eight_expert_shares_add_up_to_the_uncut_layer():
    """At a small size: the routed parts the 8 disjoint shares of 64
    experts give, with the shared experts every chip computes alike
    counted once, add up to the uncut reference layer."""
    cfg = load(TINY)
    args = {**cfg["builder_args"], "num_experts": 64, "top_k": 6}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 32))
    full_lp = next(l for l in models.deepseek_v2(
        1, 1, seq_len=40, **{**args, "experts_held": (0, 64)}).layer
        if l.name == "L1/moe")
    impl = get_layer_impl("MixtureOfExperts")
    full = impl.init(jax.random.PRNGKey(10), full_lp, [(1, 40, 32)])
    m = {**ref.model({**cfg, "builder_args": {
        **args, "experts_held": (0, 64)}}), "held": (0, 64)}
    want = ref.highest(lambda p: ref.moe(x[0], p, m))(full)
    # what every chip computes alike: the layer with no routed output
    shared = impl.apply(full_lp, [*full[:3], jnp.zeros_like(full[3]),
                                  *full[4:7]], [x], True, None)[0][0]
    total = shared
    for share in range(8):
        lo, hi = 8 * share, 8 * share + 8
        lp = next(l for l in models.deepseek_v2(
            1, 1, seq_len=40, **{**args, "experts_held": (lo, hi)}).layer
            if l.name == "L1/moe")
        part = impl.apply(lp, [full[0], *(b[lo:hi] for b in full[1:4]),
                               *full[4:7]], [x], True, None)[0][0]
        total = total + (part - shared)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_drivers_comparison_holds_the_tiny_net(dtype):
    """Logits, loss and the seven leaves' gradients as the cell's driver
    compares them, in float32 and with a bfloat16 net; and the reference
    with float8 operands is refused by the bfloat16 limits."""
    mix = {**load(os.path.join(DATA, "traffic", "tiny_seq.json")),
           "compute_dtype": dtype}
    driver = solver_seq.Driver(harness.Cell(
        name="tiny_seq", config=load(TINY), mix=mix, chips=1, seed=3,
        cache_dir=""))
    cfg, net_param = tiny(1, 32)
    cd = driver._compute_dtype()
    params = Net(net_param, NetState(Phase.TRAIN)).init(jax.random.PRNGKey(6))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(7), 1, 32, 64)
    leaves = driver.grad_leaves()
    assert leaves == [tuple(l) for l in load(CONFIG)["check"]["grad_leaves"]]
    from sparknet_tpu.ops.sequence import moe_load
    logits, loss = check_lm.system_forward(train(net_param), params, tokens,
                                           cd)
    grads = check_lm.system_grads(train(net_param), params, tokens, leaves,
                                  cd)
    net = Net(net_param, NetState(Phase.TRAIN), compute_dtype=cd)
    rows = {k: v["rows"] for k, v in
            moe_load(net, params, {"tokens": tokens}).items()}
    verdict = driver.compare(params, tokens, tokens, leaves, logits, loss,
                             grads, rows)
    if dtype == "float32":
        assert verdict["ok"], verdict
        assert verdict["logits_rel_err"] < 1e-5
        assert max(verdict["grads_rel_err"]) < 1e-4
        assert verdict["rows_rel_err"] == 0.0
        return
    # toy widths round more coarsely than the published ones: the limits
    # are the chip's, so only the order is held here
    assert verdict["finite"] and verdict["logits_rel_err"] < 0.1
    assert max(verdict["grads_rel_err"]) < 0.5
    *low, _ = driver.reference_results(params, tokens, tokens, leaves,
                                       jnp.float8_e4m3fn)
    low = check_lm.errors(*low, logits, loss, grads)
    tol = load(CONFIG)["check"]["tolerance"]["bfloat16"]
    assert all(e > t for e, t in zip(low["grads_rel_err"], tol["grads"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_adam_steps_against_the_reference(dtype):
    """``Solver.step`` on the frozen-router net against Caffe's Adam on the
    reference's gradients: the routers stay as the seed made them."""
    cfg, net_param = tiny()
    sp = load_solver_prototxt_with_net(SOLVER, net_param)
    solver = Solver(sp, seed=5, compute_dtype=(
        None if dtype == "float32" else jnp.bfloat16))
    start = jax.tree_util.tree_map(np.asarray, solver.params)
    batches = [{"tokens": check_lm.seeded_tokens(
        jax.random.PRNGKey(20 + i), 2, 16, 64)} for i in range(3)]
    solver.set_train_data(iter(batches))
    got_losses = [solver.step(1) for _ in range(3)]
    m = ref.model(cfg)
    grads_fn = ref.highest(jax.value_and_grad(
        lambda p, b: ref.loss(p, b["tokens"], m)))
    frozen = lambda k, i: k.endswith("/moe") and i == 0
    lr_mults = jax.tree_util.tree_leaves(
        {k: [0.0 if frozen(k, i) else 1.0 for i in range(len(v))]
         for k, v in start.items()})
    want_losses, want = adam_reference(start, grads_fn, batches, 3,
                                       lr_mults)
    for name in ("L1/moe", "L3/moe"):
        assert np.array_equal(np.asarray(solver.params[name][0]),
                              start[name][0])
    loss_tol, update_tol = ((1e-5, 2e-3) if dtype == "float32"
                            else (3e-2, 0.5))
    np.testing.assert_allclose(got_losses, want_losses, rtol=loss_tol)
    for name in start:
        for a, b, w in zip(start[name], solver.params[name], want[name]):
            moved = np.asarray(w) - a
            err = np.linalg.norm(np.asarray(b, np.float64) - w)
            assert err <= update_tol * max(np.linalg.norm(moved), 1e-12), name
    assert solver.iter == 3


def test_types_check_and_counters_on_the_latent_layers():
    """The types check reads the latent layers' products; the lowering
    counter's samples carry the two head sizes, and the load counters count
    the softmax router's rows."""
    from sparknet_tpu.utils import telemetry
    _, net_param = tiny(2, 16)
    net = Net(net_param, NetState(Phase.TRAIN), compute_dtype=jnp.bfloat16)
    params = Net(net_param, NetState(Phase.TRAIN)).init(jax.random.PRNGKey(1))
    held = check_lm.held_precision("bfloat16", net, params, 2, 16)
    assert held["ok"] and "float32" in held["products_fed"]
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(2), 2, 16, 64)
    load_ = sequence.moe_load(net, params, {"tokens": tokens})
    assert set(load_) == {"L1/moe", "L3/moe"}
    assert all(sum(v["rows"]) > 0 and v["dropped"] == 0
               for v in load_.values())
    samples = telemetry.get_registry().snapshot()["attn_lowering_total"][
        "samples"]
    assert {"path": "xla", "head_dim": "16", "v_head_dim": "8"} in [
        s["labels"] for s in samples]


def test_the_load_counts_what_a_step_of_the_whole_batch_leaves_out():
    """``moe_load`` forwards one sequence at a time and counts rows and
    rows left out as the step that routes the whole batch at once: its
    rows are the pooled route's and so is what it leaves out.  Here the
    first sequence's tokens share a component that the held experts'
    router columns favour, so that sequence alone sends them every pick
    (3,072 rows against a bound of 2,048 for its 1,024 tokens) and the
    other about its share: the batch of 2,048 tokens is over its bound of
    4,096 by less than the first sequence is over its own."""
    _, net_param = tiny(2, 1024)
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(11))
    shared = jnp.ones((32,)) / 32 ** 0.5
    params["embed"][0] = params["embed"][0].at[:32].add(4.0 * shared)
    for name in ("L1/moe", "L3/moe"):
        params[name][0] = params[name][0].at[:, 2:6].add(8.0 * shared[:, None])
    tokens = jnp.stack([
        check_lm.seeded_tokens(jax.random.PRNGKey(12), 1, 1024, 32)[0],
        32 + check_lm.seeded_tokens(jax.random.PRNGKey(13), 1, 1024, 32)[0]])
    load_ = sequence.moe_load(net, params, {"tokens": tokens})
    blobs = net.apply_all(params, {"tokens": tokens}, train=True)
    for n in net.nodes:
        if n.lp.type != "MixtureOfExperts":
            continue
        x = blobs[n.bottoms[0]]
        g = sequence.moe_geometry(n.lp)
        _, _, _, sent, dropped = sequence.moe_route(
            x.reshape(-1, x.shape[-1]), params[n.lp.name][0], g)
        first = sequence.moe_route(x[0], params[n.lp.name][0], g)
        assert load_[n.lp.name]["rows"] == np.asarray(sent).tolist()
        assert load_[n.lp.name]["dropped"] == int(dropped) > 0
        assert int(first[3].sum()) == 3 * 1024 and int(first[4]) > int(
            dropped)
