"""The LFM2 builder (``models/lfm2.py``) through ``Net`` and ``Solver.step``
against the benchmark's plain reference (``reference_lfm2.py``), the
configuration file against the published values, the tied embedding, and
the comparison that decides the cell's ``correct`` as its driver makes it."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.drivers import solver_seq
from benchmark.lib import check_lm, harness, seq_flops
from benchmark.lib import reference_lfm2 as ref
from sparknet_tpu import models
from sparknet_tpu.graph.net import Net
from sparknet_tpu.proto import load_solver_prototxt_with_net
from sparknet_tpu.proto.caffe_pb import NetState, Phase
from sparknet_tpu.solvers import Solver
from test_laguna import adam_reference          # Caffe's Adam in float64

CONFIG = os.path.join(REPO, "benchmark", "configs", "lfm2_24b_a2b.json")
DATA = os.path.join(REPO, "benchmark", "tests", "data")
TINY = os.path.join(DATA, "lfm2_tiny.json")
SOLVER = ('type: "Adam"\nbase_lr: 0.0003\nmomentum: 0.9\nmomentum2: 0.95\n'
          'delta: 1e-8\nclip_gradients: 1.0\nlr_policy: "fixed"\n')

# the catalog row `LFM2-24B-A2B` (LiquidAI/LFM2-24B-A2B config.json)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_key_value_heads": 8,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
}
CUT = {"num_hidden_layers": (40, 5), "num_dense_layers": (2, 1),
       "num_experts": (64, 8), "vocab_size": (65536, 8192)}
LAYER_TYPES = ["full_attention" if i % 4 == 2 else "conv"
               for i in range(40)]


def load(path):
    with open(path) as f:
        return json.load(f)


def tiny(sequences=2, positions=16):
    cfg = load(TINY)
    return cfg, models.lfm2(sequences, 1, seq_len=positions,
                            **cfg["builder_args"])


def train(net_param):
    return net_param.filtered(NetState(Phase.TRAIN))


def tiny_driver(dtype="float32"):
    """The cell's driver on the tiny configuration, as ``run.py`` makes
    it."""
    mix = {**load(os.path.join(DATA, "traffic", "tiny_seq.json")),
           "compute_dtype": dtype}
    return solver_seq.Driver(harness.Cell(
        name="tiny_seq", config=load(TINY), mix=mix, chips=1, seed=3,
        cache_dir=""))


def test_configuration_holds_the_published_widths():
    cfg = load(CONFIG)
    for key, want in PUBLISHED.items():
        assert cfg[key] == want, key
    assert cfg["layer_types"] == LAYER_TYPES
    assert sorted(cfg["reduced"]) == sorted(CUT)
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here
    dep = cfg["deployment"]
    assert dep["chips_sharing_a_layer"] == 8
    assert dep["layers_kept"] == cfg["builder_args"]["layers_kept"] == [
        0, 2, 3, 4, 5]
    assert dep["experts_held"] == [0, 8] and dep["vocab_rows"] == [0, 8192]
    assert {"tied_embedding", "intermediate_size", "short_conv", "attention",
            "router", "weights", "loss", "sequence", "router_frozen"} <= set(
                cfg["assumed"])
    assert cfg["modules"] == {"reference": "reference_lfm2",
                              "operations": "seq_flops"}


def test_builder_builds_what_the_configuration_states():
    """The parameter arithmetic of the cut, layer by layer."""
    cfg = load(CONFIG)
    net = train(models.lfm2(4, 1, **cfg["builder_args"]))
    seq_flops.check_as_built(cfg, net)
    by_name = {lp.name: seq_flops.parameters(lp, g)
               for lp, g in seq_flops.layers(net)}
    assert by_name["embed"] == by_name["lm_loss"] == 16_777_216
    assert by_name["L0/conv"] == by_name["L3/conv"] == 16_783_360
    assert by_name["L2/attn"] == 10_485_888
    assert by_name["L0/mlp"] == 72_351_744
    assert by_name["L2/moe"] == 75_628_608
    assert by_name["embedding_norm"] == 2048
    layer = lambda i, op, ffn: (by_name[f"L{i}/{op}"] + by_name[f"L{i}/{ffn}"]
                                + 4096)
    assert layer(0, "conv", "mlp") == 89_139_200
    assert layer(2, "attn", "moe") == 86_118_592
    assert layer(3, "conv", "moe") == 92_416_064
    # the tied matrix once
    assert cfg["as_built"]["parameters"] == sum(by_name.values()) - \
        by_name["lm_loss"] == 469_285_248
    assert cfg["as_built"]["bytes_at_16_a_parameter"] == 16 * 469_285_248
    with pytest.raises(SystemExit, match="not the one"):
        seq_flops.check_as_built(cfg, train(models.lfm2(
            4, 1, **{**cfg["builder_args"], "expert_width": 1024})))


def test_published_depth_is_the_builders_default():
    """The builder's defaults are the published model: 40 layers of the
    row's ``layer_types``, 2 dense layers, 64 experts of which 4 a token,
    65,536 ids, some 24 billion parameters."""
    net = train(models.lfm2(1, 1))
    rows = {r[0]: r for r in seq_flops.as_built(net)["layers"]}
    for i, kind in enumerate(LAYER_TYPES):
        assert (f"L{i}/attn" in rows) == (kind == "full_attention")
        assert (f"L{i}/conv" in rows) == (kind == "conv")
        assert (f"L{i}/mlp" in rows) == (i < 2)
        assert (f"L{i}/moe" in rows) == (i >= 2)
    assert rows["L2/attn"][2:] == [2048, 32, 8, 64, 0, 64, 0, 1]
    assert rows["L0/mlp"][2:] == [2048, 11776]
    assert rows["L39/moe"][2:] == [2048, 1536, 64, 4, 64, 0, 1]
    assert rows["embed"][2:] == rows["lm_loss"][2:] == [2048, 65536]
    assert 23.5e9 < seq_flops.as_built(net)["parameters"] < 24.5e9


def test_embedding_and_head_are_one_blob():
    """Tied: the net stores one matrix, the head has no blob of its own,
    the gradient is the sum of both uses, and Adam keeps one pair of
    moments for it."""
    cfg, net_param = tiny()
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(0))
    assert "lm_loss" not in params and len(params["embed"]) == 1
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(1), 2, 16, 64)
    got = jax.grad(lambda p: net.apply(
        p, {"tokens": tokens}, train=True).loss)(params)["embed"][0]
    m = ref.model(cfg)

    def two_uses(table, head):
        # the reference's loss with the head's copy told apart
        x = {**params, "embed": [table]}
        total = 0.0
        for seq in tokens:
            h = ref.hidden(x, seq, m)
            logp = jax.nn.log_softmax(h @ head.T, -1)[:-1]
            total = total - jnp.sum(jnp.take_along_axis(
                logp, seq[1:, None], axis=-1))
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))

    table = params["embed"][0]
    g_table, g_head = ref.highest(jax.grad(two_uses, (0, 1)))(table, table)
    assert float(jnp.abs(g_table).max()) > 0 < float(jnp.abs(g_head).max())
    scale = float(jnp.abs(g_table + g_head).max())
    assert float(jnp.abs(got - (g_table + g_head)).max()) <= 2e-4 * scale
    solver = Solver(load_solver_prototxt_with_net(SOLVER, net_param), seed=0)
    for moments in solver.state.values():
        if isinstance(moments, dict):
            assert "lm_loss" not in moments
            assert len(moments.get("embed", [None])) == 1


def test_gradients_through_the_net_against_the_reference():
    cfg, net_param = tiny(2, 24)
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(3))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(4), 2, 24, 64)
    loss, grads = jax.value_and_grad(
        lambda p: net.apply(p, {"tokens": tokens}, train=True).loss)(params)
    m = ref.model(cfg)
    assert [l["name"] + "/" + l["op"] + "/" + l["ffn"] for l in m["layers"]
            ] == ["L0/conv/mlp", "L2/attn/moe", "L3/conv/moe",
                  "L4/conv/moe", "L5/conv/moe"]
    want_loss, want = ref.highest(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, m)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for name in params:
        for g, w in zip(grads[name], want[name]):
            scale = float(jnp.abs(w).max())
            assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-9, name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_drivers_comparison_holds_the_tiny_net(dtype):
    """Logits, loss and the six leaves' gradients as the cell's driver
    compares them, in float32 and with a bfloat16 net; and the reference
    with float8 operands is refused by the bfloat16 limits."""
    driver = tiny_driver(dtype)
    cfg, net_param = tiny(1, 32)
    cd = driver._compute_dtype()
    params = Net(net_param, NetState(Phase.TRAIN)).init(jax.random.PRNGKey(6))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(7), 1, 32, 64)
    leaves = driver.grad_leaves()
    assert leaves == [("L2/moe", 0), ("L2/moe", 3), ("L3/conv", 1),
                      ("L3/conv", 0), ("L2/attn", 4), ("L0/mlp", 0)]
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
    else:
        # toy widths round more coarsely than the published ones: the
        # limits are the chip's, so only the order is held here
        assert verdict["finite"] and verdict["logits_rel_err"] < 0.1
        assert max(verdict["grads_rel_err"]) < 0.5
        return
    # the rows are the choice's: without the bias other experts are sent
    # them, which no weight shows
    m = ref.model(cfg)
    unbiased = {k: [*v[:4], jnp.zeros_like(v[4])] if k.endswith("/moe")
                else v for k, v in params.items()}
    assert any((np.asarray(ref.expert_rows(unbiased, tokens[0], m)[k])
                != np.asarray(rows[k])).any() for k in rows)
    *low, _ = driver.reference_results(params, tokens, tokens, leaves,
                                       jnp.float8_e4m3fn)
    low = check_lm.errors(*low, logits, loss, grads)
    tol = load(CONFIG)["check"]["tolerance"]["bfloat16"]
    assert low["loss_abs_err"] > tol["loss"] or any(
        e > t for e, t in zip(low["grads_rel_err"], tol["grads"]))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_adam_steps_against_the_reference(dtype):
    """``Solver.step`` on the tied, biased, frozen-router net against
    Caffe's Adam on the reference's gradients: the routers and the
    selection bias stay as the seed made them, the one embedding moves by
    the gradient of both its uses."""
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
    frozen = lambda k, i: k.endswith("/moe") and i in (0, 4)
    lr_mults = jax.tree_util.tree_leaves(
        {k: [0.0 if frozen(k, i) else 1.0 for i in range(len(v))]
         for k, v in start.items()})
    want_losses, want = adam_reference(start, grads_fn, batches, 3,
                                       lr_mults)
    for i in (2, 3, 4, 5):
        for blob in (0, 4):
            assert np.array_equal(
                np.asarray(solver.params[f"L{i}/moe"][blob]),
                start[f"L{i}/moe"][blob])
    # as tests/test_laguna.py: float32 agrees to rounding; in bfloat16
    # Adam's first steps are rate * sign(gradient), so the update is
    # compared in norm
    loss_tol, update_tol = ((1e-5, 2e-3) if dtype == "float32"
                            else (3e-2, 0.5))
    np.testing.assert_allclose(got_losses, want_losses, rtol=loss_tol)
    for name in start:
        for a, b, w in zip(start[name], solver.params[name], want[name]):
            moved = np.asarray(w) - a
            err = np.linalg.norm(np.asarray(b, np.float64) - w)
            assert err <= update_tol * max(np.linalg.norm(moved), 1e-12), name
    assert float(np.abs(np.asarray(solver.params["embed"][0])
                        - start["embed"][0]).max()) > 0
    assert solver.iter == 3


def test_types_check_and_counters_on_the_new_layers():
    """The types check reads the new layers' products, and the lowering
    and load counters count them as they count Laguna's."""
    from sparknet_tpu.ops.sequence import moe_load
    from sparknet_tpu.utils import telemetry
    _, net_param = tiny(2, 16)
    net = Net(net_param, NetState(Phase.TRAIN), compute_dtype=jnp.bfloat16)
    params = Net(net_param, NetState(Phase.TRAIN)).init(jax.random.PRNGKey(1))
    held = check_lm.held_precision("bfloat16", net, params, 2, 16)
    assert held["ok"] and "float32" in held["products_fed"]
    assert not check_lm.held_precision("float32", net, params, 2, 16)["ok"]
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(2), 2, 16, 64)
    total = lambda snap, name: sum(
        s["value"] for s in snap.get(name, {}).get("samples", []))
    before = telemetry.get_registry().snapshot()
    load = moe_load(net, params, {"tokens": tokens})
    assert set(load) == {f"L{i}/moe" for i in (2, 3, 4, 5)}
    assert all(len(v["rows"]) == 4 and v["dropped"] == 0
               for v in load.values())
    after = telemetry.get_registry().snapshot()
    assert total(after, "moe_rows_total") - total(
        before, "moe_rows_total") == sum(sum(v["rows"])
                                         for v in load.values())
    assert total(after, "attn_lowering_total") > total(
        before, "attn_lowering_total")
