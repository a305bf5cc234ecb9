"""The Laguna builder (``models/laguna.py``) through ``Net`` and
``Solver.step`` against the benchmark's plain reference, and the
configuration file against the published values."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import check_lm, lm_flops
from benchmark.lib import reference_lm as ref
from sparknet_tpu import models
from sparknet_tpu.graph.net import Net
from sparknet_tpu.proto import load_solver_prototxt_with_net
from sparknet_tpu.proto.caffe_pb import NetState, Phase
from sparknet_tpu.solvers import Solver

CONFIG = os.path.join(REPO, "benchmark", "configs", "laguna_xs_2.json")
TINY = os.path.join(REPO, "benchmark", "tests", "data", "laguna_tiny.json")
SOLVER = ('type: "Adam"\nbase_lr: 0.0003\nmomentum: 0.9\nmomentum2: 0.95\n'
          'delta: 1e-8\nclip_gradients: 1.0\nlr_policy: "fixed"\n')

# the catalog row `Laguna-XS.2` (poolside/Laguna-XS.2 config.json)
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 8192, "head_dim": 128,
    "num_attention_heads": 48, "num_key_value_heads": 8,
    "num_experts_per_tok": 8, "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512, "sliding_window": 512,
    "rms_norm_eps": 1e-6, "moe_routed_scaling_factor": 2.5,
    "max_position_embeddings": 262144, "partial_rotary_factor": 0.5,
}
CUT = {"num_hidden_layers": (40, 5), "num_experts": (256, 32),
       "vocab_size": (100352, 12544)}


def load(path):
    with open(path) as f:
        return json.load(f)


def tiny(sequences=2, positions=16):
    cfg = load(TINY)
    return cfg, models.laguna(sequences, 1, seq_len=positions,
                              **cfg["builder_args"])


def train(net_param):
    return net_param.filtered(NetState(Phase.TRAIN))


def test_configuration_holds_the_published_widths():
    cfg = load(CONFIG)
    for key, want in PUBLISHED.items():
        assert cfg[key] == want, key
    assert sorted(cfg["reduced"]) == sorted(CUT)
    for key, (published, here) in CUT.items():
        assert cfg["published"][key] == published and cfg[key] == here
    full, sliding = (cfg["rope_parameters"][k] for k in
                     ("full_attention", "sliding_attention"))
    assert (full["rope_theta"], full["factor"], full["beta_fast"],
            full["beta_slow"], full["original_max_position_embeddings"],
            full["partial_rotary_factor"]) == (500000, 64, 64, 1, 4096, 0.5)
    assert full["attention_factor"] == pytest.approx(1.4158883)
    assert (sliding["rope_theta"], sliding["partial_rotary_factor"]) == (
        10000, 1)
    assert cfg["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert cfg["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["deployment"]["chips_sharing_a_layer"] == 8
    assert {"gate", "router", "weights", "qk_norm", "rotary", "loss",
            "sequence"} <= set(cfg["assumed"])


def test_builder_builds_what_the_configuration_states():
    """The parameter arithmetic of the cut, layer by layer."""
    cfg = load(CONFIG)
    net = train(models.laguna(4, 1, **cfg["builder_args"]))
    lm_flops.check_as_built(cfg, net)
    by_name = {lp.name: lm_flops.parameters(lp, g)
               for lp, g in lm_flops.layers(net)}
    h = 2048
    assert by_name["embed"] == by_name["lm_loss"] == 25_690_112
    assert by_name["L0/attn"] == by_name["L4/attn"] == 29_458_432
    assert by_name["L1/attn"] == 37_879_808
    assert by_name["L0/mlp"] == 50_331_648
    assert by_name["L1/moe"] == 524_288 + 3_145_728 + 100_663_296
    assert cfg["as_built"]["parameters"] == sum(by_name.values()) \
        == 691_623_936
    assert by_name["final_norm"] == h


def test_published_depth_is_the_builders_default():
    """The builder's defaults are the published model: 40 layers in the
    period full, sliding, sliding, sliding with 48 and 64 query heads, a
    dense MLP first and 256 experts after, some 33 billion parameters."""
    net = train(models.laguna(1, 1))
    rows = {r[0]: r for r in lm_flops.as_built(net)["layers"]}
    assert [rows[f"L{i}/attn"][3] for i in range(8)] == [
        48, 64, 64, 64, 48, 64, 64, 64]
    assert [rows[f"L{i}/attn"][6] for i in range(4)] == [0, 512, 512, 512]
    assert [rows[f"L{i}/attn"][7] for i in range(2)] == [64, 128]
    assert rows["L0/mlp"][1] == "GatedMLP" and "L0/moe" not in rows
    assert rows["L39/moe"][3:] == [512, 256, 8, 256, 512]
    assert 33.0e9 < lm_flops.as_built(net)["parameters"] < 33.8e9


def adam_reference(params, grads_fn, batches, steps, lr_mults):
    """Caffe's Adam with global-norm clipping at 1 and a rate multiplier
    a leaf, in numpy float64."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    p = [np.asarray(x, np.float64) for x in leaves]
    m = [np.zeros_like(x) for x in p]
    v = [np.zeros_like(x) for x in p]
    losses = []
    for t in range(steps):
        loss, grads = grads_fn(jax.tree_util.tree_unflatten(
            tree, [jnp.asarray(x, jnp.float32) for x in p]), batches[t])
        g = [np.asarray(x, np.float64)
             for x in jax.tree_util.tree_leaves(grads)]
        norm = np.sqrt(sum((x ** 2).sum() for x in g))
        g = [x * min(1.0, 1.0 / max(norm, 1e-12)) for x in g]
        m = [0.9 * a + 0.1 * x for a, x in zip(m, g)]
        v = [0.95 * a + 0.05 * x * x for a, x in zip(v, g)]
        rate = 3e-4 * np.sqrt(1 - 0.95 ** (t + 1)) / (1 - 0.9 ** (t + 1))
        p = [a - r * rate * b / (np.sqrt(c) + 1e-8)
             for a, b, c, r in zip(p, m, v, lr_mults)]
        losses.append(float(loss))
    return losses, jax.tree_util.tree_unflatten(tree, p)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_adam_steps_against_the_reference(dtype):
    cfg, net_param = tiny()
    sp = load_solver_prototxt_with_net(SOLVER, net_param)
    solver = Solver(sp, seed=5, compute_dtype=(
        None if dtype == "float32" else jnp.bfloat16))
    start = jax.tree_util.tree_map(np.asarray, solver.params)
    batches = [{"tokens": check_lm.seeded_tokens(
        jax.random.PRNGKey(20 + i), 2, 16, 64)} for i in range(3)]
    solver.set_train_data(iter(batches))
    got_losses = []
    for _ in range(3):
        got_losses.append(solver.step(1))
    m = ref.model(cfg)
    grads_fn = ref.highest(jax.value_and_grad(
        lambda p, b: ref.loss(p, b["tokens"], m)))
    # the tiny configuration freezes its routers as the cell's does
    lr_mults = jax.tree_util.tree_leaves(
        {k: [0.0 if k.endswith("/moe") and i == 0 else 1.0
             for i in range(len(v))] for k, v in start.items()})
    want_losses, want = adam_reference(start, grads_fn, batches, 3,
                                       lr_mults)
    for i in (1, 2, 3, 4):
        assert np.array_equal(np.asarray(solver.params[f"L{i}/moe"][0]),
                              start[f"L{i}/moe"][0])
    # float32 agrees to rounding; in bfloat16 every blob and operand is
    # rounded to 8 bits of mantissa, and Adam's first steps are
    # rate * sign(gradient), so a weight whose tiny gradient changes sign
    # under rounding moves the other way: the update is compared in norm
    loss_tol, update_tol = ((1e-5, 2e-3) if dtype == "float32"
                            else (3e-2, 0.5))
    np.testing.assert_allclose(got_losses, want_losses, rtol=loss_tol)
    for name in start:
        for a, b, w in zip(start[name], solver.params[name], want[name]):
            moved = np.asarray(w) - a
            err = np.linalg.norm(np.asarray(b, np.float64) - w)
            assert err <= update_tol * max(np.linalg.norm(moved), 1e-12), name
    assert solver.iter == 3


def test_gradients_through_the_net_against_the_reference():
    cfg, net_param = tiny(2, 24)
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(3))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(4), 2, 24, 64)
    loss, grads = jax.value_and_grad(
        lambda p: net.apply(p, {"tokens": tokens}, train=True).loss)(params)
    m = ref.model(cfg)
    want_loss, want = ref.highest(jax.value_and_grad(
        lambda p: ref.loss(p, tokens, m)))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    for name in params:
        for g, w in zip(grads[name], want[name]):
            scale = float(jnp.abs(w).max())
            assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-9, name


def test_check_lm_holds_the_tiny_net_to_the_reference():
    """The comparison that decides ``correct``, as the driver calls it."""
    cfg, net_param = tiny(1, 32)
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(6))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(7), 1, 32, 64)
    leaves = check_lm.grad_leaves(train(net_param))
    assert leaves == [("L1/moe", 0), ("L1/moe", 3), ("L1/attn", 3),
                      ("L0/attn", 0)]
    logits, loss = check_lm.system_forward(train(net_param), params, tokens)
    grads = check_lm.system_grads(train(net_param), params, tokens, leaves)
    verdict = check_lm.compare("float32", cfg, params, tokens, tokens,
                               leaves, logits, loss, grads)
    assert verdict["ok"], verdict
    # the reference with float8 operands is refused, by the loss and the
    # gradients alike
    m = ref.model(cfg)
    f8 = jnp.float8_e4m3fn
    low = check_lm.errors(
        ref.highest(lambda p, t: ref.logits(p, t, m, f8))(params, tokens[0]),
        ref.highest(lambda p, t: ref.loss(p, t, m, f8))(params, tokens),
        check_lm.reference_grads(params, tokens, leaves, m, f8),
        logits, loss, grads)
    tol = check_lm.TOLERANCE["bfloat16"]
    assert low["loss_abs_err"] > tol["loss"] or any(
        e > t for e, t in zip(low["grads_rel_err"], tol["grads"]))


def test_types_check_reads_token_inputs():
    _, net_param = tiny()
    params = Net(net_param, NetState(Phase.TRAIN)).init(jax.random.PRNGKey(0))
    for dtype, cd, ok in (("bfloat16", jnp.bfloat16, True),
                          ("float32", jnp.bfloat16, False),
                          ("float32", None, True)):
        net = Net(net_param, NetState(Phase.TRAIN), compute_dtype=cd)
        held = check_lm.held_precision(dtype, net, params, 2, 16)
        assert held["ok"] is ok, (dtype, cd, held)
    # the router's scores are float32 whatever the compute dtype
    assert "float32" in check_lm.held_precision(
        "bfloat16", Net(net_param, NetState(Phase.TRAIN),
                        compute_dtype=jnp.bfloat16), params, 2,
        16)["products_fed"]


def test_load_and_lowering_counters():
    from sparknet_tpu.ops.sequence import moe_load
    from sparknet_tpu.utils import telemetry
    _, net_param = tiny(2, 16)
    net = Net(net_param, NetState(Phase.TRAIN))
    params = net.init(jax.random.PRNGKey(1))
    tokens = check_lm.seeded_tokens(jax.random.PRNGKey(2), 2, 16, 64)
    before = telemetry.get_registry().snapshot()

    def total(snap, name):
        return sum(s["value"] for s in snap.get(name, {}).get("samples", []))

    load = moe_load(net, params, {"tokens": tokens})
    assert set(load) == {"L1/moe", "L2/moe", "L3/moe", "L4/moe"}
    assert all(len(v["rows"]) == 4 and v["dropped"] == 0
               for v in load.values())
    after = telemetry.get_registry().snapshot()
    rows = sum(sum(v["rows"]) for v in load.values())
    assert total(after, "moe_rows_total") - total(
        before, "moe_rows_total") == rows
    assert total(after, "moe_dropped_total") == total(
        before, "moe_dropped_total")
    net.apply(params, {"tokens": tokens}, train=True)
    last = telemetry.get_registry().snapshot()
    for name, path in (("attn_lowering_total", "xla"),
                       ("moe_lowering_total", "ragged_dot")):
        assert total(last, name) > total(after, name)
        # the registry is the process's: only what this apply counted
        was = {tuple(sorted(s["labels"].items())): s["value"]
               for s in after.get(name, {}).get("samples", [])}
        new = [s["labels"] for s in last[name]["samples"]
               if s["value"] > was.get(tuple(sorted(s["labels"].items())), 0)]
        assert {labels["path"] for labels in new} == {path}
        if name == "moe_lowering_total":
            # tiles are the kernels'; ragged_dot has none to report
            assert new == [{"path": "ragged_dot"}]
