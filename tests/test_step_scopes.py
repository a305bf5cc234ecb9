"""Every operation of the compiled step and of the compiled round is traced
under a scope that names its layer or its phase: ``L[<layer>]`` from
``graph/net.py`` with the sub-scope ``cast`` inside, ``L[step.input]``,
``L[step.grads]``, ``L[step.update]`` from ``solvers/step.py``, ``L[round.average]``
and ``L[round.sync]`` from ``parallel/trainer.py``; JAX's own
``rematted_computation`` marks what ``jax.checkpoint`` runs again.  The
scopes are debug information and nothing else: without locations the lowered
text is the same with every scope taken out.

Read from the optimized HLO of the CPU's compiler, where an instruction's
``op_name`` is what a profiler trace calls its ``tf_op``.  Instructions the
compiler makes itself carry no ``op_name`` at all (on the CPU: the float32
legalisation of bfloat16 products, broadcasts, copies) and the reducers of a
``reduce`` carry a bare primitive name; what the program traced begins
``jit(``.
"""

import contextlib
import functools
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import models
from sparknet_tpu.graph import net as graph_net
from sparknet_tpu.ops.augment import AugmentSpec
from sparknet_tpu.parallel import DistributedTrainer, TrainerConfig, make_mesh
from sparknet_tpu.parallel import trainer as trainer_mod
from sparknet_tpu.proto import load_net_prototxt, load_solver_prototxt_with_net
from sparknet_tpu.solvers import Solver, step as step_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# CaffeNet's shape at a toy size: a conv..LRN chain (vertical fusion), three
# sibling 1x1 convolutions on its output (horizontal fusion), a classifier
CONVNET = """
input: "data"
input_shape { dim: 4 dim: 3 dim: 12 dim: 12 }
input: "label"
input_shape { dim: 4 }
layer { name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  convolution_param { num_output: 8 kernel_size: 3
    weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "relu1" type: "ReLU" bottom: "conv1" top: "conv1" }
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
  pooling_param { pool: MAX kernel_size: 2 stride: 2 } }
layer { name: "norm1" type: "LRN" bottom: "pool1" top: "norm1"
  lrn_param { local_size: 3 alpha: 0.0001 beta: 0.75 } }
layer { name: "a" type: "Convolution" bottom: "norm1" top: "a"
  convolution_param { num_output: 3 kernel_size: 1
    weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "b" type: "Convolution" bottom: "norm1" top: "b"
  convolution_param { num_output: 4 kernel_size: 1
    weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "c" type: "Convolution" bottom: "norm1" top: "c"
  convolution_param { num_output: 2 kernel_size: 1
    weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "cat" type: "Concat" bottom: "a" bottom: "b" bottom: "c"
  top: "cat" }
layer { name: "ip" type: "InnerProduct" bottom: "cat" top: "ip"
  inner_product_param { num_output: 5
    weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip" bottom: "label" }
"""
SGD = 'base_lr: 0.01\nmomentum: 0.9\nlr_policy: "fixed"\n'
ADAM = ('type: "Adam"\nbase_lr: 0.0003\nmomentum: 0.9\nmomentum2: 0.95\n'
        'delta: 1e-8\nlr_policy: "fixed"\n')

_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .+? ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NOT_OPERATIONS = {"parameter", "constant", "tuple", "get-tuple-element"}
# a path that ends in an HLO instruction's name (``shard_map/broadcast.42``)
# and not in a JAX primitive's: the partitioner's copy of a constant the
# body closes over (the learning-rate multipliers)
_COMPILERS_OWN = re.compile(r"/[a-z\-]+\.\d+$")
# ``lax.scan``'s own: the loop, its counter and bound, the slice of this
# turn's inputs and the write of its outputs.  A scan stays under no scope
# of the program's: the TPU's compiler names what it makes inside a loop
# after the loop, and under a scope that would read as the program's.
_SCANS_OWN = re.compile(r"^[^\[]*/while(/body/(closed_call|dynamic_slice|"
                        r"dynamic_update_slice|add)|/cond/lt)?$")


def traced_paths(hlo: str) -> list[str]:
    """The ``op_name`` of every instruction the program traced, other than
    parameters, constants, tuples and their elements."""
    paths = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(1) in _NOT_OPERATIONS:
            continue
        name = _OP_NAME.search(line)
        if (name and name.group(1).startswith("jit(")
                and not _COMPILERS_OWN.search(name.group(1))
                and not _SCANS_OWN.match(name.group(1))):
            paths.append(name.group(1))
    return paths


def innermost(path: str) -> str | None:
    hits = re.findall(r"L\[([^\]]+)\]", path)
    return hits[-1] if hits else None


# -- the three programs -------------------------------------------------------

def conv_solver(solver_text: str, *, dtype=jnp.bfloat16, augment=True):
    sp = load_solver_prototxt_with_net(solver_text, load_net_prototxt(CONVNET))
    solver = Solver(sp, seed=0, compute_dtype=dtype)
    shape = (sp.iter_size, 4, 3, 12, 12)
    if augment:
        # raw uint8 records two pixels wider than the crop
        solver.set_augment(AugmentSpec(
            crop=12, mirror=True, mean=np.full((3, 1, 1), 100, np.float32)))
        shape = (sp.iter_size, 4, 3, 14, 14)
    batch = {"data": jnp.zeros(shape, jnp.uint8 if augment else jnp.float32),
             "label": jnp.zeros((sp.iter_size, 4), jnp.float32)}
    return solver, batch


def token_solver():
    """Attention, experts, a short convolution, ``jax.checkpoint`` in every
    layer, Adam and ``clip_gradients``, at toy widths."""
    with open(os.path.join(REPO, "benchmark", "tests", "data",
                           "lfm2_tiny.json")) as f:
        args = json.load(f)["builder_args"]
    sp = load_solver_prototxt_with_net(
        ADAM + "clip_gradients: 1.0\n", models.lfm2(2, 1, seq_len=16, **args))
    solver = Solver(sp, seed=0, compute_dtype=jnp.bfloat16)
    return solver, {"tokens": jnp.zeros((1, 2, 16), jnp.int32)}


def laguna_solver():
    """Laguna's builder at toy widths: expert layers with a shared expert
    and no selection bias, where ``token_solver``'s have a bias and none."""
    with open(os.path.join(REPO, "benchmark", "tests", "data",
                           "laguna_tiny.json")) as f:
        args = json.load(f)["builder_args"]
    sp = load_solver_prototxt_with_net(
        ADAM + "clip_gradients: 1.0\n",
        models.laguna(2, 1, seq_len=16, **args))
    solver = Solver(sp, seed=0, compute_dtype=jnp.bfloat16)
    return solver, {"tokens": jnp.zeros((1, 2, 16), jnp.int32)}


def lower_step(solver, batch):
    return solver._step.lower(solver.params, solver.state, 0, batch,
                              jax.random.PRNGKey(0))


def lower_round(strategy: str, tau: int = 2):
    sp = load_solver_prototxt_with_net(
        SGD + "weight_decay: 0.0005\n", load_net_prototxt(CONVNET))
    trainer = DistributedTrainer(
        sp, mesh=make_mesh(2), config=TrainerConfig(strategy=strategy,
                                                    tau=tau), seed=0)
    batches = {"data": jnp.zeros((tau, 8, 3, 12, 12), jnp.float32),
               "label": jnp.zeros((tau, 8), jnp.float32)}
    return trainer._round.lower(
        trainer.params, trainer.state, jnp.asarray(0), batches,
        jax.random.PRNGKey(0), jnp.asarray(1.0, jnp.float32))


PROGRAMS = {
    # bfloat16, SGD with decay, augmentation in the step
    "convnet": lambda: lower_step(*conv_solver(SGD + "weight_decay: 0.0005\n")),
    # float32, no decay, no clipping, one micro-batch: no gradient
    # preparation and no cast
    "convnet_plain": lambda: lower_step(
        *conv_solver(SGD, dtype=None, augment=False)),
    "convnet_iter2": lambda: lower_step(
        *conv_solver(SGD + "iter_size: 2\n", augment=False)),
    "tokens": lambda: lower_step(*token_solver()),
    "round_local_sgd": lambda: lower_round("local_sgd"),
    "round_sync": lambda: lower_round("sync"),
}
# a second token step for the expert layers' test alone: what the tests over
# ``PROGRAMS`` expect of a token step is written for the first
LAGUNA = {"tokens_laguna": lambda: lower_step(*laguna_solver())}


@functools.cache
def lowered(program: str):
    return (PROGRAMS | LAGUNA)[program]()


@functools.cache
def compiled_text(program: str) -> str:
    return lowered(program).compile().as_text()


@functools.cache
def paths_of(program: str) -> list[str]:
    return traced_paths(compiled_text(program))


# JAX moves what a scan's body computes from the scan's constants alone out
# of the loop when it differentiates the scan, and such an operation keeps
# only the name stack inside the body, without the ``L[<layer>]`` round the
# scan: in the sequence layers (one sequence at a time under ``lax.map``) a
# norm's weights and a short convolution's taps cast and sliced once, and
# the causal mask of the masked-scores attention (a kernel on a TPU).
HOISTED_BY_JAX = {
    "jit(local_update)/convert_element_type",
    "jit(local_update)/conv_mix/convert_element_type",
    "jit(local_update)/conv_mix/slice",
    "jit(local_update)/attn_core/le",
    "jit(local_update)/attn_core/jit(_where)/broadcast_in_dim",
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_traced_instruction_carries_a_scope(program):
    paths = paths_of(program)
    assert len(paths) > 50
    bare = [p for p in paths if innermost(p) is None]
    if program == "tokens":
        assert len(bare) < 0.01 * len(paths)
        bare = [p for p in bare if p not in HOISTED_BY_JAX]
    assert bare == []


CONV_CHAIN = "conv1+relu1+pool1+norm1"
# program -> the scopes its operations must be traced under, and must not.
# Read from the lowered text with its locations: what the program traced,
# before the compiler folds a division by ``iter_size`` into its neighbour
# or the slice of a leading axis of one into nothing.
OCCURS = {
    "convnet": (
        {"step.input", "augment", "step.grads", "step.update", CONV_CHAIN,
         "a+b+c", "cat", "ip", "loss"},
        {"round.average", "round.sync", "conv1", "a", "b", "c"}),
    "convnet_plain": (
        {"step.input", "step.update", CONV_CHAIN, "a+b+c"},
        {"step.grads", "augment", "round.average", "round.sync"}),
    "convnet_iter2": (
        {"step.input", "step.grads", "step.update"},
        {"round.average", "round.sync"}),
    "tokens": (
        {"step.input", "step.grads", "step.update", "L2/attn", "L2/moe",
         "L3/conv", "L0/mlp", "lm_loss", "embed"},
        {"round.average", "round.sync"}),
    "round_local_sgd": (
        {"step.input", "step.grads", "step.update", "round.average",
         CONV_CHAIN},
        {"round.sync"}),
    "round_sync": (
        {"step.input", "step.grads", "step.update", "round.sync", CONV_CHAIN},
        {"round.average"}),
}


@pytest.mark.parametrize("program", sorted(OCCURS))
def test_the_phases_occur_where_the_configuration_has_them(program):
    there, absent = OCCURS[program]
    scopes = set(re.findall(r"L\[([^\]]+)\]",
                            lowered(program).as_text(debug_info=True)))
    assert there <= scopes
    assert not absent & scopes
    # and in what the compiler leaves of them
    compiled = {innermost(p) for p in paths_of(program)}
    assert compiled <= scopes | {None}
    assert there - {"step.input", "step.grads"} <= compiled


@pytest.mark.parametrize("program, casts, recomputes", [
    ("convnet", True, False), ("convnet_plain", False, False),
    ("tokens", True, True), ("round_local_sgd", False, False)])
def test_casts_and_recomputation_are_named(program, casts, recomputes):
    paths = paths_of(program)
    cast = [p for p in paths if "/cast/" in p]
    again = [p for p in paths if "rematted_computation" in p]
    assert bool(cast) == casts and bool(again) == recomputes
    # a cast sits inside its layer's scope, forward and backward
    assert all(re.search(r"L\[[^\]]+\]\)*/cast/", p) for p in cast)
    if casts:
        assert any("transpose(jvp(L[" in p for p in cast)
        assert any("transpose(" not in p for p in cast)
    # what runs again runs in the backward pass of the layer it belongs to
    assert all(re.search(r"transpose\(jvp\(L\[[^\]]+\]\)\)/.*"
                         r"rematted_computation", p) for p in again)
    if recomputes:
        layers = {innermost(p) for p in again}
        assert {"L2/attn", "L2/moe", "L3/conv", "L0/mlp", "lm_loss"} <= layers
        assert not layers & {"step.grads", "step.update", "embed"}


@pytest.mark.parametrize("program", ["convnet_iter2", "round_local_sgd",
                                     "round_sync"])
def test_a_scan_is_under_no_scope_and_what_it_holds_is(program):
    """The scan over micro-batches (``iter_size`` 2) and the round's scan
    over steps open no scope round themselves; every operation the program
    traces inside one has its own."""
    hlo = lowered(program).compile().as_text()
    loops = [m.group(1) for line in hlo.splitlines()
             if _INSTR.match(line) and _INSTR.match(line).group(1) == "while"
             for m in [_OP_NAME.search(line)] if m]
    assert loops and all("L[" not in p for p in loops)
    inside = [p for p in paths_of(program) if "/while/body/" in p]
    assert {innermost(p) for p in inside} >= {CONV_CHAIN, "a+b+c", "loss"}
    assert None not in {innermost(p) for p in inside}


@pytest.mark.parametrize("rule", ["SGD", "Nesterov", "AdaGrad", "RMSProp",
                                  "AdaDelta", "Adam"])
def test_every_rule_updates_under_the_update_scope(rule):
    text = f'type: "{rule}"\nbase_lr: 0.01\nlr_policy: "step"\n' \
           'gamma: 0.5\nstepsize: 10\n'
    if rule in ("SGD", "Nesterov", "AdaDelta", "Adam"):
        text += "momentum: 0.9\n"
    if rule == "AdaDelta":
        text += "delta: 1e-6\n"
    if rule == "RMSProp":
        text += "rms_decay: 0.98\n"
    solver, batch = conv_solver(text, dtype=None, augment=False)
    assert solver.rule.name == rule.upper()
    paths = traced_paths(lower_step(solver, batch).compile().as_text())
    assert [p for p in paths if innermost(p) is None] == []
    update = [p for p in paths if innermost(p) == "step.update"]
    assert len(update) >= 5                 # the rate and every leaf
    assert all("transpose(" not in p for p in update)
    assert not any(innermost(p) == "step.grads" for p in paths)


# -- the scopes add nothing but locations -------------------------------------

@pytest.mark.parametrize("program", ["convnet", "tokens", "round_local_sgd"])
def test_without_locations_the_lowered_text_is_the_same_without_scopes(
        program, monkeypatch):
    scoped = lowered(program)
    for scope in ("L[step.update]", "L[step.input]"):
        assert scope not in scoped.as_text()
        assert scope in scoped.as_text(debug_info=True)
    for module in (graph_net, step_mod, trainer_mod):
        assert module.jax is jax
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = PROGRAMS[program]()
    assert "L[" not in bare.as_text(debug_info=True).replace("L[augment]", "")
    assert bare.as_text() == scoped.as_text()


# -- what an expert layer moves, and how often ----------------------------------

@functools.cache
def lowered_text():
    spec = importlib.util.spec_from_file_location(
        "lowered_text", os.path.join(REPO, "tools", "lowered_text.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("program", ["tokens", "tokens_laguna"])
def test_an_expert_layer_routes_once_and_moves_rows(program):
    """``tools/lowered_text.py``'s ``moe_moves`` on the compiled step: under
    ``moe_route`` no scatter moves a scalar at a time, the backward pass
    makes no route again (no ``sort`` and no ``top_k`` in a recomputed
    forward), and a layer adds rows of the hidden width into place twice a
    step: the experts' outputs into their tokens, the rows' gradient into
    the input's."""
    moves = lowered_text().moe_moves(compiled_text(program))
    layers = sorted({m["layer"] for m in moves})
    assert len(layers) == 4 and all(n.endswith("/moe") for n in layers)
    for name in layers:
        mine = [m for m in moves if m["layer"] == name
                and m["scope"] == "moe_route"]
        scatters = [m for m in mine if m["opcode"] == "scatter"]
        assert [m for m in scatters if not m["updates"]] == []
        assert [m for m in mine if m["pass"] == "remat"
                and m["opcode"] in ("sort", "topk")] == []
        # the route is there, once: its top_k and its one sort
        assert [m["opcode"] for m in mine if m["pass"] == "fwd"
                and m["opcode"] in ("sort", "topk")
                and m["primitive"] in ("sort", "top_k")] == ["topk", "sort"]
        rows = [m for m in scatters if m["updates"] == [32]]    # hidden
        assert sorted(m["pass"] for m in rows) == ["bwd", "fwd"]
        # what else is scattered is the weights' way back: top_k at a time
        assert all(m["pass"] == "bwd" for m in scatters if m not in rows)


def test_moe_moves_reads_a_scalar_scatter_and_a_recomputed_sort():
    """The reader on text of the form the expert layer had: it finds the
    count's scatter of scalars and the sort inside the recomputation."""
    hlo = """
ENTRY %main (p: s32[64]) -> s32[9] {
  %p = s32[64]{0} parameter(0)
  %ones = s32[64]{0} constant({...})
  %zeros = s32[9]{0} constant({...})
  %scatter-add.1 = s32[9]{0} scatter(%zeros, %p, %ones), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%add, metadata={op_name="jit(step)/transpose(jvp(L[L1/moe]))/checkpoint/rematted_computation/moe_route/scatter-add"}
  %sort.2 = (s32[64]{0}, s32[64]{0}) sort(%p, %ones), dimensions={0}, is_stable=true, to_apply=%lt, metadata={op_name="jit(step)/transpose(jvp(L[L1/moe]))/checkpoint/rematted_computation/moe_route/jit(argsort)/sort"}
  %gather.3 = bf16[64,32]{1,0} gather(%x, %p), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,32}, metadata={op_name="jit(step)/jvp(L[L1/moe])/moe_route/gather"}
  %sort.4 = (s32[64]{0}, s32[64]{0}) sort(%p, %ones), dimensions={0}, to_apply=%lt, metadata={op_name="jit(step)/jvp(L[L2/attn])/sort"}
}
"""
    moves = lowered_text().moe_moves(hlo)
    assert [(m["layer"], m["pass"], m["opcode"], m["primitive"],
             m["updates"]) for m in moves] == [
        ("L1/moe", "remat", "scatter", "scatter-add", []),
        ("L1/moe", "remat", "sort", "sort", None),
        ("L1/moe", "fwd", "gather", "gather", [32])]

