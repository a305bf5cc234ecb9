"""Is it still the same program?  Lower it and hash the text.

    python tools/lowered_text.py --root <checkout> --set nets|cells|edges
                                 [--devices real|described] [--cell NAME ...]
                                 [--out DIR]

Imports ``sparknet_tpu`` and ``benchmark`` from ``--root`` (another checkout
of this repository, e.g. ``git archive <parent>`` unpacked beside this one),
lowers the programs of the set with shapes in place of arrays, and prints one
JSON line ``{"root": ..., "backend": ..., "fuse_plan": {program: id},
"hashes": {program: sha1[:12]}}``, each hash
of the StableHLO text without source locations (``Lowered.as_text()``: a
scope's name and a line number live only in the locations; a Pallas kernel's
body is Mosaic bytecode that carries the call stack's paths and lines, so it
is parsed and printed again without them).  Two checkouts
run the same program where the lines agree; ``--out`` keeps the texts, for a
``diff`` where they do not.  One process a root: the modules are the root's.

- ``nets``: ``jax.grad`` of the train net's loss for CaffeNet (float32 and
  bfloat16), GoogLeNet and AlexNet (float32) at batch 2, on whatever backend
  JAX has (here the CPU).
- ``cells``: what the benchmark's cells run, built as their drivers build it
  (``benchmark/rehearse.py`` is the model): ``Solver._step`` for
  ``caffenet_train_resident`` (bfloat16, 1,024) and
  ``googlenet_train_resident`` (bfloat16, 256), the trainer's round for
  ``caffenet_rounds_x4`` (float32, 512 a chip, tau 10), and the token cells'
  steps (``laguna_xs_2_train_8k``, ``lfm2_24b_a2b_train_8k``,
  ``deepseek_v2_lite_train_8k``, ``nemotron_3_nano_train_8k``: bfloat16, 4
  sequences of 8,192 ids) from the shapes of their parameters and Adam's
  state alone, 11, 7.5, 10.2 and 10.7 GB that are never made.  ``--devices real``
  lowers for the chips JAX holds and leaves out a cell that needs more;
  ``described`` lowers for a v5e:2x2 that is described and not attached, with
  trace-time backend checks steered to the chip's branch, and needs no chip.

- ``edges``: the same programs as ``cells``, compiled (``--devices
  described``: by the TPU's compiler for the described chip, about a minute a
  cell), and read for layout copies at the edges of the Pallas kernels (the
  LRN epilogue's ``relu_lrn_fwd``/``relu_lrn_bwd``, the attention's
  ``splash_mqa_fwd_residuals``/``splash_mqa_dkv_no_residuals`` and, in a
  layer whose backward pass is split, ``splash_mqa_dq_no_residuals``: a
  layer that takes the fused backward kernel has no ``dq`` call, its
  ``dkv`` call makes ``dq`` too; the state-space scan's ``ssd_scan_fwd``
  and ``ssd_scan_bwd``; the mixer's causal convolution's ``ssm_conv_fwd``
  and ``ssm_conv_bwd``): a ``copy`` or ``transpose`` that feeds such a
  custom call, reads its result, or is attributed to its
  ``pallas_call``.  Prints ``{"edges": {program: {"kernel_calls": {...},
  "copies": [...], "copy_bytes": n, "written": {class: bytes},
  "conv_copies": [...], "dq_sums": [...]}}}`` (``written``: what the
  program's operations write by class, see ``written_bytes``;
  ``conv_copies``: the copies inside a short convolution layer's scope,
  which has no kernel whose edges could be read; ``dq_sums``: the sums over
  key/value blocks of the partial ``dq`` a fused backward kernel writes,
  JAX's own inside its wrapper of the kernels and no copy) and exits 1 if
  any program holds a copy at a kernel's edge; ``--out`` keeps the compiled
  text.

Nothing runs, and for ``nets`` and ``cells`` nothing is compiled: equal text
is the whole criterion there.
"""

from __future__ import annotations

import argparse
import base64
import collections
import hashlib
import json
import os
import re
import sys


def without_kernel_locations(text: str) -> str:
    """``text`` with every ``tpu_custom_call`` body (base64 of MLIR bytecode)
    replaced by the kernel's assembly without debug information."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True       # stable_mosaic.*

    def asm(m: re.Match) -> str:
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(2)))
            return m.group(1) + json.dumps(module.operation.get_asm(
                enable_debug_info=False))[1:-1]

    return re.sub(r'(body\\22: \\22)([A-Za-z0-9+/=]+)', asm, text)


def net_texts() -> dict[str, tuple[str, str]]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.graph import Net
    from sparknet_tpu.proto import NetState, Phase

    texts = {}
    for model, dtype in (("caffenet", None), ("caffenet", jnp.bfloat16),
                         ("googlenet", None), ("alexnet", None)):
        net = Net(getattr(models, model)(2, 2), NetState(Phase.TRAIN),
                  compute_dtype=dtype)
        key = jax.random.PRNGKey(0)
        params = jax.eval_shape(net.init, key)
        ins = {b: jax.ShapeDtypeStruct(s, np.float32)
               for b, s in net.input_blobs.items()}

        def loss(p, ins, rng, net=net):
            return net.apply(p, ins, rng=rng).loss

        name = f"{model}_{'f32' if dtype is None else 'bf16'}_grad"
        texts[name] = (jax.jit(jax.grad(loss)).lower(params, ins,
                                                     key).as_text(),
                       net.fuse_plan_id())
    return texts


CELLS = ("caffenet_train_resident", "googlenet_train_resident",
         "caffenet_rounds_x4", "laguna_xs_2_train_8k",
         "lfm2_24b_a2b_train_8k", "deepseek_v2_lite_train_8k",
         "nemotron_3_nano_train_8k")
# a layer of split backward kernels calls all three splash kernels, a fused
# one the first two: its dkv kernel makes dq as well
KERNELS = ("relu_lrn_fwd", "relu_lrn_bwd", "splash_mqa_fwd_residuals",
           "splash_mqa_dkv_no_residuals", "splash_mqa_dq_no_residuals",
           "ssd_scan_fwd", "ssd_scan_bwd", "ssm_conv_fwd", "ssm_conv_bwd")


def cell_lowered(described: bool, names) -> dict[str, tuple[object, str]]:
    """``{program: (jax.stages.Lowered, fuse plan id)}`` of the cells."""
    import jax
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from benchmark.lib import harness

    if described:
        from jax.experimental import topologies
        jax.default_backend = lambda: "tpu"
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    else:
        devices = jax.devices()

    def struct(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                           sharding=sharding), tree)

    spec = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
    lowereds = {}
    for name in names:
        cell = harness.resolve_cell(spec, name, seed=0)
        if cell.chips > len(devices):
            print(f"{name}: needs {cell.chips} devices, {len(devices)} "
                  f"here: left out", file=sys.stderr)
            continue
        driver = harness.load_driver(cell.mix).Driver(cell)
        if hasattr(driver, "net_for"):      # a token driver, whatever model
            lowereds[f"{name}:Solver._step"] = (
                _token_step_lowered(driver, devices[0], struct), "off")
            continue
        raw = driver.raw_shape()
        if cell.mix["driver"] == "solver_steps":
            one = SingleDeviceSharding(devices[0])
            solver = driver.make_solver()
            b = driver.batch
            batch = {"data": jax.ShapeDtypeStruct((1, b, *raw), np.uint8,
                                                  sharding=one),
                     "label": jax.ShapeDtypeStruct((1, b), np.float32,
                                                   sharding=one)}
            lowered = solver._step.lower(
                struct(solver.params, one), struct(solver.state, one), 0,
                batch, struct(jax.random.PRNGKey(0), one))
            lowereds[f"{name}:Solver._step"] = (
                lowered, solver.train_net.fuse_plan_id())
            continue
        n = cell.chips
        trainer, _ = driver.make_trainer(n)
        mesh = Mesh(np.asarray(devices[:n]).reshape(n, 1),
                    trainer.mesh.axis_names)
        rep = NamedSharding(mesh, P())
        stacked = NamedSharding(mesh, trainer._state_tier()[1])
        feed = NamedSharding(mesh, trainer.input_sharding.spec)
        params, state = trainer.params, trainer.state
        # closed over by the round: as host arrays they are constants of
        # the program, whatever devices the trainer was built on
        trainer._lr_mults = jax.tree_util.tree_map(np.asarray,
                                                   trainer._lr_mults)
        trainer._decay_mults = jax.tree_util.tree_map(np.asarray,
                                                      trainer._decay_mults)
        trainer.mesh = mesh
        gb, rows = driver.batch * n, driver.tau * trainer.sp.iter_size
        batches = {"data": jax.ShapeDtypeStruct((rows, gb, *raw), np.uint8,
                                                sharding=feed),
                   "label": jax.ShapeDtypeStruct((rows, gb), np.float32,
                                                 sharding=feed)}
        lowered = trainer._build_round().lower(
            struct(params, rep), struct(state, stacked),
            jax.ShapeDtypeStruct((), np.int32, sharding=rep), batches,
            struct(jax.random.PRNGKey(0), rep),
            jax.ShapeDtypeStruct((), np.float32, sharding=rep))
        lowereds[f"{name}:round"] = (lowered,
                                     trainer.train_net.fuse_plan_id())
    return lowereds


def _token_step_lowered(driver, device, struct):
    """The token cell's step as ``Solver`` builds it, lowered from shapes:
    its parameters and Adam's moments are 11 GB, so no ``Solver`` is made."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.graph import Net
    from sparknet_tpu.proto import (NetState, Phase,
                                    load_solver_prototxt_with_net)
    from sparknet_tpu.solvers.step import make_step_fns
    from sparknet_tpu.solvers.update_rules import make_update_rule

    one = SingleDeviceSharding(device)
    sp = load_solver_prototxt_with_net(
        driver.mix["solver"], driver.net_for(driver.batch, driver.positions))
    net = Net(sp.net_param, NetState(Phase.TRAIN),
              compute_dtype=driver._compute_dtype())
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    rule = make_update_rule(sp)
    params = jax.eval_shape(net.init, key)
    state = jax.eval_shape(rule.init, params)
    _, step, _ = make_step_fns(sp, net, rule, net.lr_mult_tree(params),
                               net.decay_mult_tree(params))
    tokens = jax.ShapeDtypeStruct(driver.raw_shape(1, driver.batch),
                                  np.int32, sharding=one)
    return jax.jit(step, donate_argnums=(0, 1)).lower(
        struct(params, one), struct(state, one), 0, {"tokens": tokens},
        struct(key, one))


_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$")
_MOVERS = {"copy", "transpose"}
_SEE_THROUGH = {"bitcast", "get-tuple-element", "reshape", "tuple"}


_ARRAY = re.compile(r"(pred|[su]\d+|bf16|f16|f32|f64)\[([\d,]*)\]")


def _bytes(shape: str, every: bool = False) -> int:
    """Bytes of the first array in an HLO shape string, or of ``every``
    array of a tuple's."""
    total = 0
    for m in _ARRAY.finditer(shape):
        n = 1 if m.group(1) == "pred" else int(re.sub(r"\D", "",
                                                     m.group(1))) // 8
        for d in filter(None, m.group(2).split(",")):
            n *= int(d)
        if not every:
            return n
        total += n
    return total


def _one_line_each(hlo: str) -> str:
    """``hlo`` with every instruction on one line: a Pallas call and the
    copies attributed to it carry the kernel's metadata over three."""
    return re.sub(r"frontend_attributes=\{kernel_metadata=\{\n.*?\n\}\},? ?",
                  "", hlo, flags=re.S)


def kernel_edge_copies(hlo: str, kernels=KERNELS):
    """The layout copies at the edges of the named Pallas kernels in a
    compiled module's text: every ``copy``/``transpose`` (alone or as the
    whole of a fusion) that feeds such a custom call or reads its result,
    seen through bitcasts and tuple plumbing, and every one the compiler
    attributes to the kernel's own ``pallas_call``.  Returns
    ``(calls, copies)``: the custom calls found by kernel name, and a list
    of ``{"kernel", "edge", "name", "shape", "bytes"}``."""
    instrs, users, fused = {}, collections.defaultdict(list), {}
    comp = None
    for line in _one_line_each(hlo).splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            fused[comp] = set()
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        if comp is not None:
            fused[comp].add(opcode)
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        op_name = re.search(r'op_name="([^"]*)"', rest)
        calls = re.search(r"calls=%?([\w.\-]+)", rest)
        instrs[name] = {
            "opcode": opcode, "shape": shape, "operands": operands,
            "op_name": op_name.group(1) if op_name else "",
            "calls": calls.group(1) if calls else None}
        for o in operands:
            users[o].append(name)

    def moves(name):
        i = instrs.get(name)
        if i is None:
            return False
        if i["opcode"] in _MOVERS:
            return True
        return (i["opcode"] == "fusion" and bool(
            fused.get(i["calls"], set()) & _MOVERS) and fused[i["calls"]]
            <= _MOVERS | _SEE_THROUGH | {"parameter"})

    def walk(start, step):
        seen, todo = set(), list(step(start))
        while todo:
            n = todo.pop()
            if n in seen or n not in instrs:
                continue
            seen.add(n)
            if moves(n):
                yield n
            elif instrs[n]["opcode"] in _SEE_THROUGH:
                todo.extend(step(n))

    def kernel_of(op_name):
        for k in kernels:
            if re.search(rf"(^|/){k}(/pallas_call)?$", op_name):
                return k
        return None

    calls = collections.Counter()
    found = {}
    for name, i in instrs.items():
        k = kernel_of(i["op_name"])
        if k is None:
            continue
        if i["opcode"] == "custom-call":
            calls[k] += 1
            for edge, step in (("in", lambda n: instrs[n]["operands"]),
                               ("out", lambda n: users[n])):
                for c in walk(name, step):
                    found.setdefault(c, (k, edge))
        elif moves(name):
            found.setdefault(name, (k, "attributed"))
    return dict(calls), [
        {"kernel": k, "edge": edge, "name": c, "shape": instrs[c]["shape"],
         "bytes": _bytes(instrs[c]["shape"])}
        for c, (k, edge) in sorted(found.items())]


_PLUMBING = {"parameter", "tuple", "get-tuple-element", "bitcast", "constant",
             "while", "conditional", "call", "after-all", "partition-id",
             "replica-id", "opt-barrier"}
_PRODUCTS = {"convolution", "dot"}


def written_bytes(hlo: str):
    """What a compiled module's operations write, by class: every
    instruction of the entry computation and of the loops' bodies it
    reaches (a loop's body once, whatever its trip count), a fusion as the
    one operation it is.  ``product`` is a convolution or dot, alone or
    inside a fusion; ``kernel`` a custom call; ``async`` the start and
    done halves of a copy or slice between memory spaces; ``other``
    everything else that makes an array.  Returns ``(by_class, ops)``:
    bytes by class, and ``{"name", "opcode", "class", "shape", "bytes",
    "fused", "op_name"}`` an operation, ``fused`` the opcodes inside a
    fusion."""
    comps, comp = {}, None
    for line in _one_line_each(hlo).splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(2)
            comps[comp] = {"entry": bool(head.group(1)), "instrs": []}
            continue
        m = _INSTR.match(line)
        if m and comp is not None:
            name, shape, opcode, rest = m.groups()
            comps[comp]["instrs"].append((name, shape, opcode, rest))

    def reached(comp, seen):
        seen.add(comp)
        for _, _, opcode, rest in comps[comp]["instrs"]:
            if opcode in ("while", "conditional", "call"):
                for callee in re.findall(r"[=,{] ?%?([\w.\-]+)",
                                         rest.split("), ", 1)[-1]):
                    if callee in comps and callee not in seen:
                        reached(callee, seen)

    top = set()
    for name, c in comps.items():
        if c["entry"]:
            reached(name, top)
    by_class, ops = collections.Counter(), []
    for comp in sorted(top):
        for name, shape, opcode, rest in comps[comp]["instrs"]:
            if opcode in _PLUMBING:
                continue
            calls = re.search(r"calls=%?([\w.\-]+)", rest)
            fused = sorted({o for _, _, o, _ in comps.get(
                calls.group(1), {"instrs": []})["instrs"]}
                - {"parameter"}) if opcode == "fusion" and calls else []
            if opcode in _PRODUCTS or _PRODUCTS & set(fused):
                kind = "product"
            elif opcode == "custom-call":
                kind = "kernel"
            elif opcode.endswith(("-start", "-done")):
                kind = "async"
            else:
                kind = "other"
            n = _bytes(shape, every=True)
            by_class[kind] += n
            op_name = re.search(r'op_name="([^"]*)"', rest)
            ops.append({"name": name, "opcode": opcode, "class": kind,
                        "shape": shape, "bytes": n, "fused": fused,
                        "op_name": op_name.group(1) if op_name else ""})
    return dict(by_class), ops


_CONV_SCOPE = r"L\[[^\]]*/conv\]"


def _listed(op) -> dict:
    """One of ``written_bytes``'s operations as the edge report lists it."""
    return {k: op[k] for k in ("name", "shape", "bytes", "op_name")}


def scope_copies(ops, scope: str):
    """The ``copy`` and ``transpose`` operations of ``written_bytes``'s
    list whose ``op_name`` matches ``scope``: what a layer with no kernel
    of its own (the gated short convolution) copies inside its scope."""
    return [_listed(o) for o in ops
            if o["opcode"] in _MOVERS and re.search(scope, o["op_name"])]


_DQ_SUM = r"attn_core/vmap\(jit\(_splash_attention\)\)/reduce_sum$"


def partial_dq_sums(ops):
    """The reductions of ``written_bytes``'s list that sum a fused backward
    kernel's partial ``dq`` over its key/value blocks: JAX's own, inside
    its wrapper of the kernels (``dq_unreduced.sum(axis=0)``), one a fused
    layer a sequence.  ``bytes`` is the sum's result, the queries' size."""
    return [_listed(o) for o in ops
            if (o["opcode"] == "reduce" or "reduce" in o["fused"])
            and re.search(_DQ_SUM, o["op_name"])]


_MOE_SCOPE = re.compile(r"L\[([^\]]+)\].*?/(moe_route|moe_experts)(/|$)")
_MOVES = {"scatter", "gather", "sort", "topk"}


def moe_moves(hlo: str):
    """The ``scatter``, ``gather``, ``sort`` and ``topk`` instructions of a
    compiled module's text under an expert layer's sub-scopes
    (``moe_route``, ``moe_experts``), inside fusions too: ``{"layer",
    "scope", "pass" (fwd, remat, bwd), "opcode", "primitive" (the JAX
    primitive the instruction came from: the compiler's own sort of a
    scatter's indices reads ``scatter-add``), "name", "shape", "updates"}``
    each, ``updates`` the shape of one update of a scatter (``[]``: a
    scalar) or of one slice of a gather.  What moves a scalar at a time on
    a TPU costs by the element, whatever the bytes."""
    shapes, out = {}, []
    for line in _one_line_each(hlo).splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, opcode, rest = m.groups()
        shapes[name] = shape        # operands are printed before their users
        if opcode == "custom-call" and "TopK" in rest:
            opcode = "topk"
        op_name = re.search(r'op_name="([^"]*)"', rest)
        scope = _MOE_SCOPE.search(op_name.group(1)) if op_name else None
        if opcode not in _MOVES or not scope:
            continue
        op_name, updates = op_name.group(1), None
        if opcode == "scatter":
            operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
            window = re.search(r"update_window_dims=\{([\d,]*)\}", rest)
            dims = _ARRAY.search(shapes.get(operands[-1], ""))
            if window and dims:
                sizes = [int(d) for d in dims.group(2).split(",") if d]
                updates = [sizes[int(i)] for i in
                           filter(None, window.group(1).split(","))
                           if sizes[int(i)] > 1]
        elif opcode == "gather":
            sizes = re.search(r"slice_sizes=\{([\d,]*)\}", rest)
            updates = [int(d) for d in sizes.group(1).split(",")
                       if d and int(d) > 1] if sizes else None
        out.append({
            "layer": scope.group(1), "scope": scope.group(2),
            "pass": ("remat" if "rematted_computation" in op_name else
                     "bwd" if "transpose(" in op_name else "fwd"),
            "opcode": opcode,
            "primitive": op_name.rstrip("/").rsplit("/", 1)[-1],
            "name": name, "shape": shape, "updates": updates})
    return out


def edges(args) -> int:
    """``--set edges``: compile the cells' programs and list the layout
    copies at the edges of the Pallas kernels; exit 1 if any."""
    import jax
    report = {}
    for name, (lowered, _) in cell_lowered(
            args.devices == "described", args.cell or CELLS).items():
        hlo = lowered.compile().as_text()
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(
                    args.out, name.split(":")[0] + ".hlo"), "w") as f:
                f.write(hlo)
        calls, copies = kernel_edge_copies(hlo)
        written, ops = written_bytes(hlo)
        report[name] = {"kernel_calls": calls, "copies": copies,
                        "copy_bytes": sum(c["bytes"] for c in copies),
                        "written": written,
                        "conv_copies": scope_copies(ops, _CONV_SCOPE),
                        "dq_sums": partial_dq_sums(ops),
                        "moe_moves": moe_moves(hlo)}
    print(json.dumps({"root": args.root, "backend": jax.default_backend(),
                      "devices": args.devices, "edges": report}),
          flush=True)
    return 1 if any(r["copies"] for r in report.values()) else 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--set", choices=("nets", "cells", "edges"),
                    required=True)
    ap.add_argument("--devices", choices=("real", "described"),
                    default="real")
    ap.add_argument("--cell", action="append", choices=CELLS,
                    help="of --set cells, only these (default: all)")
    ap.add_argument("--out")
    args = ap.parse_args()

    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if (args.devices == "described" and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        # the rounds cell builds its trainer on four host devices first
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_"
                                   "force_host_platform_device_count=4")
    import jax
    # a described chip's cache entry could not be read back
    jax.config.update("jax_enable_compilation_cache", False)

    import sparknet_tpu
    if not os.path.abspath(sparknet_tpu.__file__).startswith(root + os.sep):
        raise SystemExit(f"sparknet_tpu came from {sparknet_tpu.__file__}, "
                         f"not from --root {root}")
    if args.set == "edges":
        return edges(args)
    texts = (net_texts() if args.set == "nets" else {
        name: (lowered.as_text(), plan) for name, (lowered, plan) in
        cell_lowered(args.devices == "described",
                     args.cell or CELLS).items()})
    texts = {name: (without_kernel_locations(text), plan)
             for name, (text, plan) in texts.items()}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        for name, (text, _) in texts.items():
            with open(os.path.join(
                    args.out, name.split(":")[0] + ".mlir"), "w") as f:
                f.write(text)
    print(json.dumps({
        "root": args.root, "backend": jax.default_backend(),
        "devices": args.devices if args.set == "cells" else "real",
        "fuse_plan": {name: plan for name, (_, plan) in texts.items()},
        "hashes": {name: hashlib.sha1(text.encode()).hexdigest()[:12]
                   for name, (text, _) in texts.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
