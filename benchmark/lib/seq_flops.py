"""Operations and bytes of every sequence layer type (``ops/sequence.py``),
from shapes and never from the compiler, and the ``as_built`` record a token
configuration is held to.  ``lm_flops.py`` knows the types Laguna has and
counts a gate in every attention layer; this module knows them all, the
gated short convolution too, reads whether an attention layer has a gate
and normalises its heads, whether an expert layer has a shared expert and
a selection bias, and counts a blob that two layers share (a tied head)
once.  A later ``benchmark`` PR folds ``lm_flops.py`` into it.

Counting.  One multiply-accumulate is two operations.  A matrix product
costs its multiply-accumulates once forward and twice backward (the
gradient of each operand), so training is three times forward;
recomputation in the backward pass is not counted.  An expert layer's
routed products are counted at the rows an even router sends to the held
experts, ``tokens * top_k * held / experts``.  The attention core is
counted at the pairs its causal (and window) mask lets through and at the
head size the model has, whatever a lowering pads it to.  What lies
between the short convolution's two products (``conv_mix``) is no product:
it is counted in bytes, the least that have to move.
"""

from __future__ import annotations

SEQUENCE_TYPES = ("RMSNorm", "Attention", "ShortConv", "GatedMLP",
                  "MixtureOfExperts", "LMHeadLoss", "Embed", "Eltwise",
                  "JavaData")
_WIDTHS = ("hidden", "vocab", "heads", "kv", "head_dim", "window",
           "rotary_dim", "gate", "qk_norm", "kernel", "width", "experts",
           "top_k", "held", "shared", "select_bias")


def layers(net_param):
    """Walk the train net: (layer, geometry dict) with ``positions`` and
    ``hidden`` of the layer's first bottom."""
    shape = {}
    for lp in net_param.layer:
        if lp.type == "JavaData":
            dims = tuple(int(d) for d in
                         lp.sub("java_data_param").get("shape").get_all(
                             "dim"))
            shape[lp.top[0]] = dims
            yield lp, {"sequences": dims[0], "positions": dims[1]}
            continue
        if lp.type not in SEQUENCE_TYPES:
            raise ValueError(f"seq_flops does not know layer type "
                             f"{lp.type!r} ({lp.name!r})")
        bottom = shape[lp.bottom[0]]
        g = {"sequences": bottom[0], "positions": bottom[1],
             "hidden": bottom[-1]}
        out = bottom
        if lp.type == "Embed":
            p = lp.sub("embed_param")
            g.update(vocab=int(p.get("input_dim")),
                     hidden=int(p.get("num_output")))
            out = (*bottom, g["hidden"])
        elif lp.type == "Attention":
            p = lp.sub("attention_param")
            d = int(p.get("head_dim"))
            g.update(heads=int(p.get("num_heads")),
                     kv=int(p.get("num_kv_heads")), head_dim=d,
                     window=int(p.get("window", 0)),
                     rotary_dim=int(p.get("rotary_dim", d)),
                     gate=int(bool(p.get("gate", True))),
                     qk_norm=int(bool(p.get("qk_norm", False))))
        elif lp.type == "ShortConv":
            g.update(kernel=int(lp.sub("short_conv_param").get("kernel", 3)))
        elif lp.type == "GatedMLP":
            g.update(width=int(lp.sub("gated_mlp_param").get("width")))
        elif lp.type == "MixtureOfExperts":
            p = lp.sub("moe_param")
            g.update(experts=int(p.get("num_experts")),
                     top_k=int(p.get("top_k")),
                     held=int(p.get("experts_held_hi"))
                     - int(p.get("experts_held_lo")),
                     width=int(p.get("expert_width")),
                     shared=int(p.get("shared_width", 0)),
                     select_bias=int(bool(p.get("select_bias", False))))
        elif lp.type == "LMHeadLoss":
            g.update(vocab=int(lp.sub("lm_head_param").get("vocab")))
            out = None
        if out is not None:
            shape[lp.top[0]] = out
        yield lp, g


def parameters(lp, g: dict) -> int:
    """Parameters of the layer's own blobs, a shared one with them."""
    h = g.get("hidden", 0)
    if lp.type in ("Embed", "LMHeadLoss"):
        return g["vocab"] * h
    if lp.type == "RMSNorm":
        return h
    if lp.type == "Attention":
        q, kv = g["heads"] * g["head_dim"], g["kv"] * g["head_dim"]
        return (h * (2 * q + 2 * kv + g["gate"] * g["heads"])
                + 2 * g["qk_norm"] * g["head_dim"])
    if lp.type == "ShortConv":
        return h * (3 * h + g["kernel"] + h)
    if lp.type == "GatedMLP":
        return 3 * h * g["width"]
    if lp.type == "MixtureOfExperts":
        return (h * (g["experts"] + 3 * g["held"] * g["width"]
                     + 3 * g["shared"]) + g["select_bias"] * g["experts"])
    return 0


def as_built(net_param) -> dict:
    """Every width of every layer of the train net (not the positions,
    which are the traffic's) and the parameters it holds, a blob that
    layers share counted at the first: what a configuration file records
    and every run checks."""
    rows, total, named = [], 0, set()
    for lp, g in layers(net_param):
        rows.append([lp.name, lp.type, *(g[k] for k in _WIDTHS if k in g)])
        names = {ps.name for ps in lp.param if ps.name}
        if not names or not names <= named:
            total += parameters(lp, g)
        named |= names
    return {"parameters": total, "layers": rows}


def causal_pairs(positions: int, window: int = 0) -> int:
    """(query, key) pairs a causal mask lets through, with a window."""
    if not window or window >= positions:
        return positions * (positions + 1) // 2
    return window * (window + 1) // 2 + (positions - window) * window


def routed_rows(g: dict) -> float:
    """Rows an even router sends the held experts, a sequence."""
    return g["positions"] * g["top_k"] * g["held"] / g["experts"]


def forward_macs(lp, g: dict) -> dict:
    """Multiply-accumulates of one sequence's forward pass through one
    layer: ``{"core": attention pairs, "experts": routed products,
    "other": every other product}``."""
    s, h = g["positions"], g.get("hidden", 0)
    out = {"core": 0.0, "experts": 0.0, "other": 0.0}
    if lp.type == "Attention":
        q, kv = g["heads"] * g["head_dim"], g["kv"] * g["head_dim"]
        out["other"] = s * h * (2 * q + 2 * kv + g["gate"] * g["heads"])
        out["core"] = (2 * causal_pairs(s, g["window"]) * g["heads"]
                       * g["head_dim"])
    elif lp.type == "ShortConv":
        out["other"] = s * h * 4 * h
    elif lp.type == "GatedMLP":
        out["other"] = s * 3 * h * g["width"]
    elif lp.type == "MixtureOfExperts":
        out["other"] = s * h * (g["experts"] + 3 * g["shared"])
        out["experts"] = routed_rows(g) * 3 * h * g["width"]
    elif lp.type == "LMHeadLoss":
        out["other"] = s * h * g["vocab"]
    return out


def train_flops_per_sequence(net_param) -> dict:
    """Operations one sequence's forward and backward passes require, by
    part, and ``total``."""
    acc = {"core": 0.0, "experts": 0.0, "other": 0.0}
    for lp, g in layers(net_param):
        for k, v in forward_macs(lp, g).items():
            acc[k] += 2 * 3 * v
    return {**acc, "total": sum(acc.values())}


# passes over one ``[positions, hidden]`` array that ``conv_mix`` cannot do
# without: forward its three inputs (B, C, x) and its output once; backward
# the cotangent and the three inputs in, the three gradients out
CONV_MIX_PASSES = 4 + 7


def conv_mix_bytes_per_sequence(net_param, itemsize: int) -> float:
    """The least bytes ``conv_mix`` must move for one sequence's forward
    and backward passes, over every short convolution of the net: 11
    passes of ``positions x hidden`` in the compute dtype; the taps and
    their gradient are nothing beside them, and recomputation is not
    counted."""
    return float(sum(
        CONV_MIX_PASSES * g["positions"] * g["hidden"] * itemsize
        for lp, g in layers(net_param) if lp.type == "ShortConv"))


def expert_bytes_per_sequence(net_param, itemsize: int) -> float:
    """The least bytes the routed products of every expert layer must move
    for one sequence's share of a step (``lm_flops.py``'s count): in each
    of the three passes (forward, and the backward products for the rows'
    and the weights' gradients) every held expert's three matrices once,
    and the routed rows once in and once out of each of the layer's two
    stages.  The weights are charged to a step and not to a sequence, so
    this takes the net's sequences a step and returns the step's bytes
    over them."""
    total = 0.0
    for lp, g in layers(net_param):
        if lp.type != "MixtureOfExperts":
            continue
        weights = 3 * g["held"] * g["hidden"] * g["width"] * itemsize
        rows = routed_rows(g) * 2 * g["hidden"] * itemsize
        total += 3 * (weights / g["sequences"] + rows)
    return total


def sequences_per_step(net_param) -> int:
    return next(g["sequences"] for _, g in layers(net_param))


def layer_names(net_param, type_: str) -> list[str]:
    return [lp.name for lp in net_param.layer if lp.type == type_]


def check_as_built(config: dict, net_param) -> None:
    """Refuse a net that is not the one the configuration file states."""
    got, want = as_built(net_param), config["as_built"]
    if got["parameters"] != want["parameters"] or got["layers"] != [
            list(r) for r in want["layers"]]:
        diff = [(g, w) for g, w in zip(got["layers"], want["layers"])
                if g != list(w)]
        raise SystemExit(
            f"configuration {config['name']!r}: the net the program "
            f"builds is not the one the configuration file states "
            f"({got['parameters']} against {want['parameters']} "
            f"parameters; first difference {diff[:1]})")
