"""Operations and bytes of the sequence layers (``ops/sequence.py``), from
shapes and never from the compiler, and the ``as_built`` record a token
configuration is held to.  ``lib/flops.py`` knows the convolutional types
only and refuses the rest; a later ``benchmark`` PR folds this in.

Counting.  One multiply-accumulate is two operations.  A matrix product
costs its multiply-accumulates once forward and twice backward (the
gradient of each operand), so training is three times forward;
recomputation in the backward pass is not counted.  An expert layer's
routed products are counted at the rows an even router sends to the held
experts, ``tokens * top_k * held / experts``.  The attention core is counted
at the pairs its mask lets through: ``j <= i`` and, with a window ``w``,
``i - j < w``.
"""

from __future__ import annotations

SEQUENCE_TYPES = ("RMSNorm", "Attention", "GatedMLP", "MixtureOfExperts",
                  "LMHeadLoss", "Embed", "Eltwise", "JavaData")


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
            raise ValueError(f"lm_flops does not know layer type "
                             f"{lp.type!r} ({lp.name!r})")
        bottom = shape[lp.bottom[0]]
        g = {"sequences": bottom[0], "positions": bottom[1],
             "hidden": bottom[-1]}
        if lp.type == "Embed":
            p = lp.sub("embed_param")
            g.update(vocab=int(p.get("input_dim")),
                     hidden=int(p.get("num_output")))
            shape[lp.top[0]] = (*bottom, g["hidden"])
        elif lp.type == "Attention":
            p = lp.sub("attention_param")
            g.update(heads=int(p.get("num_heads")),
                     kv=int(p.get("num_kv_heads")),
                     head_dim=int(p.get("head_dim")),
                     window=int(p.get("window", 0)),
                     rotary_dim=int(p.get("rotary_dim")))
            shape[lp.top[0]] = bottom
        elif lp.type == "GatedMLP":
            g.update(width=int(lp.sub("gated_mlp_param").get("width")))
            shape[lp.top[0]] = bottom
        elif lp.type == "MixtureOfExperts":
            p = lp.sub("moe_param")
            g.update(experts=int(p.get("num_experts")),
                     top_k=int(p.get("top_k")),
                     held=int(p.get("experts_held_hi"))
                     - int(p.get("experts_held_lo")),
                     width=int(p.get("expert_width")),
                     shared=int(p.get("shared_width")))
            shape[lp.top[0]] = bottom
        elif lp.type == "LMHeadLoss":
            g.update(vocab=int(lp.sub("lm_head_param").get("vocab")))
        else:                               # RMSNorm, Eltwise
            shape[lp.top[0]] = bottom
        yield lp, g


def parameters(lp, g: dict) -> int:
    h = g.get("hidden", 0)
    if lp.type in ("Embed", "LMHeadLoss"):
        return g["vocab"] * h
    if lp.type == "RMSNorm":
        return h
    if lp.type == "Attention":
        q, kv = g["heads"] * g["head_dim"], g["kv"] * g["head_dim"]
        return h * (2 * q + 2 * kv + g["heads"])
    if lp.type == "GatedMLP":
        return 3 * h * g["width"]
    if lp.type == "MixtureOfExperts":
        return h * (g["experts"] + 3 * g["held"] * g["width"]
                    + 3 * g["shared"])
    return 0


def as_built(net_param) -> dict:
    """Every width of every layer of the train net (not the positions,
    which are the traffic's) and the parameters it holds: what a
    configuration file records and every run checks."""
    rows, total = [], 0
    for lp, g in layers(net_param):
        widths = [g[k] for k in ("hidden", "vocab", "heads", "kv",
                                 "head_dim", "window", "rotary_dim", "width",
                                 "experts", "top_k", "held", "shared")
                  if k in g]
        rows.append([lp.name, lp.type, *widths])
        total += parameters(lp, g)
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
        out["other"] = s * parameters(lp, g)
        out["core"] = (2 * causal_pairs(s, g["window"]) * g["heads"]
                       * g["head_dim"])
    elif lp.type == "GatedMLP":
        out["other"] = s * parameters(lp, g)
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


def expert_bytes_per_sequence(net_param, itemsize: int) -> float:
    """Bytes the routed products of every expert layer must move for one
    sequence's share of a step: in each of the three passes (forward, and
    the backward products for the rows' and the weights' gradients) every
    held expert's three matrices once, and the routed rows once in and
    once out of each of the layer's two stages (``hidden`` wide into
    gate and up, ``hidden`` wide out of down).  The weights are charged to
    a step and not to a sequence, so this takes the net's sequences a step
    and returns the step's bytes over them."""
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
