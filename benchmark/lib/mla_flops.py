"""Operations and bytes of a sequence net that has multi-head latent
attention (``LatentAttention``), and the ``as_built`` record a token
configuration with it is held to: ``seq_flops.py``'s counts, with the one
type it does not know.

Every other layer type is counted by ``seq_flops``' own functions: a net is
walked by ``seq_flops`` with each ``LatentAttention`` layer standing in as
an ``Eltwise`` of the same blob (it keeps the shape of its input), and the
latent layers' own parameters and operations are added here.  An expert
layer's row also records its scoring (``softmax`` or ``sigmoid``) and
whether the chosen weights are normalised, so that ``as_built`` refuses
another router.

Counting, as ``seq_flops``: two operations a multiply-accumulate, three
passes (forward and the two backward products), recomputation not counted.
A latent attention layer's products are ``W_q``, ``W_dkv``, ``W_kr``,
``W_ukv`` and ``W_o`` at every position; its core is the pairs the causal
mask lets through, scores at the query/key head (``nope + rope``) and the
weighted sum at the value head, in every head.  The rotary turn (a product
with a matrix of 0 and ±1, ``ops/sequence.py``) and the latent's norm are
not counted, as ``seq_flops`` counts no rotary turn and no norm.
"""

from __future__ import annotations

import dataclasses

from . import seq_flops
from .seq_flops import (causal_pairs, layer_names,  # noqa: F401
                        sequences_per_step)

LATENT = "LatentAttention"
_LATENT_WIDTHS = ("hidden", "heads", "rank", "nope", "rope", "v", "scale")
_ROUTER_WIDTHS = ("scoring", "norm_topk")


def _stand_in(net_param):
    """The net with each latent attention layer an ``Eltwise`` of its one
    bottom: what ``seq_flops`` can walk."""
    return dataclasses.replace(net_param, layer=[
        dataclasses.replace(lp, type="Eltwise") if lp.type == LATENT else lp
        for lp in net_param.layer])


def layers(net_param):
    """``seq_flops.layers`` over the whole net, a latent attention layer's
    geometry (``heads``, ``rank``, ``nope``, ``rope``, ``v``, ``scale``)
    and an expert layer's router (``scoring``, ``norm_topk``) added."""
    by_name = {lp.name: lp for lp in net_param.layer}
    for stood, g in seq_flops.layers(_stand_in(net_param)):
        lp = by_name[stood.name]
        if lp.type == LATENT:
            p = lp.sub("latent_attention_param")
            nope, rope = (int(p.get("qk_nope_head_dim")),
                          int(p.get("qk_rope_head_dim")))
            g.update(heads=int(p.get("num_heads")),
                     rank=int(p.get("kv_lora_rank")), nope=nope, rope=rope,
                     v=int(p.get("v_head_dim")),
                     scale=float(p.get("softmax_scale",
                                       (nope + rope) ** -0.5)))
        elif lp.type == "MixtureOfExperts":
            p = lp.sub("moe_param")
            g.update(scoring=str(p.get("scoring", "sigmoid")),
                     norm_topk=int(bool(p.get("norm_topk", True))))
        yield lp, g


def _latent_products(g: dict) -> int:
    """Multiply-accumulates of a latent attention layer's projections, a
    position: also its parameters, less the latent norm's weight."""
    h, heads = g["hidden"], g["heads"]
    return (h * heads * (g["nope"] + g["rope"]) + h * g["rank"]
            + h * g["rope"] + g["rank"] * heads * (g["nope"] + g["v"])
            + heads * g["v"] * h)


def parameters(lp, g: dict) -> int:
    if lp.type == LATENT:
        return _latent_products(g) + g["rank"]
    return seq_flops.parameters(lp, g)


def as_built(net_param) -> dict:
    """``seq_flops.as_built`` with the latent layers' and the routers'
    widths: every width of every layer of the train net and the parameters
    it holds, a blob that layers share counted at the first."""
    rows, total, named = [], 0, set()
    for lp, g in layers(net_param):
        keys = (_LATENT_WIDTHS if lp.type == LATENT else
                [k for k in seq_flops._WIDTHS if k in g]
                + [k for k in _ROUTER_WIDTHS if k in g])
        rows.append([lp.name, lp.type, *(g[k] for k in keys)])
        names = {ps.name for ps in lp.param if ps.name}
        if not names or not names <= named:
            total += parameters(lp, g)
        named |= names
    return {"parameters": total, "layers": rows}


def forward_macs(lp, g: dict) -> dict:
    """``seq_flops.forward_macs``, and a latent attention layer's: its
    projections under ``other``, its core under ``core``."""
    if lp.type != LATENT:
        return seq_flops.forward_macs(lp, g)
    s = g["positions"]
    return {"core": (causal_pairs(s) * g["heads"]
                     * (g["nope"] + g["rope"] + g["v"])),
            "experts": 0.0, "other": s * _latent_products(g)}


def train_flops_per_sequence(net_param) -> dict:
    """Operations one sequence's forward and backward passes require, by
    part, and ``total``."""
    acc = {"core": 0.0, "experts": 0.0, "other": 0.0}
    for lp, g in layers(net_param):
        for k, v in forward_macs(lp, g).items():
            acc[k] += 2 * 3 * v
    return {**acc, "total": sum(acc.values())}


def expert_bytes_per_sequence(net_param, itemsize: int) -> float:
    """``seq_flops.expert_bytes_per_sequence``: a latent layer moves no
    expert's bytes."""
    return seq_flops.expert_bytes_per_sequence(_stand_in(net_param),
                                               itemsize)


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
