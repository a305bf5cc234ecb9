"""Laguna: a decoder-only mixture-of-experts language model (poolside,
Laguna XS.2; https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json).

The defaults are the published configuration: 40 layers of width 2048 in
the pattern full, sliding, sliding, sliding; 48 query heads in a full layer
and 64 in a sliding one over 8 key/value heads of 128; window 512; rotary
over half a head with YaRN in full layers and over the whole head in
sliding ones; a gate on each head's output; layer 0 a dense gated MLP of
8192, every other layer 256 experts of 512, 8 a token, and a shared expert.
A chip that holds a share of a deployment passes ``num_layers``,
``experts_held`` and ``vocab`` (``benchmark/configs/laguna_xs_2.json``);
the CPU tests pass small widths.  What the configuration does not say
(the gate's form, the router's scores, the fillers) is listed under
``assumed`` in that file.
"""

from __future__ import annotations

from ..proto.caffe_pb import NetParameter, Phase
from .dsl import gaussian, java_data_layer, layer, net_param

_PERIOD = ("full", "sliding", "sliding", "sliding")


def laguna(train_batch: int = 4, test_batch: int = 1, *,
           seq_len: int = 8192, num_layers: int = 40, vocab: int = 100352,
           hidden: int = 2048, head_dim: int = 128, kv_heads: int = 8,
           heads: dict | None = None, window: int = 512,
           dense_width: int = 8192, num_experts: int = 256,
           experts_held: tuple[int, int] | None = None, top_k: int = 8,
           expert_width: int = 512, shared_width: int = 512,
           routed_scaling: float = 2.5, eps: float = 1e-6,
           yarn_original_length: int = 4096, std: float = 0.02,
           router_std: float = 0.006, embed_std: float = 1.0,
           train_router: bool = True) -> NetParameter:
    """``train_router=False`` keeps the routers as the seed made them
    (``lr_mult`` 0) and their scores' gradient out of the residual stream
    (``detach_router``).  A chip that holds a share of the experts is sent
    only that share's part of the gradient that passes through a router's
    scores, and it teaches router and stream alike that the held experts
    are the useful ones (the configuration file's
    ``assumed.router_frozen``)."""
    heads = heads or {"full": 48, "sliding": 64}
    lo, hi = experts_held or (0, num_experts)
    by_kind = {
        "full": {"rotary_dim": head_dim // 2, "rope_theta": 500000.0,
                 "yarn_factor": 64.0,
                 "yarn_original_length": yarn_original_length,
                 "yarn_beta_fast": 64.0, "yarn_beta_slow": 1.0,
                 "rope_attention_factor": 1.4158883083359672},
        "sliding": {"rotary_dim": head_dim, "rope_theta": 10000.0,
                    "window": window},
    }
    layers = [
        java_data_layer("tokens_train", ["tokens"], Phase.TRAIN,
                        (train_batch, seq_len)),
        java_data_layer("tokens_test", ["tokens"], Phase.TEST,
                        (test_batch, seq_len)),
        layer("embed", "Embed", ["tokens"], ["x0"], embed_param={
            "num_output": hidden, "input_dim": vocab, "bias_term": False,
            "weight_filler": gaussian(embed_std)}),
    ]
    x = "x0"
    for i in range(num_layers):
        kind = _PERIOD[i % len(_PERIOD)]
        p = f"L{i}"
        norm = {"rms_norm_param": {"eps": eps}}
        layers += [
            layer(f"{p}/norm1", "RMSNorm", [x], [f"{p}/n1"], **norm),
            layer(f"{p}/attn", "Attention", [f"{p}/n1"], [f"{p}/a"],
                  attention_param={
                      "num_heads": heads[kind], "num_kv_heads": kv_heads,
                      "head_dim": head_dim, **by_kind[kind],
                      "weight_filler": gaussian(std)}),
            layer(f"{p}/res1", "Eltwise", [x, f"{p}/a"], [f"{p}/h"]),
            layer(f"{p}/norm2", "RMSNorm", [f"{p}/h"], [f"{p}/n2"], **norm),
        ]
        if i == 0:
            layers.append(layer(
                f"{p}/mlp", "GatedMLP", [f"{p}/n2"], [f"{p}/m"],
                gated_mlp_param={"width": dense_width,
                                 "weight_filler": gaussian(std)}))
        else:
            layers.append(layer(
                f"{p}/moe", "MixtureOfExperts", [f"{p}/n2"], [f"{p}/m"],
                param=[{"lr_mult": 1.0 if train_router else 0.0}],
                moe_param={
                    "num_experts": num_experts, "top_k": top_k,
                    "experts_held_lo": lo, "experts_held_hi": hi,
                    "expert_width": expert_width,
                    "shared_width": shared_width,
                    "routed_scaling": routed_scaling,
                    "weight_filler": gaussian(std),
                    "router_filler": gaussian(router_std),
                    "router_column_norm": router_std * hidden ** 0.5,
                    "detach_router": not train_router}))
        x = f"x{i + 1}"
        layers.append(layer(f"{p}/res2", "Eltwise", [f"{p}/h", f"{p}/m"],
                            [x]))
    head = layer("lm_loss", "LMHeadLoss", ["xf", "tokens"], ["loss"],
                 lm_head_param={"vocab": vocab,
                                "weight_filler": gaussian(std)})
    head.loss_weight = [1.0]
    layers += [
        layer("final_norm", "RMSNorm", [x], ["xf"],
              rms_norm_param={"eps": eps}),
        head,
    ]
    return net_param("Laguna", layers)
