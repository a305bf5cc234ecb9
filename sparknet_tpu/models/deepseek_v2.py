"""DeepSeek-V2: a decoder-only mixture-of-experts language model with
multi-head latent attention (DeepSeek-V2-Lite;
https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json).

The defaults are the published configuration: 27 layers of width 2048, each
a latent attention layer of 16 heads (keys and values from a latent of 512,
RMS-normalised; per head 128 dimensions without rotary and a rotary key of 64
shared by the heads; values of 128; no query compression; YaRN rotary of
factor 40 over 4,096 positions, whose ``mscale`` squares into the softmax
scale); layer 0 a dense gated MLP of 10,944, every other layer a softmax
router over 64 experts of 1,408, 6 a token weighed by their probabilities
as they are (``norm_topk_prob`` false), beside two shared experts; an untied
head.  A chip that holds a share of a deployment passes ``layers_kept``,
``experts_held`` and ``vocab`` (``benchmark/configs/deepseek_v2_lite.json``);
the CPU tests pass small widths.  What the configuration does not say (the
fillers, the order of the rotary pairs) is listed under ``assumed`` in that
file.
"""

from __future__ import annotations

import math
from typing import Sequence

from ..proto.caffe_pb import NetParameter, Phase
from .dsl import gaussian, java_data_layer, layer, net_param


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention scale ``0.1 * mscale * ln(factor) + 1`` (1 where
    the positions are not stretched)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def deepseek_v2(train_batch: int = 4, test_batch: int = 1, *,
                seq_len: int = 8192, num_layers: int = 27,
                layers_kept: Sequence[int] | None = None,
                first_dense: int = 1, vocab: int = 102400,
                hidden: int = 2048, heads: int = 16, kv_lora_rank: int = 512,
                qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                v_head_dim: int = 128, dense_width: int = 10944,
                num_experts: int = 64,
                experts_held: tuple[int, int] | None = None, top_k: int = 6,
                expert_width: int = 1408, shared_experts: int = 2,
                routed_scaling: float = 1.0, eps: float = 1e-6,
                rope_theta: float = 10000.0, yarn_factor: float = 40.0,
                yarn_original_length: int = 4096, mscale: float = 0.707,
                mscale_all_dim: float = 0.707, std: float = 0.02,
                router_std: float = 0.006, embed_std: float = 1.0,
                train_router: bool = True) -> NetParameter:
    """Layer ``i`` of ``num_layers`` is dense below ``first_dense`` and an
    expert layer above; ``layers_kept`` builds those published layers
    alone, under their published indices.  The softmax scale is
    ``(qk_nope_head_dim + qk_rope_head_dim)^-1/2 * m^2`` with ``m =
    yarn_mscale(yarn_factor, mscale_all_dim)``, and cos and sin are
    multiplied by ``yarn_mscale(yarn_factor, mscale) / m``.
    The two shared experts are one gated MLP of twice the width, which is
    their sum.  ``train_router=False`` keeps the routers as the seed made
    them and their scores' gradient out of the residual stream
    (``models/laguna.py`` says why a chip's share needs it); the
    sequence-level auxiliary balance loss acts on the routers alone, so with
    them frozen it is left out."""
    lo, hi = experts_held or (0, num_experts)
    kept = list(range(num_layers)) if layers_kept is None else list(
        layers_kept)
    m_all = yarn_mscale(yarn_factor, mscale_all_dim)
    latent = {
        "num_heads": heads, "kv_lora_rank": kv_lora_rank,
        "qk_nope_head_dim": qk_nope_head_dim,
        "qk_rope_head_dim": qk_rope_head_dim, "v_head_dim": v_head_dim,
        "rope_theta": rope_theta, "yarn_factor": yarn_factor,
        "yarn_original_length": yarn_original_length,
        "yarn_beta_fast": 32.0, "yarn_beta_slow": 1.0,
        "rope_attention_factor": yarn_mscale(yarn_factor, mscale) / m_all,
        "softmax_scale": (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        * m_all * m_all,
        "kv_norm_eps": eps, "weight_filler": gaussian(std)}
    layers = [
        java_data_layer("tokens_train", ["tokens"], Phase.TRAIN,
                        (train_batch, seq_len)),
        java_data_layer("tokens_test", ["tokens"], Phase.TEST,
                        (test_batch, seq_len)),
        layer("embed", "Embed", ["tokens"], ["x0"], embed_param={
            "num_output": hidden, "input_dim": vocab, "bias_term": False,
            "weight_filler": gaussian(embed_std)}),
    ]
    norm = {"rms_norm_param": {"eps": eps}}
    x = "x0"
    for n, i in enumerate(kept):
        p = f"L{i}"
        layers += [
            layer(f"{p}/norm1", "RMSNorm", [x], [f"{p}/n1"], **norm),
            layer(f"{p}/attn", "LatentAttention", [f"{p}/n1"], [f"{p}/a"],
                  latent_attention_param=latent),
            layer(f"{p}/res1", "Eltwise", [x, f"{p}/a"], [f"{p}/h"]),
            layer(f"{p}/norm2", "RMSNorm", [f"{p}/h"], [f"{p}/n2"], **norm),
        ]
        if i < first_dense:
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
                    "shared_width": shared_experts * expert_width,
                    "routed_scaling": routed_scaling,
                    "scoring": "softmax", "norm_topk": False,
                    "weight_filler": gaussian(std),
                    "router_filler": gaussian(router_std),
                    "router_column_norm": router_std * hidden ** 0.5,
                    "detach_router": not train_router}))
        x = f"x{n + 1}"
        layers.append(layer(f"{p}/res2", "Eltwise", [f"{p}/h", f"{p}/m"],
                            [x]))
    head = layer("lm_loss", "LMHeadLoss", ["xf", "tokens"], ["loss"],
                 lm_head_param={"vocab": vocab,
                                "weight_filler": gaussian(std)})
    head.loss_weight = [1.0]
    layers += [
        layer("final_norm", "RMSNorm", [x], ["xf"], **norm),
        head,
    ]
    return net_param("DeepSeekV2", layers)
