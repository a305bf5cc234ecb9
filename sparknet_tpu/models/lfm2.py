"""LFM2: a decoder-only hybrid of gated short convolutions and grouped-query
attention with a mixture of experts (Liquid AI, LFM2-24B-A2B;
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json).

The defaults are the published configuration: 40 layers of width 2048, of
which layers 2, 6, ..., 38 are attention (32 query heads over 8 key/value
heads of 64, an RMSNorm over each head of q and of k, rotary over the whole
head, no gate) and the other 30 a double-gated short convolution of 3 taps;
layers 0 and 1 have a dense gated MLP of 11776, every other layer 64
experts of 1536, 4 a token chosen by ``sigmoid(x W_r) + b`` and weighed by
``sigmoid(x W_r)``, no shared expert; the head is the embedding (tied).  A
chip that holds a share of a deployment passes ``layers_kept``,
``experts_held`` and ``vocab`` (``benchmark/configs/lfm2_24b_a2b.json``);
the CPU tests pass small widths.  What the configuration does not say (the
tie, the order of the convolution's thirds and taps, the fillers) is listed
under ``assumed`` in that file.
"""

from __future__ import annotations

from typing import Sequence

from ..proto.caffe_pb import NetParameter, Phase
from .dsl import gaussian, java_data_layer, layer, net_param

# the convolution taps' spread: that of PyTorch's default for a depthwise
# Conv1d of 3 taps, U(-1/sqrt(3), 1/sqrt(3)), whose standard deviation is 1/3
_TAP_STD = 1.0 / 3.0


def lfm2(train_batch: int = 4, test_batch: int = 1, *,
         seq_len: int = 8192, num_layers: int = 40,
         layers_kept: Sequence[int] | None = None, num_dense_layers: int = 2,
         vocab: int = 65536, hidden: int = 2048, heads: int = 32,
         kv_heads: int = 8, conv_kernel: int = 3, dense_width: int = 11776,
         num_experts: int = 64,
         experts_held: tuple[int, int] | None = None, top_k: int = 4,
         expert_width: int = 1536, routed_scaling: float = 1.0,
         eps: float = 1e-5, rope_theta: float = 1e6, std: float = 0.02,
         router_std: float = 0.006, select_bias_std: float = 0.002,
         train_router: bool = True) -> NetParameter:
    """Layer ``i`` of ``num_layers`` is attention where ``i % 4 == 2`` and a
    short convolution elsewhere, dense below ``num_dense_layers``;
    ``layers_kept`` builds those published layers alone, under their
    published indices (``L0``, ``L2``, ...).  ``train_router=False`` keeps
    the routers and the selection bias as the seed made them and their
    scores' gradient out of the residual stream (``models/laguna.py`` says
    why a chip's share needs it); the bias has no gradient either way (it
    only chooses), so its ``lr_mult`` is 0 always: the balancing step that
    would move it between optimizer steps is not run.  The embedding is
    filled with ``std`` like every other matrix, not ``laguna``'s 1.0: it
    is the head too, and a token's own row, where it dominates the
    residual stream, is by far its own largest logit (at 1.0 a loss of
    1,240 at the seed, and steps that sent every token to the same experts
    within 40; PERF.md, PR 37)."""
    lo, hi = experts_held or (0, num_experts)
    kept = list(range(num_layers)) if layers_kept is None else list(
        layers_kept)
    layers = [
        java_data_layer("tokens_train", ["tokens"], Phase.TRAIN,
                        (train_batch, seq_len)),
        java_data_layer("tokens_test", ["tokens"], Phase.TEST,
                        (test_batch, seq_len)),
        layer("embed", "Embed", ["tokens"], ["x0"],
              param=[{"name": "embedding"}], embed_param={
                  "num_output": hidden, "input_dim": vocab,
                  "bias_term": False,
                  "weight_filler": gaussian(std)}),
    ]
    norm = {"rms_norm_param": {"eps": eps}}
    x = "x0"
    for n, i in enumerate(kept):
        p = f"L{i}"
        layers.append(layer(f"{p}/norm1", "RMSNorm", [x], [f"{p}/n1"],
                            **norm))
        if i % 4 == 2:
            layers.append(layer(
                f"{p}/attn", "Attention", [f"{p}/n1"], [f"{p}/a"],
                attention_param={
                    "num_heads": heads, "num_kv_heads": kv_heads,
                    "head_dim": hidden // heads, "rope_theta": rope_theta,
                    "gate": False, "qk_norm": True, "qk_norm_eps": eps,
                    "weight_filler": gaussian(std)}))
        else:
            layers.append(layer(
                f"{p}/conv", "ShortConv", [f"{p}/n1"], [f"{p}/a"],
                short_conv_param={
                    "kernel": conv_kernel, "weight_filler": gaussian(std),
                    "kernel_filler": gaussian(_TAP_STD)}))
        layers += [
            layer(f"{p}/res1", "Eltwise", [x, f"{p}/a"], [f"{p}/h"]),
            layer(f"{p}/norm2", "RMSNorm", [f"{p}/h"], [f"{p}/n2"], **norm),
        ]
        if i < num_dense_layers:
            layers.append(layer(
                f"{p}/mlp", "GatedMLP", [f"{p}/n2"], [f"{p}/m"],
                gated_mlp_param={"width": dense_width,
                                 "weight_filler": gaussian(std)}))
        else:
            frozen = {"lr_mult": 0.0}
            layers.append(layer(
                f"{p}/moe", "MixtureOfExperts", [f"{p}/n2"], [f"{p}/m"],
                param=[{} if train_router else frozen, {}, {}, {}, frozen],
                moe_param={
                    "num_experts": num_experts, "top_k": top_k,
                    "experts_held_lo": lo, "experts_held_hi": hi,
                    "expert_width": expert_width, "shared_width": 0,
                    "routed_scaling": routed_scaling, "norm_eps": 1e-6,
                    "select_bias": True,
                    "select_bias_filler": gaussian(select_bias_std),
                    "weight_filler": gaussian(std),
                    "router_filler": gaussian(router_std),
                    "router_column_norm": router_std * hidden ** 0.5,
                    "detach_router": not train_router}))
        x = f"x{n + 1}"
        layers.append(layer(f"{p}/res2", "Eltwise", [f"{p}/h", f"{p}/m"],
                            [x]))
    head = layer("lm_loss", "LMHeadLoss", ["xf", "tokens"], ["loss"],
                 param=[{"name": "embedding"}],
                 lm_head_param={"vocab": vocab, "transposed": True,
                                "weight_filler": gaussian(std)})
    head.loss_weight = [1.0]
    layers += [
        layer("embedding_norm", "RMSNorm", [x], ["xf"], **norm),
        head,
    ]
    return net_param("LFM2", layers)
