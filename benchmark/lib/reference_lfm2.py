"""The plain reference for LFM2 (``lfm2_moe``): forward pass, loss and
(through ``jax.grad``) gradients in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No kernels, no recomputation,
nothing imported from the program; ``highest``, ``_mm`` and ``norm`` are
``reference_lm.py``'s, which know no model.  It reads a configuration file
(``model``) and the weights as the program stores them, ``{layer:
[blobs]}`` under the builder's layer names, matrices ``[in, out]``.

Equations (LFM2-24B-A2B's ``config.json``; what it leaves open is listed
under ``assumed`` in the configuration file).  ``norm(x) = x *
rsqrt(mean(x^2) + norm_eps) * w`` throughout.

- ``x = E[tokens]``; for each layer kept ``h = x + op(norm(x))``, ``x = h +
  ffn(norm(h))``; ``logits = norm(x) E^T``: the head is the embedding.
- ``op`` of a ``conv`` layer: ``[B, C, z] = x W_in``, thirds in that order
  (``W_in`` is stored ``[hidden, 3, hidden]``); ``u = B * z``; ``c_t =
  w[:, 2] u_t + w[:, 1] u_{t-1} + w[:, 0] u_{t-2}`` with zeros before the
  sequence (a depthwise causal convolution of ``conv_L_cache`` taps,
  ``w [hidden, taps]``); ``(C * c) W_out``.  No activation, no bias.
- ``op`` of a ``full_attention`` layer: ``q, k, v = x W_q, x W_k, x W_v``
  without bias, heads of ``hidden / heads``; query head ``h`` reads
  key/value head ``h // group``.  Each head of q and of k is normalised
  (``norm`` over the head, one weight of ``head_dim`` for q and one for k)
  and then rotated over the whole head, halves paired as ``transformers``
  pairs them (``x1 cos - x2 sin, x2 cos + x1 sin``), angle ``position *
  theta^(-2i/head_dim)``.  ``scores = q k^T / sqrt(head_dim)``; key ``j``
  is seen from ``i`` if ``j <= i``; softmax; weighted sum of v; ``W_o``.
  No gate, no window.
- ``ffn`` of a layer below ``num_dense_layers`` and every expert:
  ``(silu(x W_1) * (x W_3)) W_2``, blobs in the order W_1 (gate), W_3
  (up), W_2 (down).
- ``ffn`` of an expert layer: ``s = sigmoid(x W_r)`` over all experts; the
  ``top_k`` experts with the largest ``s + b`` (the lower index on a tie);
  weights ``s_e / (sum of the chosen s + 1e-6)`` times
  ``routed_scaling_factor``: the bias chooses and does not weigh.  The held
  experts ``[lo, hi)`` add ``weight * expert(x)`` for the tokens that chose
  them and what the absent ones would add is left out.  No shared expert.
  Where the configuration does not train its routers (``train_router``
  false) the scores' gradient stops at the router's input.  Every held
  expert is computed for every token and masked: a dense loop, so that no
  dispatch can go wrong here.
- Loss: the mean over positions ``t < S - 1`` and sequences of the softmax
  cross-entropy of ``logits[t]`` against ``tokens[t + 1]``.

Attention is computed a block of queries at a time so that one sequence of
8,192 positions at the published widths fits beside a training step's
state; every other part of a sequence fits whole.  ``dtype`` rounds both
operands of every matrix product to that type first (and nothing else):
the reference in a lower precision, which the comparison must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference_lm import _mm, highest, norm  # noqa: F401  (highest: callers)

QUERY_BLOCK = 256
TOPK_NORM_EPS = 1e-6


def model(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file: its
    top-level keys are the model's ``config.json`` as run here,
    ``published`` holds what the cut changed, and ``builder_args`` which
    published layers are kept, which experts are held, and what a test
    shrinks."""
    args = config.get("builder_args", {})
    published = config.get("published", {})
    total = int(args.get("num_layers", published.get(
        "num_hidden_layers", config["num_hidden_layers"])))
    dense = int(args.get("num_dense_layers", published.get(
        "num_dense_layers", config["num_dense_layers"])))
    experts = int(args.get("num_experts", published.get(
        "num_experts", config["num_experts"])))
    return {
        "layers": [
            {"name": f"L{i}",
             "op": ("attn" if config["layer_types"][i] == "full_attention"
                    else "conv"),
             "ffn": "mlp" if i < dense else "moe"}
            for i in args.get("layers_kept", range(total))],
        "heads": int(args.get("heads", config["num_attention_heads"])),
        "kv_heads": int(args.get("kv_heads", config["num_key_value_heads"])),
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "eps": float(config["norm_eps"]),
        "top_k": int(args.get("top_k", config["num_experts_per_tok"])),
        "held": tuple(args.get("experts_held", (0, experts))),
        "scaling": float(config["routed_scaling_factor"]),
        "train_router": bool(args.get("train_router", True)),
    }


def rotate(x, theta: float):
    """x [S, heads, head_dim], rotated over the whole head."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def short_conv(x, blobs, dtype=None):
    w_in, taps, w_out = blobs
    s, h = x.shape
    bcz = _mm(x, w_in.reshape(h, 3 * h), dtype)
    b, c, z = bcz[:, :h], bcz[:, h:2 * h], bcz[:, 2 * h:]
    u = b * z
    kernel = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, h), u.dtype), u])
    conv = sum(padded[j:j + s] * taps[:, j] for j in range(kernel))
    return _mm(c * conv, w_out, dtype)


def attention(x, blobs, m: dict, dtype=None):
    wq, wk, wv, wo, q_weight, k_weight = blobs
    s, heads, kv = x.shape[0], m["heads"], m["kv_heads"]
    d = wq.shape[1] // heads
    q = rotate(norm(_mm(x, wq, dtype).reshape(s, heads, d), q_weight,
                    m["eps"]), m["theta"])
    k = rotate(norm(_mm(x, wk, dtype).reshape(s, kv, d), k_weight,
                    m["eps"]), m["theta"])
    v = _mm(x, wv, dtype).reshape(s, kv, d)
    k = jnp.repeat(k, heads // kv, axis=1).transpose(1, 2, 0)   # [H, d, S]
    v = jnp.repeat(v, heads // kv, axis=1).transpose(1, 0, 2)   # [H, S, d]
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, heads, d).transpose(0, 2, 1, 3)              # [n,H,b,d]
    starts = jnp.arange(qb.shape[0]) * block

    def one_block(args):
        qi, start = args
        scores = _mm(qi, k, dtype) / math.sqrt(d)               # [H, b, S]
        i = start + jnp.arange(block)[:, None]
        seen = jnp.arange(s)[None, :] <= i
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _mm(p, v, dtype)                                 # [H, b, d]

    out = jax.lax.map(one_block, (qb, starts))                  # [n,H,b,d]
    out = out.transpose(0, 2, 1, 3).reshape(-1, heads * d)[:s]
    return _mm(out, wo, dtype)


def mlp(x, w1, w3, w2, dtype=None):
    return _mm(jax.nn.silu(_mm(x, w1, dtype)) * _mm(x, w3, dtype), w2, dtype)


def route(x, wr, bias, m: dict, dtype=None):
    """(weights [S, top_k], experts [S, top_k])."""
    if not m.get("train_router", True):
        x = jax.lax.stop_gradient(x)
    scores = jax.nn.sigmoid(_mm(x, wr, dtype))
    order = jnp.argsort(-(scores + bias), axis=-1, stable=True)[
        :, :m["top_k"]]
    top = jnp.take_along_axis(scores, order, axis=-1)
    weight = top / (jnp.sum(top, -1, keepdims=True) + TOPK_NORM_EPS)
    return weight * m["scaling"], order


def moe(x, blobs, m: dict, dtype=None):
    wr, w1, w3, w2, bias = blobs
    weight, chosen = route(x, wr, bias, m, dtype)
    lo = m["held"][0]

    def add_expert(acc, args):
        e, g, u, d = args
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return acc + w_e[:, None] * mlp(x, g, u, d, dtype), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (lo + jnp.arange(w1.shape[0]), w1, w3, w2))
    return routed


def hidden(params: dict, tokens, m: dict, dtype=None, sent=None):
    """tokens [S] -> the normalised hidden states the head reads.  A dict
    ``sent`` is filled with the rows each expert layer's choice sends each
    held expert."""
    f32 = lambda name: [jnp.asarray(b, jnp.float32) for b in params[name]]
    x = f32("embed")[0][tokens]
    for layer in m["layers"]:
        p = layer["name"]
        n1 = norm(x, f32(f"{p}/norm1")[0], m["eps"])
        h = x + (attention(n1, f32(f"{p}/attn"), m, dtype)
                 if layer["op"] == "attn"
                 else short_conv(n1, f32(f"{p}/conv"), dtype))
        n2 = norm(h, f32(f"{p}/norm2")[0], m["eps"])
        if layer["ffn"] == "moe" and sent is not None:
            wr, w1, *_, bias = f32(f"{p}/moe")
            _, chosen = route(n2, wr, bias, m, dtype)
            sent[f"{p}/moe"] = jnp.sum(
                chosen[..., None] == m["held"][0] + jnp.arange(w1.shape[0]),
                axis=(0, 1))
        x = h + (mlp(n2, *f32(f"{p}/mlp"), dtype) if layer["ffn"] == "mlp"
                 else moe(n2, f32(f"{p}/moe"), m, dtype))
    return norm(x, f32("embedding_norm")[0], m["eps"])


def expert_rows(params: dict, tokens, m: dict, dtype=None) -> dict:
    """tokens [S] -> {expert layer: rows its choice sends each held
    expert}: what ``score + bias`` chooses, which the weights do not show."""
    sent = {}
    hidden(params, tokens, m, dtype, sent)
    return sent


def logits(params: dict, tokens, m: dict, dtype=None):
    """tokens [S] -> logits [S, vocab]: the head is the embedding."""
    embedding = jnp.asarray(params["embed"][0], jnp.float32)
    return _mm(hidden(params, tokens, m, dtype), embedding.T, dtype)


def loss(params: dict, tokens, m: dict, dtype=None):
    """tokens [N, S] -> the mean next-token cross-entropy."""
    total = 0.0
    for seq in tokens:
        logp = jax.nn.log_softmax(logits(params, seq, m, dtype)[:-1], -1)
        total = total - jnp.sum(
            jnp.take_along_axis(logp, seq[1:, None], axis=-1))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
