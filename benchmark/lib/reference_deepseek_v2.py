"""The plain reference for DeepSeek-V2 (``deepseek_v2``): forward pass, loss
and (through ``jax.grad``) gradients in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No kernels, no recomputation,
nothing imported from the program; ``highest``, ``_mm``, ``norm`` and
``rotate`` (YaRN's frequencies, halves paired) are ``reference_lm.py``'s,
which know no model.  It reads a configuration file
(``model``) and the weights as the program stores them, ``{layer:
[blobs]}`` under the builder's layer names, matrices ``[in, out]``.

Equations (DeepSeek-V2-Lite's ``config.json`` and the family's published
modelling; what they leave open is listed under ``assumed`` in the
configuration file).  ``norm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * w``.

- ``x = E[tokens]``; for each layer kept ``h = x + mla(norm(x))``, ``x = h +
  ffn(norm(h))``; ``logits = norm(x) W_head`` (untied).
- ``mla``, ``heads`` heads, no query compression (``q_lora_rank`` null):
  ``[q_nope_h | q_r_h] = x W_q`` (``qk_nope_head_dim``, ``qk_rope_head_dim``
  a head); ``[c | k_r] = x [W_dkv | W_kr]`` (the published
  ``kv_a_proj_with_mqa``; the program stores its two parts as two blobs);
  ``ĉ = norm(c)`` with ``γ_kv`` (``kv_a_layernorm``), ``k_r`` not
  normalised; ``[k_nope_h | v_h] = ĉ W_ukv`` (``kv_b_proj``, per head
  ``qk_nope_head_dim`` then ``v_head_dim``); ``q_r_h`` and the one ``k_r``,
  shared by all heads, rotated by position with YaRN's frequencies
  (``rope_scaling``: the blend of ``inv_freq / factor`` and ``inv_freq`` on
  the ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
  times over ``original_max_position_embeddings``, floor and ceiling
  taken) and cos and sin times ``mscale(factor, mscale) / mscale(factor,
  mscale_all_dim)`` (1 here); ``s_h = τ (q_nope_h k_nope_h^T + q_r_h
  k_r^T)`` with ``τ = (qk_nope_head_dim + qk_rope_head_dim)^-1/2 *
  mscale(factor, mscale_all_dim)^2``, ``mscale(f, m) = 0.1 m ln f + 1``;
  causal softmax; ``o_h = p v_h``; ``[o_1 .. o_heads] W_o``.
- ``ffn`` of a layer below ``first_k_dense_replace`` and of every expert:
  ``(silu(x W_1) * (x W_3)) W_2``, blobs W_1 (gate), W_3 (up), W_2 (down).
- ``ffn`` of an expert layer: ``p = softmax(x W_r)`` over all experts
  (``scoring_func``); the ``num_experts_per_tok`` largest (greedy, one
  group; the lower index on a tie); the weights are the chosen ``p``
  themselves (``norm_topk_prob`` false) times ``routed_scaling_factor``.
  The held experts ``[lo, hi)`` add ``weight * expert(x)`` for the tokens
  that chose them and what the absent ones would add is left out; the
  ``n_shared_experts`` shared experts of ``moe_intermediate_size`` are each
  added unweighted, computed one by one from their slices of the program's
  one shared blob of ``n_shared_experts`` times the width (its columns of
  ``W_1`` and ``W_3`` and rows of ``W_2``: a gated MLP of a concatenated
  width is the sum of the MLPs of its parts).  Where the configuration
  does not train its routers (``train_router`` false) the scores' gradient
  stops at the router's input.  Every held expert is computed for every
  token and masked: a dense loop, so that no dispatch can go wrong here.
- Loss: the mean over positions ``t < S - 1`` and sequences of the softmax
  cross-entropy of ``logits[t]`` against ``tokens[t + 1]``.

Departures, each a fixed relabelling of random weights: rotary pairs halves
(``x1 cos - x2 sin``, as ``transformers`` pairs them) where the published
modelling turns interleaved pairs, a fixed permutation of the rotary
columns of ``W_q`` and ``W_kr`` that a loader of published weights applies;
``kv_a_proj_with_mqa`` kept as ``W_dkv`` and ``W_kr``.  The sequence-level
auxiliary loss (``seq_aux``) moves only the routers, which the benchmark's
configuration does not train, and is left out.

Attention is computed a block of queries at a time so that one sequence of
8,192 positions at the published widths fits beside a training step's
state.  ``dtype`` rounds both operands of every matrix product to that type
first (and nothing else): the reference in a lower precision, which the
comparison must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .reference_lm import _mm, highest, norm, rotate  # noqa: F401  (highest)

QUERY_BLOCK = 256


def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


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
    experts = int(args.get("num_experts", published.get(
        "n_routed_experts", config["n_routed_experts"])))
    rope = config["rope_scaling"]
    nope = int(args.get("qk_nope_head_dim", config["qk_nope_head_dim"]))
    rot = int(args.get("qk_rope_head_dim", config["qk_rope_head_dim"]))
    m_all = mscale(rope["factor"], rope["mscale_all_dim"])
    return {
        "layers": [
            {"name": f"L{i}",
             "ffn": "mlp" if i < config["first_k_dense_replace"] else "moe"}
            for i in args.get("layers_kept", range(total))],
        "heads": int(args.get("heads", config["num_attention_heads"])),
        "nope": nope, "rope_dim": rot,
        "v": int(args.get("v_head_dim", config["v_head_dim"])),
        # ``reference_lm.rotate``'s terms: YaRN over the whole rotary key
        "rope": {"rope_type": "yarn", "partial_rotary_factor": 1,
                 "rope_theta": float(config["rope_theta"]),
                 **{k: float(rope[k]) for k in (
                     "factor", "beta_fast", "beta_slow",
                     "original_max_position_embeddings")},
                 "attention_factor": mscale(rope["factor"], rope["mscale"])
                 / m_all},
        "tau": (nope + rot) ** -0.5 * m_all * m_all,
        "eps": float(config["rms_norm_eps"]),
        "top_k": int(args.get("top_k", config["num_experts_per_tok"])),
        "held": tuple(args.get("experts_held", (0, experts))),
        "shared": int(config["n_shared_experts"]),
        "scaling": float(config["routed_scaling_factor"]),
        "train_router": bool(args.get("train_router", True)),
    }


def mla(x, blobs, m: dict, dtype=None):
    wq, wdkv, wkr, gamma, wukv, wo = blobs
    s, heads, nope, v_dim = x.shape[0], m["heads"], m["nope"], m["v"]
    q = _mm(x, wq, dtype).reshape(s, heads, nope + m["rope_dim"])
    q_nope, q_r = q[..., :nope], rotate(q[..., nope:], m["rope"])
    c = norm(_mm(x, wdkv, dtype), gamma, m["eps"])
    k_r = rotate(_mm(x, wkr, dtype)[:, None], m["rope"])[:, 0]  # [S, rope]
    kv = _mm(c, wukv, dtype).reshape(s, heads, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_nope = k_nope.transpose(1, 2, 0)                          # [H, n, S]
    v = v.transpose(1, 0, 2)                                    # [H, S, v]
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    blocked = lambda t: jnp.pad(t, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, heads, t.shape[-1]).transpose(0, 2, 1, 3)   # [n,H,b,d]
    starts = jnp.arange((s + pad) // block) * block

    def one_block(args):
        qn, qr, start = args
        scores = m["tau"] * (_mm(qn, k_nope, dtype)             # [H, b, S]
                             + _mm(qr, k_r.T, dtype))
        i = start + jnp.arange(block)[:, None]
        seen = jnp.arange(s)[None, :] <= i
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _mm(p, v, dtype)                                 # [H, b, v]

    out = jax.lax.map(one_block, (blocked(q_nope), blocked(q_r), starts))
    out = out.transpose(0, 2, 1, 3).reshape(-1, heads * v_dim)[:s]
    return _mm(out, wo, dtype)


def mlp(x, w1, w3, w2, dtype=None):
    return _mm(jax.nn.silu(_mm(x, w1, dtype)) * _mm(x, w3, dtype), w2, dtype)


def route(x, wr, m: dict, dtype=None):
    """(weights [S, top_k], experts [S, top_k])."""
    if not m.get("train_router", True):
        x = jax.lax.stop_gradient(x)
    p = jax.nn.softmax(_mm(x, wr, dtype), axis=-1)
    order = jnp.argsort(-p, axis=-1, stable=True)[:, :m["top_k"]]
    return jnp.take_along_axis(p, order, axis=-1) * m["scaling"], order


def moe(x, blobs, m: dict, dtype=None):
    wr, w1, w3, w2, s1, s3, s2 = blobs
    weight, chosen = route(x, wr, m, dtype)
    lo = m["held"][0]

    def add_expert(acc, args):
        e, g, u, d = args
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return acc + w_e[:, None] * mlp(x, g, u, d, dtype), None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                             (lo + jnp.arange(w1.shape[0]), w1, w3, w2))
    width = s1.shape[1] // m["shared"]
    for j in range(m["shared"]):
        part = slice(j * width, (j + 1) * width)
        routed = routed + mlp(x, s1[:, part], s3[:, part], s2[part], dtype)
    return routed


def hidden(params: dict, tokens, m: dict, dtype=None, sent=None):
    """tokens [S] -> the normalised hidden states the head reads.  A dict
    ``sent`` is filled with the rows each expert layer's choice sends each
    held expert."""
    f32 = lambda name: [jnp.asarray(b, jnp.float32) for b in params[name]]
    x = f32("embed")[0][tokens]
    for layer in m["layers"]:
        p = layer["name"]
        h = x + mla(norm(x, f32(f"{p}/norm1")[0], m["eps"]), f32(f"{p}/attn"),
                    m, dtype)
        n2 = norm(h, f32(f"{p}/norm2")[0], m["eps"])
        if layer["ffn"] == "moe" and sent is not None:
            wr, w1, *_ = f32(f"{p}/moe")
            _, chosen = route(n2, wr, m, dtype)
            sent[f"{p}/moe"] = jnp.sum(
                chosen[..., None] == m["held"][0] + jnp.arange(w1.shape[0]),
                axis=(0, 1))
        x = h + (mlp(n2, *f32(f"{p}/mlp"), dtype) if layer["ffn"] == "mlp"
                 else moe(n2, f32(f"{p}/moe"), m, dtype))
    return norm(x, f32("final_norm")[0], m["eps"])


def expert_rows(params: dict, tokens, m: dict, dtype=None) -> dict:
    """tokens [S] -> {expert layer: rows its choice sends each held
    expert}."""
    sent = {}
    hidden(params, tokens, m, dtype, sent)
    return sent


def logits(params: dict, tokens, m: dict, dtype=None):
    """tokens [S] -> logits [S, vocab]."""
    return _mm(hidden(params, tokens, m, dtype),
               jnp.asarray(params["lm_loss"][0], jnp.float32), dtype)


def loss(params: dict, tokens, m: dict, dtype=None):
    """tokens [N, S] -> the mean next-token cross-entropy."""
    total = 0.0
    for seq in tokens:
        logp = jax.nn.log_softmax(logits(params, seq, m, dtype)[:-1], -1)
        total = total - jnp.sum(
            jnp.take_along_axis(logp, seq[1:, None], axis=-1))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))
