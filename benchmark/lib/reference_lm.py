"""The plain reference for decoder-only language models: forward pass, loss
and (through ``jax.grad``) gradients in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No kernels, no recomputation,
nothing imported from the program.  It reads a configuration file
(``model``) and the weights as the program stores them, ``{layer: [matrices stored [in, out]]}`` under the
builder's layer names.

Equations (Laguna XS.2's ``config.json``; what it leaves open is listed
under ``assumed`` in the configuration file):

- ``x = E[tokens]``; for each layer ``h = x + attn(norm(x))``,
  ``x = h + mlp(norm(h))``; ``logits = norm(x) W_head``;
  ``norm(x) = x * rsqrt(mean(x^2) + eps) * w``.
- Attention: ``q, k, v = x W_q, x W_k, x W_v`` without bias, heads of
  ``head_dim``; query head ``h`` reads key/value head ``h // group``.
  Rotary on the first ``rotary_dim`` dimensions of q and k, halves paired as
  ``transformers`` pairs them (``x1 cos - x2 sin, x2 cos + x1 sin``), angle
  ``position * inv_freq``; ``inv_freq = theta^(-2i/rotary_dim)``, and with
  YaRN the blend ``inv_freq / factor * (1 - keep) + inv_freq * keep`` where
  ``keep`` falls linearly from 1 to 0 between the dimensions that turn
  ``beta_fast`` and ``beta_slow`` times in ``original_max_position``
  positions (floor and ceiling taken), and cos and sin are multiplied by
  ``attention_factor``.  ``scores = q k^T / sqrt(head_dim)``; key ``j`` is
  seen from ``i`` if ``j <= i``, and in a sliding layer only if also
  ``i - j < window``; softmax; weighted sum of v.  Each head's output is
  multiplied by ``sigmoid(x W_g)[head]``, then ``W_o``.
- MLP and every expert: ``(silu(x W_gate) * (x W_up)) W_down``.
- Expert layer: ``scores = sigmoid(x W_r)`` over all experts; the ``top_k``
  largest (the lower index on a tie), their scores divided by their sum,
  times ``routed_scaling``; the held experts ``[lo, hi)`` add
  ``weight * expert(x)`` for the tokens that chose them, what the absent
  ones would add is left out, and the shared expert is added unweighted.
  Where the configuration does not train its routers (``train_router``
  false) the scores' gradient stops at the router's input.
  Every held expert is computed for every token and masked: a dense loop,
  so that no dispatch can go wrong here.
- Loss: the mean over positions ``t < S - 1`` and sequences of the softmax
  cross-entropy of ``logits[t]`` against ``tokens[t + 1]``.

Attention is computed a block of queries at a time so that one sequence of
8,192 positions at the published widths fits beside a training step's
state.  ``dtype`` rounds both operands of every matrix product to
that type first (and nothing else): the reference in a lower precision,
which the comparison in ``check_lm`` must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


def model(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file: its
    top-level keys are the model's ``config.json`` as run here, ``published``
    holds what the cut changed, and ``builder_args`` what a test shrinks."""
    args = config.get("builder_args", {})
    rope = config["rope_parameters"]
    kinds = [t.split("_")[0] for t in config["layer_types"]]
    heads = args.get("heads")
    experts = int(args.get("num_experts",
                           config["published"]["num_experts"]))
    full = dict(rope["full_attention"])
    full["original_max_position_embeddings"] = int(args.get(
        "yarn_original_length", full["original_max_position_embeddings"]))
    return {
        "layers": [
            {"kind": kinds[i],
             "heads": int(heads[kinds[i]] if heads else
                          config["num_attention_heads_per_layer"][i]),
             "mlp": config["mlp_layer_types"][i]}
            for i in range(int(config["num_hidden_layers"]))],
        "kv_heads": int(args.get("kv_heads", config["num_key_value_heads"])),
        "head_dim": int(args.get("head_dim", config["head_dim"])),
        "window": int(args.get("window", config["sliding_window"])),
        "eps": float(config["rms_norm_eps"]),
        "experts": experts,
        "top_k": int(args.get("top_k", config["num_experts_per_tok"])),
        "held": tuple(args.get("experts_held", (0, experts))),
        "scaling": float(config["moe_routed_scaling_factor"]),
        "train_router": bool(args.get("train_router", True)),
        "rope": {"full": full, "sliding": rope["sliding_attention"]},
    }


def _mm(a, b, dtype):
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return jnp.matmul(a, b)


def inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    dim = int(head_dim * rope["partial_rotary_factor"])
    base = float(rope["rope_theta"])
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope["rope_type"] == "default":
        return 1.0 / freqs
    length = rope["original_max_position_embeddings"]

    def turns_at(n):     # the dimension that turns n times in `length`
        return dim * math.log(length / (n * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(turns_at(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (1.0 / (rope["factor"] * freqs)) * (1 - keep) + (
        1.0 / freqs) * keep


def rotate(x, rope: dict):
    """x [S, heads, head_dim]."""
    f = inv_freq(rope, x.shape[-1])
    half = len(f)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * jnp.asarray(
        f, jnp.float32)[None, :]
    factor = float(rope.get("attention_factor", 1.0))
    cos, sin = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang)
                                                  * factor)[:, None]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., 2 * half:]], axis=-1)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def attention(x, blobs, layer: dict, m: dict, dtype=None):
    wq, wk, wv, wg, wo = blobs
    s, d, heads, kv = x.shape[0], m["head_dim"], layer["heads"], m["kv_heads"]
    rope = m["rope"][layer["kind"]]
    q = rotate(_mm(x, wq, dtype).reshape(s, heads, d), rope)
    k = rotate(_mm(x, wk, dtype).reshape(s, kv, d), rope)
    v = _mm(x, wv, dtype).reshape(s, kv, d)
    k = jnp.repeat(k, heads // kv, axis=1).transpose(1, 2, 0)   # [H, d, S]
    v = jnp.repeat(v, heads // kv, axis=1).transpose(1, 0, 2)   # [H, S, d]
    window = m["window"] if layer["kind"] == "sliding" else s
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, block, heads, d).transpose(0, 2, 1, 3)              # [n,H,b,d]
    starts = jnp.arange(qb.shape[0]) * block

    def one_block(args):
        qi, start = args
        scores = _mm(qi, k, dtype) / math.sqrt(d)               # [H, b, S]
        i = start + jnp.arange(block)[:, None]
        j = jnp.arange(s)[None, :]
        seen = (j <= i) & (i - j < window)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _mm(p, v, dtype)                                 # [H, b, d]

    out = jax.lax.map(one_block, (qb, starts))                  # [n,H,b,d]
    out = out.transpose(0, 2, 1, 3).reshape(-1, heads, d)[:s]
    out = out * jax.nn.sigmoid(_mm(x, wg, dtype))[..., None]
    return _mm(out.reshape(s, heads * d), wo, dtype)


def mlp(x, wg, wu, wd, dtype=None):
    return _mm(jax.nn.silu(_mm(x, wg, dtype)) * _mm(x, wu, dtype), wd, dtype)


def route(x, wr, m: dict, dtype=None):
    """(weights [S, top_k], experts [S, top_k])."""
    if not m.get("train_router", True):
        x = jax.lax.stop_gradient(x)
    scores = jax.nn.sigmoid(_mm(x, wr, dtype))
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :m["top_k"]]
    top = jnp.take_along_axis(scores, order, axis=-1)
    return top / jnp.sum(top, -1, keepdims=True) * m["scaling"], order


def moe(x, blobs, m: dict, dtype=None):
    wr, eg, eu, ed, sg, su, sd = blobs
    weight, chosen = route(x, wr, m, dtype)
    lo = m["held"][0]

    def add_expert(acc, args):
        e, g, u, d = args
        w_e = jnp.sum(jnp.where(chosen == e, weight, 0.0), axis=-1)
        return acc + w_e[:, None] * mlp(x, g, u, d, dtype), None

    routed, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (lo + jnp.arange(eg.shape[0]), eg, eu, ed))
    return routed + mlp(x, sg, su, sd, dtype)


def logits(params: dict, tokens, m: dict, dtype=None):
    """tokens [S] -> logits [S, vocab]."""
    f32 = lambda name: [jnp.asarray(b, jnp.float32) for b in params[name]]
    x = f32("embed")[0][tokens]
    for i, layer in enumerate(m["layers"]):
        p = f"L{i}"
        h = x + attention(norm(x, f32(f"{p}/norm1")[0], m["eps"]),
                          f32(f"{p}/attn"), layer, m, dtype)
        n2 = norm(h, f32(f"{p}/norm2")[0], m["eps"])
        x = h + (mlp(n2, *f32(f"{p}/mlp"), dtype) if layer["mlp"] == "dense"
                 else moe(n2, f32(f"{p}/moe"), m, dtype))
    return _mm(norm(x, f32("final_norm")[0], m["eps"]),
               f32("lm_loss")[0], dtype)


def loss(params: dict, tokens, m: dict, dtype=None):
    """tokens [N, S] -> the mean next-token cross-entropy."""
    total = 0.0
    for seq in tokens:
        logp = jax.nn.log_softmax(logits(params, seq, m, dtype)[:-1], -1)
        total = total - jnp.sum(
            jnp.take_along_axis(logp, seq[1:, None], axis=-1))
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def highest(fn):
    """``fn`` jitted under the matrix precision the reference is defined
    at: on a TPU float32 products otherwise run in bfloat16 passes."""
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return jax.jit(run)
