"""Plain reference of a sparse-expert decoder-only LM's loss, written from
the architecture's description (Mellum2's block): token embedding; per
layer, pre-norm grouped-query self-attention whose mask and rotary
embedding (rotate-half form) follow the layer's entry in
``layer_types``, then a pre-norm sparse-expert SwiGLU FFN, each added to
the residual stream; a final norm, an untied unembedding and the mean
next-token cross-entropy over the vocabulary slice the configuration
holds.

- ``sliding_attention`` layers: causal, each query sees its last
  ``sliding_window`` keys; RoPE ``default`` at its section's θ.
- ``full_attention`` layers: causal over every key; RoPE ``yarn`` as HF
  defines it (frequencies blended between θ^(-2i/d) and that over
  ``factor`` on a linear ramp between the dimensions whose wavelengths
  fit ``beta_fast`` and ``beta_slow`` turns in
  ``original_max_position_embeddings``, floor and ceil of the ends), its
  ``attention_factor`` multiplying cos and sin.
- Experts: router logits in float32 over all ``published.num_experts``,
  softmax, the top ``num_experts_per_tok``, their gates renormalised
  (``norm_topk_prob``).  The layer holds experts [0, ``num_experts``):
  each of them runs on every token, weighted by its renormalised gate
  where the token chose it and by 0 where not; the absent experts'
  choices add nothing (this chip's share of an expert-parallel layer).

Departures from the source, as the configuration lists them under
``assumed``: the token embedding multiplied by sqrt(d_model), which
the source config does not name; RMSNorm's scale stored as (scale - 1),
eps ``rms_norm_eps``; no q/k norm, no router auxiliary loss, no
multi-token-prediction head, no biases.  ``params`` uses the program's
tree layout: layer i is slot i mod p of period i div p (p the layer
pattern's period), stacked on a leading period axis under
``periods/slot<j>``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.numerics import HIGHEST


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope_frequencies(head_dim, rope):
    """(inverse frequencies (hd/2,), factor on cos and sin) of one
    ``rope_parameters`` section."""
    theta = rope["rope_theta"]
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    if rope["rope_type"] == "default":
        return inv, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not in this reference")
    factor, ctx = rope["factor"], rope["original_max_position_embeddings"]

    def dim(turns):
        return head_dim * math.log(ctx / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), head_dim - 1)
    if high == low:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp  # share of the extrapolated (unscaled) frequency
    inv = (inv / factor) * (1.0 - keep) + inv * keep
    scale = rope.get("attention_factor") or (0.1 * math.log(factor) + 1.0)
    return inv, scale


def _rope(x, rope):
    """x (B, S, H, hd): rotate the two halves of each head by position."""
    S, hd = x.shape[1], x.shape[-1]
    inv, scale = rope_frequencies(hd, rope)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos = scale * jnp.cos(ang)[None, :, None, :]
    sin = scale * jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, x, kind, cfg, num):
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // Hkv
    rope = cfg["rope_parameters"][kind]
    q = num.act(_mm("bsd,dhk->bshk", x, num.act(p["wq"])))
    k = num.act(_mm("bsd,dhk->bshk", x, num.act(p["wk"])))
    v = num.act(_mm("bsd,dhk->bshk", x, num.act(p["wv"])))
    q = num.act(_rope(q, rope))
    k = num.act(_rope(k, rope))
    # query head h reads key/value head h // G
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = _mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
    pos = jnp.arange(S)
    keep = pos[:, None] >= pos[None, :]
    if kind == "sliding_attention":
        keep &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
    s = jnp.where(keep[None, None], s, -1e30)
    prob = num.act(jax.nn.softmax(s, axis=-1))
    out = num.act(_mm("bhqs,bshk->bqhk", prob, v))
    return num.act(_mm("bshk,hkd->bsd", out, num.act(p["wo"])))


def moe_ffn(p, x, cfg, num):
    """Every held expert on every token, weighted by its renormalised
    top-k gate (0 where the token did not choose it)."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    held = cfg["num_experts"]
    logits = _mm("td,de->te", xt, p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jnp.where(chosen[..., None] == jnp.arange(held), gates[..., None], 0.0),
                     axis=1)  # (T, held)
    g = num.act(_mm("td,edf->etf", xt, num.act(p["w_gate"])))
    u = num.act(_mm("td,edf->etf", xt, num.act(p["w_up"])))
    h = num.act(num.act(jax.nn.silu(g)) * u)
    y = num.act(_mm("etf,efd->etd", h, num.act(p["w_down"])))
    return num.act(jnp.einsum("te,etd->td", weight, y).reshape(B, S, D))


def loss(params, batch, num, cfg):
    tokens, labels = batch["x"], batch["y"]
    D, eps, L = cfg["hidden_size"], cfg["rms_norm_eps"], cfg["num_hidden_layers"]
    if not cfg["norm_topk_prob"]:
        raise ValueError("this reference renormalises the top-k gates (norm_topk_prob)")
    period = len(params["periods"])
    h = num.act(num.act(params["embed"][tokens]) * math.sqrt(D))
    for i in range(L):
        lp = jax.tree_util.tree_map(lambda a: a[i // period],
                                    params["periods"][f"slot{i % period}"])
        kind = cfg["layer_types"][i]
        h = num.act(h + _attention(lp["attn"], num.act(_rmsnorm(h, lp["norm1"], eps)), kind, cfg,
                                   num))
        h = num.act(h + moe_ffn(lp["moe"], num.act(_rmsnorm(h, lp["norm2"], eps)), cfg, num))
    h = num.act(_rmsnorm(h, params["final_norm"], eps))
    logits = num.act(_mm("bsd,dv->bsv", h, num.act(params["unembed"])))
    logz = jax.nn.logsumexp(logits, axis=-1)
    label = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - label)
