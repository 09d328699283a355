"""Plain reference of a dense decoder-only LM's loss, written from the
architecture's description: token embedding scaled by sqrt(d_model);
per layer, pre-norm grouped-query self-attention with rotary position
embeddings (rotate-half form) and a causal sliding window, then a
pre-norm GELU (tanh form) MLP, each added to the residual stream; a final
norm, an untied unembedding and the mean next-token cross-entropy.

Norms are RMSNorm with the scale stored as (scale - 1) and eps 1e-6, and
the linears carry no bias (the configuration lists both under
``assumed``).  ``params`` uses the program's tree layout: the layer
weights are stacked on a leading layer axis under ``periods/slot0``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.numerics import HIGHEST

EPS = 1e-6


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + scale)


def _rope(x, theta):
    """x (B, S, H, hd): rotate the two halves of each head by position."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(p, x, cfg, num):
    B, S, _ = x.shape
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    G = H // Hkv
    q = num.act(_mm("bsd,dhk->bshk", x, num.act(p["wq"])))
    k = num.act(_mm("bsd,dhk->bshk", x, num.act(p["wk"])))
    v = num.act(_mm("bsd,dhk->bshk", x, num.act(p["wv"])))
    q = num.act(_rope(q, cfg["rope_theta"]))
    k = num.act(_rope(k, cfg["rope_theta"]))
    # query head h reads key/value head h // G
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    s = _mm("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
    pos = jnp.arange(S)
    keep = (pos[:, None] >= pos[None, :]) & (pos[:, None] - pos[None, :] < cfg["sliding_window"])
    s = jnp.where(keep[None, None], s, -1e30)
    prob = num.act(jax.nn.softmax(s, axis=-1))
    out = num.act(_mm("bhqs,bshk->bqhk", prob, v))
    return num.act(_mm("bshk,hkd->bsd", out, num.act(p["wo"])))


def _mlp(p, x, num):
    u = num.act(_mm("bsd,df->bsf", x, num.act(p["w_up"])))
    return num.act(_mm("bsf,fd->bsd", num.act(jax.nn.gelu(u, approximate=True)),
                       num.act(p["w_down"])))


def loss(params, batch, num, cfg):
    tokens, labels = batch["x"], batch["y"]
    D = cfg["hidden_size"]
    h = num.act(num.act(params["embed"][tokens]) * math.sqrt(D))
    layers = params["periods"]["slot0"]
    for i in range(cfg["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        h = num.act(h + _attention(lp["attn"], num.act(_rmsnorm(h, lp["norm1"])), cfg, num))
        h = num.act(h + _mlp(lp["mlp"], num.act(_rmsnorm(h, lp["norm2"])), num))
    h = num.act(_rmsnorm(h, params["final_norm"]))
    logits = num.act(_mm("bsd,dv->bsv", h, num.act(params["unembed"])))
    logz = jax.nn.logsumexp(logits, axis=-1)
    label = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - label)
