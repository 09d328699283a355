"""Layer zoo shared by every architecture family.

Pure functions over param pytrees.  Conventions:

* activations (B, S, D); attention heads (B, S, H, hd)
* norms and softmax accumulate in f32 regardless of activation dtype
* every layer takes ``parallel`` (a ParallelContext or None); with a mesh it
  applies sharding constraints / shard_map, otherwise it is plain jnp.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, YarnRope
from repro.utils.compat import shard_map

# ----------------------------------------------------------------------
# parallel context
# ----------------------------------------------------------------------


@dataclass(frozen=True)
class ParallelContext:
    """Mesh + logical axis names.  ``data_axes`` may be ("pod","data")."""

    mesh: Any
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]


def shard(x, spec: Optional[P], parallel: Optional[ParallelContext]):
    if parallel is None or spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, jax.sharding.NamedSharding(parallel.mesh, spec))


# ----------------------------------------------------------------------
# initializers
# ----------------------------------------------------------------------


def dense_init(rng, shape, in_axis_size: Optional[int] = None, dtype=jnp.float32):
    """Scaled normal init: std = 1/sqrt(fan_in)."""
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (std * jax.random.normal(rng, shape, dtype=jnp.float32)).astype(dtype)


def embed_init(rng, shape, dtype=jnp.float32):
    return (0.02 * jax.random.normal(rng, shape, dtype=jnp.float32)).astype(dtype)


# ----------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def init_rmsnorm(d: int, dtype=jnp.float32):
    return jnp.zeros((d,), dtype=dtype)  # stored as (scale - 1)


# ----------------------------------------------------------------------
# rotary embeddings
# ----------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float, yarn: Optional[YarnRope] = None):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    freqs = 1.0 / (theta**exponents)  # (hd/2,)
    if yarn is None:
        return freqs
    low, high = yarn_ramp(head_dim, theta, yarn)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


def yarn_ramp(head_dim: int, theta: float, yarn: YarnRope) -> Tuple[float, float]:
    """YaRN's ramp ends (HF ``truncate``: floor and ceil): below ``low`` a
    dimension's wavelength fits more than ``beta_fast`` turns in the
    original context and keeps its frequency; from ``high`` on, fewer
    than ``beta_slow``, and it is interpolated (÷ factor)."""

    def dim(turns):
        ctx = yarn.original_max_position_embeddings
        return head_dim * math.log(ctx / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(yarn.beta_fast)), 0)
    high = min(math.ceil(dim(yarn.beta_slow)), head_dim - 1)
    return low, (high + 0.001 if high == low else high)


def apply_rope(x, positions, theta: float, yarn: Optional[YarnRope] = None):
    """x: (B, S, H, hd); positions: (S,) or (B, S) int32.  With ``yarn``
    the frequencies are YaRN's and cos and sin carry its attention
    factor."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta, yarn)  # (hd/2,)
    pos = positions.astype(jnp.float32)
    angles = pos[..., None] * freqs  # (S, hd/2) or (B, S, hd/2)
    if angles.ndim == 2:  # (S, hd/2) -> (1, S, 1, hd/2)
        angles = angles[None, :, None, :]
    else:  # (B, S, hd/2) -> (B, S, 1, hd/2)
        angles = angles[:, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig, dtype=jnp.float32) -> Dict[str, Any]:
    D, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (D, H, hd), in_axis_size=D, dtype=dtype),
        "wk": dense_init(ks[1], (D, Hkv, hd), in_axis_size=D, dtype=dtype),
        "wv": dense_init(ks[2], (D, Hkv, hd), in_axis_size=D, dtype=dtype),
        "wo": dense_init(ks[3], (H, hd, D), in_axis_size=H * hd, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _gqa_scores(q, k):
    """q: (B,Sq,H,hd)  k: (B,Skv,Hkv,hd) -> (B,H,Sq,Skv) with GQA grouping.

    The dot runs in the INPUT dtype and upcasts after: with
    ``preferred_element_type=f32`` GSPMD materializes an f32 copy of the
    whole (sequence-sharded) K cache and gathers it per decode layer
    (measured 104 GB/step on zamba2 decode_32k — §Perf-B iter 5).  bf16
    MXU accumulation is f32 internally on TPU, so accuracy is unchanged;
    the explicit upcast happens on the small scores tensor instead."""
    B, Sq, H, hd = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    return s.reshape(B, Hkv * G, Sq, k.shape[1])


def _gqa_combine(probs, v):
    """probs: (B,H,Sq,Skv)  v: (B,Skv,Hkv,hd) -> (B,Sq,H,hd)."""
    B, H, Sq, Skv = probs.shape
    Hkv = v.shape[2]
    G = H // Hkv
    pg = probs.reshape(B, Hkv, G, Sq, Skv)
    out = jnp.einsum("bkgqs,bskh->bqkgh", pg, v)
    return out.reshape(B, Sq, H, v.shape[-1])


def attend_direct(q, k, v, mask, scale: float):
    """Reference attention.  mask: broadcastable to (B,H,Sq,Skv), True=keep."""
    s = _gqa_scores(q, k) * scale
    s = jnp.where(mask, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return _gqa_combine(p, v)


def attend_blocked(q, k, v, *, causal: bool, window: Optional[int], scale: float,
                   q_positions, kv_positions, q_block: int = 512, kv_block: int = 1024,
                   causal_skip: bool = False):
    """Blocked online-softmax attention in pure jnp (the flash ref).

    Scans over q blocks; for each q block scans kv blocks with running
    (max, sum, acc).  Memory is O(q_block * kv_block) per step instead of
    O(Sq*Skv).

    * ``window`` (static int): each q block only visits a dynamic slice of
      K/V of static length window+q_block → true sub-quadratic FLOPs for
      sliding-window layers (starcoder2, gemma3 local).
    * ``causal_skip``: unroll the q-block loop in python so q block i only
      scans kv blocks [0, i] — halves causal-attention FLOPs at the cost of
      a bigger HLO (off by default; a §Perf hillclimb lever).
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    nq = -(-Sq // q_block)
    q_pad = nq * q_block - Sq
    qp = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, (0, q_pad), constant_values=-1)
    qb = qp.reshape(B, nq, q_block, H, hd)
    qposb = qpos.reshape(nq, q_block)

    def kv_inner(qblk, qpos_blk, kb, vb, kposb):
        """Online softmax of one q block over a stack of kv blocks.

        kb/vb: (n, kv_block, Hkv, hd); kposb: (n, kv_block)."""

        def kv_step(carry, ki):
            m, l, acc = carry
            kblk, vblk, kpos_blk = ki
            s = _gqa_scores(qblk, kblk) * scale  # (B,H,qb,kvb) f32
            msk = jnp.ones((q_block, kv_block), dtype=bool)
            if causal:
                msk &= qpos_blk[:, None] >= kpos_blk[None, :]
            if window is not None:
                msk &= qpos_blk[:, None] - kpos_blk[None, :] < window
            msk &= (qpos_blk[:, None] >= 0) & (kpos_blk[None, :] < 2**30)
            s = jnp.where(msk[None, None], s, jnp.float32(-1e30))
            m_new = jnp.maximum(m, s.max(axis=-1))  # (B,H,qb)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            pv = _gqa_combine(p.astype(qblk.dtype), vblk)  # (B,qb,H,hd)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, q_block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, q_block), jnp.float32)
        a0 = jnp.zeros((B, q_block, H, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (kb, vb, kposb))
        lT = l.transpose(0, 2, 1)[..., None]  # (B,qb,H,1)
        return (acc / jnp.maximum(lT, 1e-30)).astype(qblk.dtype)

    if window is not None and causal:
        # --- sliding window: static-length kv slice per q block ---
        w_up = -(-window // kv_block) * kv_block
        span = w_up + q_block  # static slice length
        n_in = span // kv_block if span % kv_block == 0 else -(-span // kv_block)
        span = n_in * kv_block
        kv_pad_lo = w_up  # so the first q block's slice is in range
        kp = jnp.pad(k, ((0, 0), (kv_pad_lo, q_pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (kv_pad_lo, q_pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kv_positions, (kv_pad_lo, q_pad), constant_values=2**30)
        kpos = kpos.at[:kv_pad_lo].set(2**30)
        starts = jnp.arange(nq) * q_block  # slice start in padded coords

        def q_step(_, qi):
            qblk, qpos_blk, st = qi
            kslc = jax.lax.dynamic_slice_in_dim(kp, st, span, axis=1)
            vslc = jax.lax.dynamic_slice_in_dim(vp, st, span, axis=1)
            pslc = jax.lax.dynamic_slice_in_dim(kpos, st, span, axis=0)
            kb = jnp.moveaxis(kslc.reshape(B, n_in, kv_block, *kslc.shape[2:]), 1, 0)
            vb = jnp.moveaxis(vslc.reshape(B, n_in, kv_block, *vslc.shape[2:]), 1, 0)
            pb = pslc.reshape(n_in, kv_block)
            return None, kv_inner(qblk, qpos_blk, kb, vb, pb)

        _, outs = jax.lax.scan(q_step, None, (qb.swapaxes(0, 1), qposb, starts))
        out = outs.swapaxes(0, 1).reshape(B, nq * q_block, H, hd)
        return out[:, :Sq]

    nkv = -(-Skv // kv_block)
    kv_pad = nkv * kv_block - Skv
    kp = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0), (0, 0)))
    kpos = jnp.pad(kv_positions, (0, kv_pad), constant_values=2**30)
    kb_all = kp.reshape(B, nkv, kv_block, *kp.shape[2:])
    vb_all = vp.reshape(B, nkv, kv_block, *vp.shape[2:])
    kposb = kpos.reshape(nkv, kv_block)

    if causal and causal_skip:
        # python-unrolled q loop; q block i visits kv blocks [0, i_kv]
        outs = []
        kv_per_q = q_block // kv_block if q_block >= kv_block else 1
        for i in range(nq):
            hi = min(nkv, max(1, (i + 1) * q_block // kv_block + (1 if q_block % kv_block else 0)))
            outs.append(
                kv_inner(
                    qb[:, i], qposb[i],
                    jnp.moveaxis(kb_all[:, :hi], 1, 0),
                    jnp.moveaxis(vb_all[:, :hi], 1, 0),
                    kposb[:hi],
                )
            )
        out = jnp.stack(outs, axis=1).reshape(B, nq * q_block, H, hd)
        return out[:, :Sq]

    def q_step(_, qi):
        qblk, qpos_blk = qi
        return None, kv_inner(
            qblk, qpos_blk,
            jnp.moveaxis(kb_all, 1, 0), jnp.moveaxis(vb_all, 1, 0), kposb,
        )

    _, outs = jax.lax.scan(q_step, None, (qb.swapaxes(0, 1), qposb))
    out = outs.swapaxes(0, 1).reshape(B, nq * q_block, H, hd)
    return out[:, :Sq]


BLOCKED_ATTENTION_THRESHOLD = 4096


def self_attention(
    params: Dict[str, Any],
    x,
    *,
    cfg: ModelConfig,
    positions,  # (S,) int32 absolute positions of x's tokens
    is_global,  # python bool or traced bool: full attention vs sliding window
    cache: Optional[Dict[str, Any]] = None,  # {"k","v"}: (B, S_max, Hkv, hd)
    cache_pos: Optional[jax.Array] = None,  # scalar: write index for decode
    parallel: Optional[ParallelContext] = None,
    kv_spec: Optional[P] = None,
    use_flash: bool = True,
    return_kv: bool = False,  # prefill: emit this segment's K/V as a cache
    use_kernel: bool = False,  # Pallas flash kernel instead of the jnp path
):
    """Returns (out, new_cache).  Decode mode iff cache is not None."""
    B, S, D = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"])
        k = rmsnorm(k, params["k_norm"])
    if cfg.use_rope:
        # RoPE by layer type: YaRN on the full layers only
        yarn = cfg.rope_yarn if _static_true(is_global) else None
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
    if parallel is not None and cache is None:
        # train/prefill: shard heads over "model" (TP attention).  In DECODE
        # the cache is sequence-sharded over "model"; head-sharding q forces
        # GSPMD to all-gather the whole KV cache per layer to reconcile the
        # layouts (measured 104 GB/step on zamba2 decode_32k — §Perf-B
        # iter 4).  Leaving q replicated lets attention compute
        # sequence-parallel partials per S-shard (flash-decoding style) with
        # only a small psum to combine.
        hspec = P(parallel.data_axes, None, parallel.model_axis, None)
        q = shard(q, hspec, parallel)

    window = cfg.sliding_window if not _static_true(is_global) else None

    if cache is None:
        # ---- train/prefill: full self attention over x itself ----
        if use_kernel:
            from repro.kernels.flash_attention import ops as fa_ops

            out = fa_ops.flash_attention(q, k, v, causal=True, window=window, scale=scale)
        elif use_flash and S >= BLOCKED_ATTENTION_THRESHOLD:
            out = attend_blocked(
                q, k, v, causal=True, window=window, scale=scale,
                q_positions=positions, kv_positions=positions,
            )
        else:
            msk = positions[:, None] >= positions[None, :]
            if window is not None:
                msk &= positions[:, None] - positions[None, :] < window
            out = attend_direct(q, k, v, msk[None, None], scale)
        new_cache = {"k": shard(k, kv_spec, parallel), "v": shard(v, kv_spec, parallel)} if return_kv else None
    else:
        # ---- decode: write this token's k/v into the cache, attend over it --
        S_max = cache["k"].shape[1]
        kc = _cache_write(cache["k"], k, cache_pos, kv_spec, parallel)
        vc = _cache_write(cache["v"], v, cache_pos, kv_spec, parallel)
        kv_positions = jnp.arange(S_max, dtype=jnp.int32)
        valid = kv_positions <= cache_pos
        if window is not None:
            valid &= kv_positions > cache_pos - window
        out = attend_direct(q, kc, vc, valid[None, None, None, :], scale)
        new_cache = {"k": kc, "v": vc}

    o = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    if parallel is not None:
        o = shard(o, P(parallel.data_axes, None, None), parallel)
    return o, new_cache


def _cache_write(cache, kv_new, pos, kv_spec, parallel):
    """Write one token (B, 1, Hkv, hd) into cache (B, S, Hkv, hd) at ``pos``.

    dynamic_update_slice at a traced position on a sequence-sharded cache
    makes GSPMD gather/reshard the cache (≈104 GB/step measured on zamba2
    decode_32k, §Perf hillclimb B).  A one-hot ``where``-blend was tried and
    measured WORSE (2.05e11 B gathered — GSPMD replicated the ``where``
    output despite the trailing constraint).  The deployable fix is a
    shard_map-local cache update (each shard compares pos against its own
    slab and writes locally) — implemented below behind
    ``set_cache_write_mode("shardmap")``; DUS stays the default because the
    mode is selected per deployment (EXPERIMENTS.md §Perf-B iter 3).
    """
    if CACHE_WRITE_MODE == "shardmap" and parallel is not None and kv_spec is not None:
        return _cache_write_shardmap(cache, kv_new, pos, kv_spec, parallel)
    upd = jax.lax.dynamic_update_slice(
        cache, kv_new.astype(cache.dtype), (0, pos.astype(jnp.int32), 0, 0)
    )
    return shard(upd, kv_spec, parallel)


CACHE_WRITE_MODE = "dus"  # "dus" | "shardmap" (§Perf-B iter 3)


def set_cache_write_mode(mode: str) -> None:
    global CACHE_WRITE_MODE
    assert mode in ("dus", "shardmap")
    CACHE_WRITE_MODE = mode


def _cache_write_shardmap(cache, kv_new, pos, kv_spec, parallel):
    """Shard-local cache write: each shard compares ``pos`` against its own
    sequence slab and blends locally — zero cross-shard traffic by
    construction (vs GSPMD's gather-update-reshard of a sharded-dim DUS)."""
    mesh = parallel.mesh
    seq_entry = kv_spec[1]  # (B, S, Hkv, hd) → S sharding axes
    seq_axes = seq_entry if isinstance(seq_entry, tuple) else (seq_entry,)
    seq_axes = tuple(a for a in seq_axes if a is not None)
    if not seq_axes:  # sequence unsharded: DUS is already shard-local
        upd = jax.lax.dynamic_update_slice(
            cache, kv_new.astype(cache.dtype), (0, pos.astype(jnp.int32), 0, 0)
        )
        return shard(upd, kv_spec, parallel)
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    S_local = cache.shape[1] // n_shards
    kv_in_spec = P(kv_spec[0], None, None, None)

    def body(c_loc, kv_loc, pos_s):
        # flat shard index along the (possibly compound) sequence axes
        idx = 0
        for a in seq_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        start = idx * S_local
        local = pos_s.astype(jnp.int32) - start
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, S_local, 1, 1), 1)
        mask = iota == local  # off-shard ⇒ never equal ⇒ no-op
        return jnp.where(mask, kv_loc.astype(c_loc.dtype), c_loc)

    return shard_map(
        body, mesh=mesh,
        in_specs=(kv_spec, kv_in_spec, P()),
        out_specs=kv_spec,
        check_vma=False,
    )(cache, kv_new, pos)


def _static_true(b) -> bool:
    """True iff ``b`` is a static python truth (global attention layer)."""
    return isinstance(b, bool) and b


# ----------------------------------------------------------------------
# cross attention (encoder-decoder)
# ----------------------------------------------------------------------


def cross_attention(params, x, enc_kv, *, cfg: ModelConfig, parallel=None):
    """x: (B, Sq, D) queries; enc_kv: {"k","v"}: (B, S_src, Hkv, hd).

    Long sources use the blocked online-softmax path — a direct (Sq, S_src)
    score matrix at 4k×4k dominated the enc-dec train-step peak memory."""
    hd = cfg.resolved_head_dim
    scale = 1.0 / math.sqrt(hd)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    S_src = enc_kv["k"].shape[1]
    Sq = x.shape[1]
    k = enc_kv["k"].astype(x.dtype)
    v = enc_kv["v"].astype(x.dtype)
    if max(Sq, S_src) >= BLOCKED_ATTENTION_THRESHOLD:
        out = attend_blocked(
            q, k, v, causal=False, window=None, scale=scale,
            q_positions=jnp.arange(Sq, dtype=jnp.int32),
            kv_positions=jnp.arange(S_src, dtype=jnp.int32),
        )
    else:
        msk = jnp.ones((1, 1, Sq, S_src), dtype=bool)
        out = attend_direct(q, k, v, msk, scale)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))


def encode_kv(params, enc_out, *, cfg: ModelConfig):
    """Precompute cross-attention K/V from encoder output (done once)."""
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"].astype(enc_out.dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"].astype(enc_out.dtype))
    return {"k": k, "v": v}


# ----------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------


def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None, dtype=jnp.float32):
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.mlp_type == "gated_silu":
        return {
            "w_gate": dense_init(ks[0], (D, F), dtype=dtype),
            "w_up": dense_init(ks[1], (D, F), dtype=dtype),
            "w_down": dense_init(ks[2], (F, D), in_axis_size=F, dtype=dtype),
        }
    return {
        "w_up": dense_init(ks[0], (D, F), dtype=dtype),
        "w_down": dense_init(ks[1], (F, D), in_axis_size=F, dtype=dtype),
    }


def mlp(params, x, *, cfg: ModelConfig, parallel: Optional[ParallelContext] = None):
    if cfg.mlp_type == "gated_silu":
        g = jnp.einsum("bsd,df->bsf", x, params["w_gate"].astype(x.dtype))
        u = jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, params["w_up"].astype(x.dtype)))
    if parallel is not None:
        h = shard(h, P(parallel.data_axes, None, parallel.model_axis), parallel)
    out = jnp.einsum("bsf,fd->bsd", h, params["w_down"].astype(x.dtype))
    if parallel is not None:
        out = shard(out, P(parallel.data_axes, None, None), parallel)
    return out


# ----------------------------------------------------------------------
# Mixture of Experts
# ----------------------------------------------------------------------


def init_moe(rng, cfg: ModelConfig, dtype=jnp.float32):
    """The router routes over all ``n_experts``; the expert stacks hold
    the layer's ``experts_held``."""
    D, F, E = cfg.d_model, cfg.d_ff, cfg.experts_held
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (D, cfg.n_experts), dtype=jnp.float32),  # router kept f32
        "w_gate": dense_init(ks[1], (E, D, F), in_axis_size=D, dtype=dtype),
        "w_up": dense_init(ks[2], (E, D, F), in_axis_size=D, dtype=dtype),
        "w_down": dense_init(ks[3], (E, F, D), in_axis_size=F, dtype=dtype),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(ks[4], cfg, dtype=dtype)
    return p


def _router(params, x, cfg: ModelConfig):
    """Returns (gates (T,k), experts (T,k), probs (T,E), aux_loss scalar).
    Logits in f32 over all routed experts; the top-k gates renormalised."""
    T = x.shape[0]
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.top_k)  # (T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # aux losses: load-balance (Switch) + router z-loss, where configured
    aux = jnp.float32(0.0)
    if cfg.load_balance_loss:
        me = probs.mean(axis=0)  # (E,)
        ce = jnp.zeros((cfg.n_experts,), jnp.float32)
        ce = ce.at[experts.reshape(-1)].add(1.0) / (T * cfg.top_k)
        aux = aux + cfg.n_experts * jnp.sum(me * ce) * cfg.load_balance_loss
    if cfg.router_z_loss:
        aux = aux + jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * cfg.router_z_loss
    return gates, experts, probs, aux


def moe_ref(params, x, *, cfg: ModelConfig):
    """Dense reference MoE: every held expert computed on every token,
    masked by its gate (0 where the token did not choose it).

    O(T*E*D*F) — only for reduced configs / oracles.  Returns (out, aux).
    """
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    gates, experts, _, aux = _router(params, xt, cfg)
    # combine weight per expert per token: (T, E), of which the held ones
    comb = jnp.zeros((xt.shape[0], cfg.n_experts), x.dtype)
    comb = comb.at[jnp.arange(xt.shape[0])[:, None], experts].add(gates.astype(x.dtype))
    comb = comb[:, : params["w_gate"].shape[0]]

    def one_expert(wg, wu, wd):
        h = jax.nn.silu(xt @ wg.astype(x.dtype)) * (xt @ wu.astype(x.dtype))
        return h @ wd.astype(x.dtype)  # (T, D)

    outs = jax.vmap(one_expert)(params["w_gate"], params["w_up"], params["w_down"])  # (E,T,D)
    out = jnp.einsum("te,etd->td", comb, outs)
    if cfg.shared_expert:
        out = out + mlp(params["shared"], x, cfg=cfg).reshape(-1, D)
    return out.reshape(B, S, D), aux


def moe_capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def moe_block(params, x, *, cfg: ModelConfig, parallel: Optional[ParallelContext] = None):
    """Sparse-expert FFN over the experts the layer holds: dropless
    (``moe_dropless``) where ``cfg.capacity_factor`` is None, else with
    capacity slots (``_dropping_local``).

    With a mesh, runs under shard_map: tokens stay on their (pod,data) shard,
    the held experts are sharded over the model axis; each model shard
    computes its experts for the local tokens and the partial outputs are
    psum'd over the model axis (one (T_local, D) all-reduce per MoE layer —
    the same volume as a tensor-parallel MLP).
    """
    B, S, D = x.shape
    local = moe_dropless if cfg.capacity_factor is None else _dropping_local

    if parallel is None:
        out, aux = local(params, x.reshape(-1, D), cfg=cfg, e_lo=0)
        out = out.reshape(B, S, D)
        if cfg.shared_expert:
            out = out + mlp(params["shared"], x, cfg=cfg)
        return out, aux

    mesh = parallel.mesh
    maxis = parallel.model_axis
    msize = parallel.model_size
    e_per = cfg.experts_held // msize
    assert e_per * msize == cfg.experts_held, (
        f"held experts={cfg.experts_held} must divide model axis {msize}"
    )

    def body(xl, router, wg, wu, wd):
        # xl: (B_l, S, D) local tokens; wg/wu/wd: (E_l, ...) local experts
        j = jax.lax.axis_index(maxis)
        xt = xl.reshape(-1, D)
        p_local = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        out, aux = local(p_local, xt, cfg=cfg, e_lo=j * e_per)
        out = jax.lax.psum(out, maxis)
        aux = jax.lax.psum(aux, maxis) / msize
        return out.reshape(xl.shape), aux

    specs_in = (
        P(parallel.data_axes, None, None),  # x
        P(None, None),  # router replicated
        P(maxis, None, None),
        P(maxis, None, None),
        P(maxis, None, None),
    )
    specs_out = (P(parallel.data_axes, None, None), P())
    out, aux = shard_map(
        body, mesh=mesh, in_specs=specs_in, out_specs=specs_out, check_vma=False
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    if cfg.shared_expert:
        out = out + mlp(params["shared"], x, cfg=cfg, parallel=parallel)
    return out, aux


def _dropping_local(params, xt, *, cfg: ModelConfig, e_lo):
    """Tokens xt (T, D) through E_local experts starting at ``e_lo`` (may be
    a traced axis_index) with capacity slots (token-dropping, GShard-style
    slots, but WITHOUT the (T,E,C) one-hot dispatch tensor — slots are
    computed with a (T*k, E) cumsum and a scatter-add, which is what keeps
    dbrx-scale (E=16, top-4) feasible).

    params["w_*"] hold exactly E_local experts (static, from the leaf
    shape).  Routing decisions are computed over ALL E experts (router is
    replicated); only choices landing in [e_lo, e_lo + E_local) run here.
    """
    T, D = xt.shape
    E_local = params["w_gate"].shape[0]
    e_hi = e_lo + E_local
    C = moe_capacity(cfg, T)
    gates, experts, _, aux = _router(params, xt, cfg)  # (T,k)

    flat_e = experts.reshape(-1)  # (T*k,)
    # position of each (token, choice) within its expert's queue — global
    # over all E so capacity semantics match the unsharded reference
    onehot = jax.nn.one_hot(flat_e, cfg.n_experts, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1  # (T*k, E)
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (T*k,)

    local = (flat_e >= e_lo) & (flat_e < e_hi) & (pos < C)
    slot = jnp.where(local, (flat_e - e_lo) * C + pos, E_local * C)  # drop slot at end
    buf = jnp.zeros((E_local * C + 1, D), xt.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), cfg.top_k)
    buf = buf.at[slot].add(xt[tok_idx])
    buf = buf[:-1].reshape(E_local, C, D)

    h = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(xt.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(xt.dtype))
    h = jax.nn.silu(h) * u
    eout = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(xt.dtype))
    eout = eout.reshape(E_local * C, D)
    eout = jnp.concatenate([eout, jnp.zeros((1, D), xt.dtype)], axis=0)

    gathered = eout[slot] * gates.reshape(-1)[:, None].astype(xt.dtype)  # (T*k, D)
    out = jnp.zeros((T, D), xt.dtype).at[tok_idx].add(gathered)
    return out, aux


# ----------------------------------------------------------------------
# dropless experts: grouped matmuls over rows sorted by expert
# ----------------------------------------------------------------------


def _unbatched(fn):
    """``fn`` under ``vmap`` as one call per batch element: the TPU's
    grouped matmul (``ragged_dot``) takes no batch dimension, and the
    federated engine maps each client's step over its cohort."""
    fn = jax.custom_batching.custom_vmap(fn)

    @fn.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = [fn(*(a[i] if b else a for a, b in zip(args, in_batched)))
                for i in range(axis_size)]
        return jnp.stack(outs), True

    return fn


@_unbatched
def _rows_by_group(x, w, group_sizes):
    """(M, K) rows in group order × (G, K, N) -> (M, N)."""
    return jax.lax.ragged_dot(x, w, group_sizes)


_CONTRACT_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=[0],
    rhs_group_dimensions=[])


@_unbatched
def _per_group(x, dy, group_sizes):
    """Per group g: x[rows of g]^T @ dy[rows of g]: (M, K), (M, N) -> (G, K, N)."""
    return jax.lax.ragged_dot_general(x, dy, group_sizes, _CONTRACT_ROWS)


def _grouped_rows_only(y, group_sizes):
    """Rows past the last group are no group's: zero, whatever the
    kernel left in them."""
    rows = jnp.arange(y.shape[0])
    return jnp.where((rows < jnp.sum(group_sizes))[:, None], y, jnp.zeros((), y.dtype))


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """Row i of group g times w[g]: x (M, K) sorted by group, w (G, K, N),
    group_sizes (G,) summing to at most M; rows past the groups give 0.
    On TPU ``ragged_dot`` lowers to a Mosaic kernel whose grid visits only
    the row tiles that hold grouped rows, so the work follows the rows
    routed here, not M."""
    return _grouped_rows_only(_rows_by_group(x, w, group_sizes), group_sizes)


def _grouped_matmul_fwd(x, w, group_sizes):
    return grouped_matmul(x, w, group_sizes), (x, w, group_sizes)


def _grouped_matmul_bwd(res, dy):
    x, w, group_sizes = res
    dx = _grouped_rows_only(_rows_by_group(dy, jnp.swapaxes(w, 1, 2), group_sizes), group_sizes)
    return dx, _per_group(x, dy, group_sizes).astype(w.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def moe_dropless(params, xt, *, cfg: ModelConfig, e_lo):
    """Tokens xt (T, D) through the E_local held experts [e_lo, e_lo +
    E_local) with no capacity: every (token, choice) routed to a held
    expert is computed, in the worst case all T*k of them; choices of
    absent experts add nothing.  The rows are sorted by expert and run as
    grouped matmuls (SwiGLU), then scattered back weighted by their gates.

    Scopes: ``moe.dispatch`` (router, top-k, sort, gather, weighted
    scatter) and ``moe.experts`` (the grouped matmuls and the SiLU product).
    """
    T, D = xt.shape
    E_local = params["w_gate"].shape[0]
    dt = xt.dtype
    with jax.named_scope("moe.dispatch"):
        gates, experts, _, aux = _router(params, xt, cfg)  # (T,k)
        group = experts.reshape(-1) - e_lo  # (T*k,)
        held = (group >= 0) & (group < E_local)
        group = jnp.where(held, group, E_local)  # absent experts' choices sort last
        order = jnp.argsort(group)
        token = order // cfg.top_k
        sizes = jnp.zeros((E_local,), jnp.int32).at[group].add(1, mode="drop")
        weight = jnp.where(held, gates.reshape(-1), 0.0)[order]
        rows = xt[token]
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(grouped_matmul(rows, params["w_gate"].astype(dt), sizes))
        h = h * grouped_matmul(rows, params["w_up"].astype(dt), sizes)
        y = grouped_matmul(h, params["w_down"].astype(dt), sizes)
    with jax.named_scope("moe.dispatch"):
        out = jnp.zeros((T, D), jnp.float32).at[token].add(y.astype(jnp.float32) * weight[:, None])
    return out.astype(dt), aux
