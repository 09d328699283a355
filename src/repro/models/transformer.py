"""Decoder-only transformer assembly (dense / MoE / SSM / hybrid / VLM).

The layer stack is organized as a *period scan*: each architecture has a
static repeating period of slots (e.g. gemma3 = 5 local-attention slots +
1 global slot; llama4 = dense slot + MoE slot; zamba2 = 5 mamba slots + 1
shared-attention slot), parameters are stacked with a leading ``n_periods``
axis, and the stack is traversed with one ``lax.scan`` whose body statically
unrolls the slots.  This keeps the HLO small, keeps slot structure (window
size, MoE-ness) static — which is what makes sliding-window layers truly
sub-quadratic — and gives remat a natural boundary (the period).

Zamba2's signature shared attention block lives OUTSIDE the scanned stack
(one parameter set, applied at every shared slot); its KV caches are still
per-application and are threaded through the scan as xs/ys.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import mamba2 as m2
from repro.models.layers import (
    ParallelContext,
    embed_init,
    init_attention,
    init_mlp,
    init_moe,
    init_rmsnorm,
    mlp,
    moe_block,
    rmsnorm,
    self_attention,
    shard,
)


@dataclass(frozen=True)
class SlotSpec:
    kind: str  # "attn" | "mamba"
    is_global: bool = True  # attention: full vs sliding window
    is_moe: bool = False
    shared: bool = False  # params live in params["shared"], not the stack


def period_layout(cfg: ModelConfig) -> Tuple[List[SlotSpec], int, List[SlotSpec]]:
    """Returns (period_slots, n_periods, tail_slots)."""
    if cfg.family == "ssm":
        return [SlotSpec("mamba")], cfg.n_layers, []
    if cfg.family == "hybrid":
        period = cfg.attn_every
        slots = [SlotSpec("mamba")] * (period - 1) + [SlotSpec("attn", shared=True)]
        n_periods = cfg.n_layers // period
        n_tail = cfg.n_layers - n_periods * period
        return slots, n_periods, [SlotSpec("mamba")] * n_tail
    # dense / moe / vlm: attention+ffn slots
    slots: List[SlotSpec] = []
    if cfg.local_global_pattern is not None:
        n_local, n_global = cfg.local_global_pattern
        slots = [SlotSpec("attn", is_global=False)] * n_local + [
            SlotSpec("attn", is_global=True)
        ] * n_global
    elif cfg.sliding_window is not None:
        slots = [SlotSpec("attn", is_global=False)]
    else:
        slots = [SlotSpec("attn", is_global=True)]
    if cfg.n_experts > 0 and cfg.moe_every > 1:
        # expand the period so MoE-ness is static per slot
        base = slots
        reps = cfg.moe_every // len(base) if cfg.moe_every % len(base) == 0 else cfg.moe_every
        slots = []
        for i in range(cfg.moe_every):
            s = base[i % len(base)]
            slots.append(SlotSpec(s.kind, s.is_global, is_moe=(i == cfg.moe_every - 1)))
    elif cfg.n_experts > 0:
        slots = [SlotSpec(s.kind, s.is_global, is_moe=True) for s in slots]
    period = len(slots)
    assert cfg.n_layers % period == 0, (cfg.name, cfg.n_layers, period)
    return slots, cfg.n_layers // period, []


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------


def _init_slot(rng, slot: SlotSpec, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(rng, 4)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if slot.kind == "mamba":
        p["mamba"] = m2.init_mamba2(ks[0], cfg, dtype)
        return p
    p["attn"] = init_attention(ks[0], cfg, dtype)
    p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
    if slot.is_moe:
        p["moe"] = init_moe(ks[1], cfg, dtype)
    elif cfg.d_ff > 0:
        p["mlp"] = init_mlp(ks[1], cfg, dtype=dtype)
    return p


def _stack_init(rng, n: int, slots: List[SlotSpec], cfg: ModelConfig, dtype):
    """Init n periods of params, stacked on a leading axis per leaf."""

    def one(r):
        ks = jax.random.split(r, len(slots))
        return {
            f"slot{i}": _init_slot(ks[i], s, cfg, dtype)
            for i, s in enumerate(slots)
            if not s.shared
        }

    if n == 0:
        return {}
    return jax.vmap(one)(jax.random.split(rng, n))


def init_params(rng, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    slots, n_periods, tail = period_layout(cfg)
    ks = jax.random.split(rng, 6)
    params: Dict[str, Any] = {
        "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
        "periods": _stack_init(ks[1], n_periods, slots, cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(ks[2], (cfg.d_model, cfg.padded_vocab), dtype)
    if tail:
        params["tail"] = _stack_init(ks[3], len(tail), [tail[0]], cfg, dtype)
    if any(s.shared for s in slots):
        params["shared"] = _init_slot(ks[4], SlotSpec("attn", is_global=True), cfg, dtype)
    return params


# ----------------------------------------------------------------------
# slot application
# ----------------------------------------------------------------------


def _apply_slot(
    slot: SlotSpec,
    p: Dict[str, Any],
    h,
    *,
    cfg: ModelConfig,
    positions,
    cache_slot: Optional[Dict[str, Any]],
    cache_pos,
    parallel,
    kv_spec,
    use_kernels: bool,
    emit_cache: bool = False,
):
    """One slot (attention+ffn or mamba).  Returns (h, new_cache_slot, aux)."""
    aux = jnp.float32(0.0)
    if slot.kind == "mamba":
        y, new_state = m2.mamba2_block(
            p["mamba"], rmsnorm(h, p["norm1"]), cfg=cfg, state=cache_slot,
            parallel=parallel, use_kernel=use_kernels, return_state=emit_cache,
        )
        return h + y, new_state, aux
    attn_out, new_kv = self_attention(
        p["attn"],
        rmsnorm(h, p["norm1"]),
        cfg=cfg,
        positions=positions,
        is_global=slot.is_global,
        cache=cache_slot,
        cache_pos=cache_pos,
        parallel=parallel,
        kv_spec=kv_spec,
        return_kv=emit_cache,
        use_kernel=use_kernels,
    )
    h = h + attn_out
    if slot.is_moe:
        y, moe_aux = moe_block(p["moe"], rmsnorm(h, p["norm2"]), cfg=cfg, parallel=parallel)
        aux = aux + moe_aux
    elif "mlp" in p:
        y = mlp(p["mlp"], rmsnorm(h, p["norm2"]), cfg=cfg, parallel=parallel)
    else:
        y = jnp.zeros_like(h)
    return h + y, new_kv, aux


def _period_body(
    slots: List[SlotSpec],
    cfg: ModelConfig,
    positions,
    cache_pos,
    parallel,
    kv_spec,
    use_kernels: bool,
    shared_params,
    emit_cache: bool = False,
):
    """Returns a scan body over ((h, aux), (period_params, period_cache))."""

    def body(carry, xs):
        h, aux = carry
        pp, cache_in = xs
        cache_out = {}
        for i, slot in enumerate(slots):
            key = f"slot{i}"
            p = shared_params if slot.shared else pp[key]
            cslot = None if cache_in is None else cache_in.get(key)
            h, new_c, a = _apply_slot(
                slot, p, h, cfg=cfg, positions=positions, cache_slot=cslot,
                cache_pos=cache_pos, parallel=parallel, kv_spec=kv_spec,
                use_kernels=use_kernels, emit_cache=emit_cache,
            )
            aux = aux + a
            if new_c is not None:
                cache_out[key] = new_c
        return (h, aux), (cache_out if cache_out else None)

    return body


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------


def forward(
    params: Dict[str, Any],
    tokens,  # (B, S) int32  (VLM image tokens share the vocab)
    *,
    cfg: ModelConfig,
    cache: Optional[Dict[str, Any]] = None,
    cache_pos=None,
    parallel: Optional[ParallelContext] = None,
    kv_spec=None,
    remat: str = "none",
    use_kernels: bool = False,
    return_cache: bool = False,  # prefill: emit per-layer K/V (+ SSM states)
    scan_unroll: int = 1,  # dry-run: unroll the period scan so XLA cost
                           # analysis counts every trip (execution uses 1)
):
    """Returns (logits (B,S,V), new_cache, aux_loss)."""
    slots, n_periods, tail = period_layout(cfg)
    adtype = jnp.dtype(cfg.dtype)
    h = params["embed"][tokens].astype(adtype) * (cfg.d_model**0.5)
    if parallel is not None:
        h = shard(h, P(parallel.data_axes, None, None), parallel)

    B, S = tokens.shape
    if cache is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    else:
        positions = jnp.asarray(cache_pos, jnp.int32)[None]

    shared_p = params.get("shared")
    emit = return_cache and cache is None
    body = _period_body(
        slots, cfg, positions, cache_pos, parallel, kv_spec, use_kernels, shared_p,
        emit_cache=emit,
    )
    if remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False,
        )

    aux0 = jnp.float32(0.0)
    cache_periods = None if cache is None else cache["periods"]
    (h, aux), new_cache_periods = jax.lax.scan(
        body, (h, aux0), (params["periods"], cache_periods),
        unroll=min(scan_unroll, n_periods) if scan_unroll > 1 else 1,
    )

    new_cache = None
    if tail:
        tail_body = _period_body(
            [tail[0]], cfg, positions, cache_pos, parallel, kv_spec, use_kernels,
            shared_p, emit_cache=emit,
        )
        cache_tail = None if cache is None else cache["tail"]
        (h, aux), new_cache_tail = jax.lax.scan(
            tail_body, (h, aux), (params["tail"], cache_tail),
            unroll=min(scan_unroll, len(tail)) if scan_unroll > 1 else 1,
        )
    if cache is not None or emit:
        new_cache = {"periods": new_cache_periods}
        if tail:
            new_cache["tail"] = new_cache_tail

    h = rmsnorm(h, params["final_norm"])
    unembed = (
        params["embed"].T if cfg.tie_embeddings else params["unembed"]
    ).astype(adtype)
    logits = jnp.einsum("bsd,dv->bsv", h, unembed)
    if parallel is not None:
        logits = shard(logits, P(parallel.data_axes, None, parallel.model_axis), parallel)
    return logits, new_cache, aux


# ----------------------------------------------------------------------
# cache
# ----------------------------------------------------------------------


def init_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=None
) -> Dict[str, Any]:
    """Cache pytree matching the scan layout: per-slot leaves stacked over
    periods.  Attention slots: {"k","v"} (n_periods, B, S, Hkv, hd); mamba
    slots: {"ssm","conv"} stacked likewise."""
    dtype = jnp.dtype(dtype or cfg.dtype)
    slots, n_periods, tail = period_layout(cfg)

    def slot_cache(slot: SlotSpec, n: int):
        if slot.kind == "mamba":
            st = m2.init_mamba2_state(cfg, batch, dtype)
            return jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (n, *x.shape)), st)
        hd = cfg.resolved_head_dim
        # sliding-window slots only ever read the last `window` positions —
        # but the baseline allocates full length (ring-buffer variant is the
        # §Perf memory optimization).
        shape = (n, batch, max_len, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    cache = {
        "periods": {
            f"slot{i}": slot_cache(s, n_periods) for i, s in enumerate(slots)
        }
    }
    if tail:
        cache["tail"] = {"slot0": slot_cache(tail[0], len(tail))}
    return cache
