"""Uniform model API over all architecture families.

``build_model(cfg)`` returns a ``Model`` whose members are pure functions:

    params                  = model.init(rng)
    loss, metrics           = model.loss_fn(params, batch, ...)
    logits, cache, aux      = model.apply(params, tokens, ...)
    cache                   = model.init_cache(params, batch, max_len, batch_ctx)
    logits, cache           = model.decode_step(params, token, cache, pos, ...)
    batch                   = model.dummy_batch(shape)   # concrete, for smoke tests
    specs                   = model.input_specs(shape)   # ShapeDtypeStruct, for dry-run

The federated layer (repro.core) only ever sees ``loss_fn`` — FedCM is
optimizer-level and architecture-agnostic (DESIGN.md §7).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, transformer
from repro.models.layers import ParallelContext


def cross_entropy(logits, labels, z_reg: float = 0.0):
    """Mean token cross entropy in f32. logits (B,S,V), labels (B,S) int32.

    Sharding-friendly formulation: the label log-prob is a one-hot einsum
    (partial-sums + psum when V is model-sharded) instead of
    ``take_along_axis`` — a gather over a sharded axis makes GSPMD
    all-gather the full f32 logits (≈8 GiB/chip at llama3 vocab), which
    dominated both the memory AND collective roofline terms.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    logz = m[..., 0] + jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1))
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=lf.dtype)
    ll = jnp.einsum("...v,...v->...", lf, onehot)
    loss = jnp.mean(logz - ll)
    if z_reg:
        loss = loss + z_reg * jnp.mean(jnp.square(logz))
    return loss


class Model(NamedTuple):
    cfg: ModelConfig
    init: Callable[..., Any]
    apply: Callable[..., Any]
    loss_fn: Callable[..., Any]
    init_cache: Callable[..., Any]
    decode_step: Callable[..., Any]
    dummy_batch: Callable[[ShapeConfig], Dict[str, Any]]
    input_specs: Callable[[ShapeConfig], Dict[str, Any]]


def federated_lm_loss(model: Model) -> Callable[[Any, Dict[str, Any]], jax.Array]:
    """The federated engine's ``loss_fn`` for an LM client: the engine
    gathers ``{"x", "y"}`` minibatches (``repro.data.FederatedTokens``), the
    model reads ``{"tokens", "labels"}``."""

    def loss(params, batch):
        return model.loss_fn(params, {"tokens": batch["x"], "labels": batch["y"]})[0]

    return loss


def build_model(cfg: ModelConfig) -> Model:
    if cfg.is_encoder_decoder:
        return _build_encdec(cfg)
    return _build_decoder_only(cfg)


# ----------------------------------------------------------------------
# decoder-only (dense / moe / ssm / hybrid / vlm)
# ----------------------------------------------------------------------


def _build_decoder_only(cfg: ModelConfig) -> Model:
    def init(rng):
        return transformer.init_params(rng, cfg)

    def apply(params, tokens, *, cache=None, cache_pos=None, parallel=None,
              kv_spec=None, remat="none", use_kernels=False, return_cache=False,
              scan_unroll=1):
        return transformer.forward(
            params, tokens, cfg=cfg, cache=cache, cache_pos=cache_pos,
            parallel=parallel, kv_spec=kv_spec, remat=remat, use_kernels=use_kernels,
            return_cache=return_cache, scan_unroll=scan_unroll,
        )

    def loss_fn(params, batch, *, parallel=None, remat="none", use_kernels=False,
                scan_unroll=1):
        logits, _, aux = apply(
            params, batch["tokens"], parallel=parallel, remat=remat,
            use_kernels=use_kernels, scan_unroll=scan_unroll,
        )
        xe = cross_entropy(logits, batch["labels"])
        return xe + aux, {"xent": xe, "aux": aux}

    def init_cache(params, batch, max_len):
        return transformer.init_cache(cfg, batch, max_len)

    def decode_step(params, token, cache, pos, *, parallel=None, kv_spec=None,
                    scan_unroll=1):
        logits, new_cache, _ = apply(
            params, token, cache=cache, cache_pos=pos, parallel=parallel,
            kv_spec=kv_spec, scan_unroll=scan_unroll,
        )
        return logits, new_cache

    def dummy_batch(shape: ShapeConfig):
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab_size, size=(shape.global_batch, shape.seq_len))
        return {
            "tokens": jnp.asarray(toks, jnp.int32),
            "labels": jnp.asarray(np.roll(toks, -1, axis=1), jnp.int32),
        }

    def input_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        if shape.kind in ("train", "prefill"):
            return {
                "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
            }
        # decode: one new token against an S-deep cache
        cache = jax.eval_shape(lambda: transformer.init_cache(cfg, B, S))
        return {
            "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "cache": cache,
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }

    return Model(cfg, init, apply, loss_fn, init_cache, decode_step, dummy_batch, input_specs)


# ----------------------------------------------------------------------
# encoder-decoder (seamless)
# ----------------------------------------------------------------------


def _build_encdec(cfg: ModelConfig) -> Model:
    def init(rng):
        return encdec.init_params(rng, cfg)

    def apply(params, batch, *, parallel=None, remat="none", use_kernels=False,
              scan_unroll=1):
        enc_out = encdec.encode(params, batch["src_embeds"], cfg=cfg,
                                parallel=parallel, remat=remat, scan_unroll=scan_unroll)
        logits = encdec.decode_train(
            params, batch["tgt_tokens"], enc_out, cfg=cfg, parallel=parallel,
            remat=remat, scan_unroll=scan_unroll,
        )
        return logits, None, jnp.float32(0.0)

    def loss_fn(params, batch, *, parallel=None, remat="none", use_kernels=False,
                scan_unroll=1):
        logits, _, _ = apply(params, batch, parallel=parallel, remat=remat,
                             scan_unroll=scan_unroll)
        xe = cross_entropy(logits, batch["labels"])
        return xe, {"xent": xe, "aux": jnp.float32(0.0)}

    def init_cache(params, batch, max_len, enc_out=None):
        if enc_out is None:
            raise ValueError("encdec cache needs enc_out")
        return encdec.init_decode_cache(params, cfg, batch, max_len, enc_out)

    def decode_step(params, token, cache, pos, *, parallel=None, kv_spec=None,
                    scan_unroll=1):
        return encdec.decode_step(
            params, token, cache, pos, cfg=cfg, parallel=parallel, kv_spec=kv_spec,
            scan_unroll=scan_unroll,
        )

    def dummy_batch(shape: ShapeConfig):
        rng = np.random.default_rng(0)
        B, S = shape.global_batch, shape.seq_len
        toks = rng.integers(0, cfg.vocab_size, size=(B, S))
        return {
            "src_embeds": jnp.asarray(
                rng.normal(size=(B, S, cfg.d_model)), jnp.dtype(cfg.dtype)
            ),
            "tgt_tokens": jnp.asarray(toks, jnp.int32),
            "labels": jnp.asarray(np.roll(toks, -1, axis=1), jnp.int32),
        }

    def input_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        adt = jnp.dtype(cfg.dtype)
        if shape.kind in ("train", "prefill"):
            return {
                "src_embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), adt),
                "tgt_tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
            }
        hd = cfg.resolved_head_dim
        L = cfg.n_layers
        cache = {
            "k": jax.ShapeDtypeStruct((L, B, S, cfg.n_kv_heads, hd), adt),
            "v": jax.ShapeDtypeStruct((L, B, S, cfg.n_kv_heads, hd), adt),
            "cross_k": jax.ShapeDtypeStruct((L, B, S, cfg.n_kv_heads, hd), adt),
            "cross_v": jax.ShapeDtypeStruct((L, B, S, cfg.n_kv_heads, hd), adt),
        }
        return {
            "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
            "cache": cache,
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }

    return Model(cfg, init, apply, loss_fn, init_cache, decode_step, dummy_batch, input_specs)
