"""Cross-path model consistency: decode == forward, blocked == direct
attention, capacity-MoE ≈ dense-MoE, prefill cache == decode-built cache."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, reduced
from repro.models import build_model
from repro.models.layers import attend_blocked, attend_direct, moe_block, moe_ref

RNG = np.random.default_rng(0)

DECODE_ARCHS = ["llama3.2-1b", "mamba2-1.3b", "zamba2-7b", "gemma3-12b",
                "dbrx-132b", "starcoder2-7b", "qwen3-14b", "chameleon-34b",
                "llama4-maverick-400b-a17b"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    """Token-by-token decode reproduces the teacher-forced forward pass.

    MoE archs: capacity binds only under training token counts — raise the
    capacity factor so routing is drop-free and the paths are comparable
    (decode routes per-token and never drops)."""
    from dataclasses import replace

    cfg = reduced(get_config(arch))
    if cfg.n_experts:
        cfg = replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    T = 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, cfg.vocab_size)
    logits_full, _, _ = model.apply(params, toks)
    cache = model.init_cache(params, 2, T)
    outs = []
    for t in range(T):
        lg, cache = model.decode_step(params, toks[:, t : t + 1], cache, jnp.int32(t))
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(logits_full, np.float32),
        rtol=5e-3, atol=5e-3,
    )


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-1.3b", "gemma3-12b", "zamba2-7b"])
def test_prefill_cache_matches_decode_built_cache(arch):
    """Prefill's emitted cache lets decode continue exactly as if the prompt
    had been decoded token-by-token."""
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    P, G = 7, 3
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, P + G), 0, cfg.vocab_size)

    # path A: decode everything token by token
    cache_a = model.init_cache(params, 2, P + G)
    la = None
    for t in range(P + G):
        la, cache_a = model.decode_step(params, toks[:, t : t + 1], cache_a, jnp.int32(t))

    # path B: prefill P tokens, splice cache into a big buffer, decode G more
    _, pre_cache, _ = model.apply(params, toks[:, :P], return_cache=True)
    cache_b = model.init_cache(params, 2, P + G)

    def merge(dst, src):
        if (dst.ndim == src.ndim and dst.ndim >= 3 and dst.shape[:2] == src.shape[:2]
                and dst.shape[2] >= src.shape[2] and dst.shape[3:] == src.shape[3:]):
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), (0,) * dst.ndim)
        return src.astype(dst.dtype)

    cache_b = jax.tree_util.tree_map(merge, cache_b, pre_cache)
    lb = None
    for t in range(P, P + G):
        lb, cache_b = model.decode_step(params, toks[:, t : t + 1], cache_b, jnp.int32(t))
    np.testing.assert_allclose(
        np.asarray(la, np.float32), np.asarray(lb, np.float32), rtol=5e-3, atol=5e-3
    )


@pytest.mark.parametrize("window", [None, 13])
@pytest.mark.parametrize("q_block,kv_block", [(16, 16), (32, 16), (16, 32)])
def test_blocked_attention_matches_direct(window, q_block, kv_block):
    B, S, H, Hkv, hd = 2, 50, 4, 2, 16
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    pos = jnp.arange(S)
    msk = pos[:, None] >= pos[None, :]
    if window is not None:
        msk &= pos[:, None] - pos[None, :] < window
    ref = attend_direct(q, k, v, msk[None, None], hd**-0.5)
    out = attend_blocked(
        q, k, v, causal=True, window=window, scale=hd**-0.5,
        q_positions=pos, kv_positions=pos, q_block=q_block, kv_block=kv_block,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


def test_moe_dropping_matches_ref_at_high_capacity():
    """With capacity_factor high enough that nothing drops, the scatter/
    gather MoE must equal the dense masked reference exactly."""
    from dataclasses import replace

    cfg = reduced(get_config("dbrx-132b"))
    cfg = replace(cfg, capacity_factor=8.0)  # no drops
    from repro.models.layers import init_moe

    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.normal(size=(2, 12, cfg.d_model)), jnp.float32)
    out_d, aux_d = moe_block(p, x, cfg=cfg)
    out_r, aux_r = moe_ref(p, x, cfg=cfg)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_d), float(aux_r), rtol=1e-5)


def test_moe_capacity_drops_tokens_but_stays_finite():
    from dataclasses import replace

    cfg = reduced(get_config("dbrx-132b"))
    cfg = replace(cfg, capacity_factor=0.25)  # aggressive dropping
    from repro.models.layers import init_moe

    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(RNG.normal(size=(2, 64, cfg.d_model)), jnp.float32)
    out, aux = moe_block(p, x, cfg=cfg)
    assert np.all(np.isfinite(np.asarray(out)))
    # dropped tokens ⇒ output differs from the no-drop reference
    out_r, _ = moe_ref(p, x, cfg=cfg)
    assert float(jnp.max(jnp.abs(out - out_r))) > 1e-6


def test_gemma_local_global_period():
    cfg = get_config("gemma3-12b")
    from repro.models.transformer import period_layout

    slots, n_periods, tail = period_layout(cfg)
    assert len(slots) == 6 and n_periods == 8 and not tail
    assert [s.is_global for s in slots] == [False] * 5 + [True]


def test_zamba_shared_attention_is_shared():
    """All attention applications in the hybrid stack read ONE param set."""
    cfg = reduced(get_config("zamba2-7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert "shared" in params
    from repro.models.transformer import period_layout

    slots, n_periods, tail = period_layout(get_config("zamba2-7b"))
    n_attn = sum(1 for s in slots if s.shared)
    assert n_attn == 1 and slots[-1].shared
    # 81 layers, attn_every=6 → 13 periods of 6 + 3 tail mamba layers
    assert n_periods == 13 and len(tail) == 3


def test_llama4_moe_interleave():
    cfg = get_config("llama4-maverick-400b-a17b")
    from repro.models.transformer import period_layout

    slots, n_periods, _ = period_layout(cfg)
    assert len(slots) == 2
    assert [s.is_moe for s in slots] == [False, True]
    assert cfg.shared_expert


def test_vlm_image_token_mask_path():
    """Chameleon consumes early-fused discrete tokens; image tokens are just
    vocab ids — verify a mixed batch runs and positions are respected."""
    cfg = reduced(get_config("chameleon-34b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, _, _ = model.apply(params, toks)
    assert logits.shape == (2, 16, cfg.vocab_size)
