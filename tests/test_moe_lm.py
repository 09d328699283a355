"""The sparse-expert LM client (Mellum2's block) against its plain
reference, at a reduced size of the benchmark cell's shape: 4 layers
``sssf`` with a window under the sequence, 8 routed experts of which the
layer holds 2, top-2, YaRN RoPE on the full layer and default RoPE on the
sliding ones.

The reference is ``bench/reference/moe_lm.py``, which imports nothing of
the program; the configuration is ``bench/configs/mellum2-12b-a2.5b-4l.json``
with its sizes cut, mapped to the program by ``bench/families/moe_lm.py``.
Also here: the dropless layer under adversarial routing, the share test
(disjoint held shares add up to the uncut layer), YaRN at factor 1, the
grouped matmul under ``vmap`` and ``grad``, and the layer's two named
scopes in the compiled round program.
"""
import json
import math
import re
import sys
from dataclasses import replace
from functools import partial
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.families import moe_lm as family  # noqa: E402
from bench.reference import moe_lm as ref  # noqa: E402
from bench.reference.numerics import numerics  # noqa: E402
from repro.configs.base import FedConfig, ModelConfig, YarnRope  # noqa: E402
from repro.core import FederatedEngine  # noqa: E402
from repro.models import build_model, federated_lm_loss  # noqa: E402
from repro.models import layers  # noqa: E402

CONFIG = json.loads((ROOT / "bench" / "configs" / "mellum2-12b-a2.5b-4l.json").read_text())
F32 = numerics("f32")


def tiny_config(dtype="float32", **kw):
    c = json.loads(json.dumps(CONFIG))
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             moe_intermediate_size=32, vocab_size=128, num_experts=2, num_experts_per_tok=2,
             sliding_window=6, activation_dtype=dtype,
             published={**c["published"], "num_experts": 8}, **kw)
    return c


def _batch(c, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c["vocab_size"], size=(B, S + 1))
    return {"x": jnp.asarray(toks[:, :-1], jnp.int32), "y": jnp.asarray(toks[:, 1:], jnp.int32)}


def test_loss_and_gradient_match_reference():
    """Program (build_model -> federated_lm_loss) and reference on the
    same seeded weights, both in float32.  Tolerances: the two sum in
    different orders (the program's grouped matmuls and scatter against
    the reference's dense experts), so the loss agrees to 1e-5 relative
    and each gradient leaf to 1e-4 of its largest entry."""
    c = tiny_config()
    model = build_model(family.model_config(c))
    params = family.init_params(jax.random.PRNGKey(3), c)
    batch = _batch(c)
    lp, gp = jax.value_and_grad(federated_lm_loss(model))(params, batch)
    lr, gr = jax.value_and_grad(partial(ref.loss, num=F32, cfg=c))(params, batch)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gp)[0],
                            jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b)))
        assert scale > 0, jax.tree_util.keystr(path)
        err = float(jnp.max(jnp.abs(a - b))) / scale
        assert err < 1e-4, (jax.tree_util.keystr(path), err)


def test_sliding_and_full_layers_differ():
    """The reference comparison above would not see a mix-up of the layer
    types if they computed alike: at S = 16 over a window of 6 the full
    layer's mask and YaRN RoPE change the loss."""
    c = tiny_config()
    params = family.init_params(jax.random.PRNGKey(3), c)
    batch = _batch(c)
    base = float(ref.loss(params, batch, F32, c))
    flat = dict(c, layer_types=["sliding_attention"] * 4)
    assert abs(float(ref.loss(params, batch, F32, flat)) - base) > 1e-4


def _moe_cfg(held):
    return ModelConfig(name="moe-test", family="moe", n_layers=1, d_model=32, n_heads=2,
                       n_kv_heads=1, d_ff=16, vocab_size=64, mlp_type="gated_silu",
                       n_experts=8, n_experts_held=held, top_k=2, capacity_factor=None,
                       router_z_loss=0.0, load_balance_loss=0.0)


def _ref_ffn(p, x, cfg):
    c = {"num_experts": cfg.experts_held, "num_experts_per_tok": cfg.top_k}
    return ref.moe_ffn(p, x, c, F32)


def test_adversarial_routing_drops_nothing():
    """Every token routes both its choices to the two held experts, so all
    T * k rows are held: the dropless layer computes each of them and
    equals the dense reference, where a capacity of 1.25x the mean load
    drops most."""
    cfg = _moe_cfg(2)
    p = layers.init_moe(jax.random.PRNGKey(0), cfg)
    p["router"] = p["router"].at[:, 0].set(10.0).at[:, 1].set(9.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model)))
    _, experts, _, _ = layers._router(p, x.reshape(-1, cfg.d_model), cfg)
    assert np.all(np.sort(np.asarray(experts), axis=1) == [0, 1])
    out, aux = layers.moe_block(p, x, cfg=cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_ref_ffn(p, x, cfg)),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) == 0.0
    dropped, _ = layers.moe_block(p, x, cfg=replace(cfg, capacity_factor=1.25))
    assert float(jnp.max(jnp.abs(dropped - out))) > 1e-2


def test_held_shares_sum_to_uncut_layer():
    """The share test: the outputs of the 4 disjoint shares of 2 experts
    (the layer told it holds [2j, 2j + 2)) add up to the layer that holds
    all 8, and to the reference's uncut layer."""
    full = _moe_cfg(8)
    p = layers.init_moe(jax.random.PRNGKey(0), full)
    xt = jax.random.normal(jax.random.PRNGKey(2), (40, full.d_model))
    whole, _ = layers.moe_dropless(p, xt, cfg=full, e_lo=0)
    share_cfg = _moe_cfg(2)
    parts = []
    for j in range(4):
        share = dict(p, **{k: p[k][2 * j: 2 * j + 2] for k in ("w_gate", "w_up", "w_down")})
        parts.append(layers.moe_dropless(share, xt, cfg=share_cfg, e_lo=2 * j)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(_ref_ffn(p, xt[None], full)[0]),
                               rtol=1e-5, atol=1e-5)
    assert all(float(jnp.max(jnp.abs(q))) > 0 for q in parts)


def test_grouped_matmul_under_vmap_and_grad():
    """The grouped matmul, batched as the engine's cohort map batches it,
    against a dense product by each row's own group (rows past the groups
    give 0), values and gradients."""
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 20, 6))
    w = jax.random.normal(k[1], (2, 3, 6, 5))
    sizes = jnp.asarray([[4, 0, 9], [1, 2, 17]], jnp.int32)

    def dense(x, w, sizes):
        ends = jnp.cumsum(sizes)
        group = jnp.searchsorted(ends, jnp.arange(x.shape[0]), side="right")
        wr = jnp.take(w, jnp.minimum(group, w.shape[0] - 1), axis=0)
        return jnp.where((group < w.shape[0])[:, None], jnp.einsum("mk,mkn->mn", x, wr), 0.0)

    def f(fn):
        return jax.vmap(jax.value_and_grad(lambda x, w, s: jnp.sum(jnp.sin(fn(x, w, s))),
                                           argnums=(0, 1)))(x, w, sizes)

    got, want = f(layers.grouped_matmul), f(dense)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_yarn_at_factor_one_is_default_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 2, 16))
    pos = jnp.arange(12, dtype=jnp.int32)
    one = YarnRope(factor=1.0, original_max_position_embeddings=8192, beta_fast=32.0,
                   beta_slow=1.0, attention_factor=1.0)
    np.testing.assert_allclose(np.asarray(layers.apply_rope(x, pos, 5e5, one)),
                               np.asarray(layers.apply_rope(x, pos, 5e5)), rtol=1e-6, atol=1e-6)
    section = {"rope_type": "yarn", "rope_theta": 5e5, "factor": 1.0,
               "original_max_position_embeddings": 8192, "beta_fast": 32, "beta_slow": 1}
    inv, scale = ref.rope_frequencies(16, section)
    base, _ = ref.rope_frequencies(16, {"rope_type": "default", "rope_theta": 5e5})
    np.testing.assert_allclose(np.asarray(inv), np.asarray(base), rtol=1e-6)
    assert scale == 1.0


def test_yarn_frequencies_at_mellum2():
    """Mellum2's full layers (head 128, θ 5e5, factor 16 over 8,192
    positions, β 32/1): the ramp runs over dimensions 18 to 35; below it
    the frequencies are the default ones, from its end on a sixteenth; the
    program and the reference agree."""
    full = CONFIG["rope_parameters"]["full_attention"]
    yarn = family.model_config(CONFIG).rope_yarn
    assert layers.yarn_ramp(128, 5e5, yarn) == (18, 35)
    got = np.asarray(layers.rope_frequencies(128, 5e5, yarn))
    base = np.asarray(layers.rope_frequencies(128, 5e5))
    np.testing.assert_allclose(got[:18], base[:18], rtol=1e-6)
    np.testing.assert_allclose(got[35:], base[35:] / 16, rtol=1e-6)
    assert np.all(got[18:35] <= base[18:35]) and np.all(got[18:35] >= base[18:35] / 16)
    inv, scale = ref.rope_frequencies(128, full)
    np.testing.assert_allclose(got, np.asarray(inv), rtol=1e-6)
    assert scale == yarn.attention_factor == pytest.approx(0.1 * math.log(16) + 1)


# ---------------------------------------------------------------- scopes

DISPATCH, EXPERTS = "moe.dispatch", "moe.experts"


def _round_hlo(model_cfg, vocab):
    model = build_model(model_cfg)
    cfg = FedConfig(algo="fedcm", num_clients=4, cohort_size=2, local_steps=2,
                    participation="fixed")
    eng = FederatedEngine(cfg, federated_lm_loss(model), batch_size=2)
    state = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    tokens = jnp.zeros((4, 4, 16), jnp.int32) + (jnp.arange(16) % vocab)
    return eng._run_rounds.lower(state, tokens, tokens, n_rounds=1).compile().as_text()


def _op_names(hlo):
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.fixture(scope="module")
def moe_round_hlo():
    return _round_hlo(family.model_config(tiny_config("bfloat16")), 128)


@pytest.mark.parametrize("scope", [DISPATCH, EXPERTS])
def test_moe_scopes_reach_forward_and_backward_ops(moe_round_hlo, scope):
    """Both scopes reach the op_names of the compiled round program of the
    tiny cell, in the forward pass and through autodiff in the backward
    (under ``transpose(jvp())``, the period scan's transpose), inside the
    clients' local steps."""
    names = [n for n in _op_names(moe_round_hlo) if scope in n]
    assert any("transpose(" not in n for n in names), scope
    assert any("transpose(jvp" in n for n in names), scope
    assert all("fedcm.local_steps" in n for n in names if n.startswith("jit("))


SC2_TINY = ModelConfig(name="sc2-tiny", family="dense", n_layers=1, d_model=64, n_heads=4,
                       n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=128, rope_theta=1e6,
                       mlp_type="gelu", sliding_window=8, dtype="bfloat16")


def _plain_rope(x, positions, theta, yarn=None):
    """RoPE as the dense path computed it before RoPE followed the layer
    type: one θ, no scaling."""
    assert yarn is None
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    angles = angles[None, :, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _strip_metadata(hlo):
    """The instructions, without their ``metadata`` and without the
    tables of source locations it points into."""
    lines = [ln for ln in hlo.splitlines()
             if ln.startswith((" ", "%", "ROOT", "ENTRY", "}")) or ln.startswith("HloModule")]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n".join(lines))


def test_dense_round_program_holds_no_moe_scope_and_is_unchanged(monkeypatch):
    """The StarCoder2-shaped round program (every layer sliding, one θ)
    holds neither MoE scope, and compiles to the same optimized HLO,
    metadata aside, as with RoPE computed the way it was before it
    followed the layer type."""
    hlo = _round_hlo(SC2_TINY, 128)
    assert not any(DISPATCH in n or EXPERTS in n for n in _op_names(hlo))
    monkeypatch.setattr(layers, "apply_rope", _plain_rope)
    assert _strip_metadata(_round_hlo(SC2_TINY, 128)) == _strip_metadata(hlo)
