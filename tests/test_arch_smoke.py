"""Per-architecture smoke tests (deliverable (f)).

For every assigned architecture: instantiate the REDUCED same-family
variant (≤2 layers, d_model ≤ 512, ≤ 4 experts), run one forward and one
train step on CPU, assert output shapes and finiteness; run one decode step
where the family decodes.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, ShapeConfig, get_config, reduced
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import Knobs, build_train_step
from repro.models import build_model

SMOKE_SHAPE = ShapeConfig("smoke", "train", seq_len=16, global_batch=2)


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    arch = request.param
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return arch, cfg, model, params


def _assert_finite(tree, what):
    for leaf in jax.tree_util.tree_leaves(tree):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))), what


def test_reduced_respects_assignment_contract():
    for arch in ARCH_IDS:
        cfg = reduced(get_config(arch))
        assert cfg.n_layers <= 2
        assert cfg.d_model <= 512
        assert cfg.n_experts <= 4


def test_full_configs_match_assignment():
    """The FULL configs must carry the exact assigned hyperparameters."""
    expect = {
        "starcoder2-7b": (32, 4608, 36, 4, 18432, 49152),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "mellum2-12b-a2.5b": (28, 2304, 32, 4, 896, 98304),
    }
    for arch, (L, D, H, Hkv, F, V) in expect.items():
        cfg = get_config(arch)
        assert cfg.n_layers == L, arch
        assert cfg.d_model == D, arch
        assert cfg.n_heads == H, arch
        assert cfg.n_kv_heads == Hkv, arch
        assert cfg.d_ff == F, arch
        assert cfg.vocab_size == V, arch
    # family-specific extras
    assert get_config("llama4-maverick-400b-a17b").n_experts == 128
    assert get_config("llama4-maverick-400b-a17b").top_k == 1
    assert get_config("dbrx-132b").n_experts == 16
    assert get_config("dbrx-132b").top_k == 4
    assert get_config("zamba2-7b").ssm_state == 64
    assert get_config("mamba2-1.3b").ssm_state == 128
    assert get_config("gemma3-12b").local_global_pattern == (5, 1)
    assert get_config("qwen3-14b").qk_norm
    assert get_config("seamless-m4t-large-v2").is_encoder_decoder
    mellum = get_config("mellum2-12b-a2.5b")
    assert (mellum.n_experts, mellum.n_experts_held, mellum.top_k) == (64, 8, 8)
    assert mellum.capacity_factor is None and mellum.local_global_pattern == (3, 1)
    assert mellum.rope_yarn.factor == 16 and mellum.sliding_window == 1024


def test_forward_shapes_and_finiteness(arch_setup):
    arch, cfg, model, params = arch_setup
    batch = model.dummy_batch(SMOKE_SHAPE)
    if cfg.is_encoder_decoder:
        logits, _, aux = model.apply(params, batch)
    else:
        logits, cache, aux = model.apply(params, batch["tokens"])
        assert cache is None
    B, S = SMOKE_SHAPE.global_batch, SMOKE_SHAPE.seq_len
    assert logits.shape == (B, S, cfg.vocab_size), arch
    _assert_finite(logits, f"{arch} logits")
    _assert_finite(aux, f"{arch} aux")


def test_train_step_runs_and_is_finite(arch_setup):
    arch, cfg, model, params = arch_setup
    mesh = make_test_mesh()
    knobs = Knobs(remat="none", param_dtype="float32", learning_rate=1e-3)
    bundle = build_train_step(cfg, SMOKE_SHAPE, mesh, knobs)
    from repro.optim.optimizers import adamw

    opt = adamw(1e-3)
    opt_state = opt.init(params)
    batch = model.dummy_batch(SMOKE_SHAPE)
    new_params, new_opt, metrics = jax.jit(bundle.fn)(params, opt_state, batch)
    assert float(metrics["loss"]) > 0.0, arch
    assert np.isfinite(float(metrics["loss"])), arch
    assert np.isfinite(float(metrics["grad_norm"])), arch
    _assert_finite(new_params, f"{arch} updated params")
    # params must actually move
    moved = any(
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) > 0
        for a, b in zip(jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(new_params))
    )
    assert moved, arch


def test_loss_decreases_over_a_few_steps(arch_setup):
    arch, cfg, model, params = arch_setup
    from repro.optim.optimizers import adamw
    from repro.utils.trees import tree_add

    opt = adamw(3e-3)
    opt_state = opt.init(params)
    batch = model.dummy_batch(SMOKE_SHAPE)

    @jax.jit
    def step(p, s):
        (l, _), g = jax.value_and_grad(lambda q: model.loss_fn(q, batch), has_aux=True)(p)
        u, s = opt.update(g, s, p)
        return tree_add(p, u), s, l

    losses = []
    for _ in range(8):
        params, opt_state, l = step(params, opt_state)
        losses.append(float(l))
    assert losses[-1] < losses[0], (arch, losses)


def test_decode_step(arch_setup):
    arch, cfg, model, params = arch_setup
    B, maxlen = 2, 8
    if cfg.is_encoder_decoder:
        from repro.models import encdec

        enc_out = encdec.encode(
            params, jnp.zeros((B, 4, cfg.d_model), jnp.float32), cfg=cfg
        )
        cache = model.init_cache(params, B, maxlen, enc_out=enc_out)
    else:
        cache = model.init_cache(params, B, maxlen)
    tok = jnp.zeros((B, 1), jnp.int32)
    logits, new_cache = model.decode_step(params, tok, cache, jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size), arch
    _assert_finite(logits, f"{arch} decode logits")
    # cache structure unchanged
    assert jax.tree_util.tree_structure(cache) == jax.tree_util.tree_structure(new_cache)


def test_input_specs_are_abstract(arch_setup):
    arch, cfg, model, params = arch_setup
    for name, kind, S, B in [("train_s", "train", 32, 2), ("dec_s", "decode", 32, 2)]:
        specs = model.input_specs(ShapeConfig(name, kind, S, B))
        for leaf in jax.tree_util.tree_leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct), (arch, name)


def test_param_count_estimate_close_to_actual(arch_setup):
    """cfg.param_count() (used for MODEL_FLOPS) ≈ the real init'd count."""
    arch, cfg, model, params = arch_setup
    actual = sum(x.size for x in jax.tree_util.tree_leaves(params))
    est = cfg.param_count()
    assert abs(est - actual) / actual < 0.35, (arch, est, actual)
