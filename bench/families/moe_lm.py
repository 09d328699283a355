"""A sparse-expert decoder-only LM client (Mellum2's block) on per-client
Markov token corpora, trained through ``FederatedEngine.run_rounds`` with
one metrics fetch per chunk.  The configuration's file gives the
architecture in the source's own key names: ``layer_types`` a repeated
period of sliding-window layers and one full-attention layer, RoPE by
layer type (``rope_parameters``), every MLP sparse.  ``num_experts`` is
the experts each layer holds here, ``published.num_experts`` the experts
its router routes over; the layer is dropless over the held share.

The work counts (``round_counts``) are this family's, beside
``bench.counts`` for the dense decoder."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import counts
from bench.cell import Cell
from bench.families import fed_config
from bench.gen.lm_corpus import federated_lm_corpus
from bench.reference import moe_lm as ref_moe_lm
from repro.configs.base import ModelConfig, YarnRope
from repro.core import FederatedEngine
from repro.models import build_model, federated_lm_loss

SLIDING, FULL = "sliding_attention", "full_attention"


def _period(config: dict) -> int:
    """Length of the layer pattern: sliding layers, then one full layer,
    repeated over the layers held here."""
    L = config["num_hidden_layers"]
    types = config["layer_types"][:L]
    p = types.index(FULL) + 1 if FULL in types else 0
    if not p or L % p or types != ([SLIDING] * (p - 1) + [FULL]) * (L // p):
        raise ValueError(f"layer_types {types} are not whole periods of sliding layers then full")
    if config["mlp_layer_types"][:L] != ["sparse"] * L or not config["norm_topk_prob"]:
        raise ValueError("this family runs sparse MLPs with renormalised top-k gates only")
    return p


def model_config(config: dict) -> ModelConfig:
    p = _period(config)
    rope = config["rope_parameters"]
    full, local = rope[FULL], rope[SLIDING]
    if full["rope_type"] != "yarn" or local["rope_type"] != "default":
        raise ValueError(f"rope_parameters {rope} are not YaRN full and default sliding layers")
    if full["rope_theta"] != local["rope_theta"]:
        raise ValueError(f"rope_parameters {rope} give the two layer types different θ")
    return ModelConfig(
        name=config["name"], family="moe",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["moe_intermediate_size"],
        vocab_size=config["vocab_size"], use_rope=True, rope_theta=full["rope_theta"],
        rope_yarn=YarnRope(factor=full["factor"],
                           original_max_position_embeddings=full["original_max_position_embeddings"],
                           beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
                           attention_factor=full["attention_factor"]),
        sliding_window=config["sliding_window"], local_global_pattern=(p - 1, 1),
        mlp_type="gated_silu", n_experts=config["published"]["num_experts"],
        n_experts_held=config["num_experts"], top_k=config["num_experts_per_tok"],
        capacity_factor=None, router_z_loss=0.0, load_balance_loss=0.0,
        dtype=config["activation_dtype"], param_dtype=config["parameter_dtype"])


def init_params(key, config: dict):
    """Seeded weights in the program's tree layout: normal with std
    1/sqrt(fan_in) for the layers and the router, 0.02 for both
    vocabulary tables, norm scales stored as (scale - 1) = 0."""
    D, V = config["hidden_size"], config["vocab_size"]
    F, E, E_all = (config["moe_intermediate_size"], config["num_experts"],
                   config["published"]["num_experts"])
    H, Hkv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                  config["head_dim"])
    p = _period(config)
    n = config["num_hidden_layers"] // p
    k = iter(jax.random.split(key, 2 + 8 * p))

    def normal(shape, std):
        return std * jax.random.normal(next(k), shape, jnp.float32)

    def slot():
        return {
            "norm1": jnp.zeros((n, D), jnp.float32),
            "attn": {"wq": normal((n, D, H, hd), D ** -0.5),
                     "wk": normal((n, D, Hkv, hd), D ** -0.5),
                     "wv": normal((n, D, Hkv, hd), D ** -0.5),
                     "wo": normal((n, H, hd, D), (H * hd) ** -0.5)},
            "norm2": jnp.zeros((n, D), jnp.float32),
            "moe": {"router": normal((n, D, E_all), D ** -0.5),
                    "w_gate": normal((n, E, D, F), D ** -0.5),
                    "w_up": normal((n, E, D, F), D ** -0.5),
                    "w_down": normal((n, E, F, D), F ** -0.5)},
        }

    return {
        "embed": normal((V, D), 0.02),
        "final_norm": jnp.zeros((D,), jnp.float32),
        "periods": {f"slot{i}": slot() for i in range(p)},
        "unembed": normal((D, V), 0.02),
    }


def param_counts(cfg: dict) -> dict:
    """Parameter counts with untied embeddings and no biases: ``total``
    as held here, ``matmul`` the matmul parameters one token runs through
    in expectation (attention, the router, top_k × held / routed of one
    expert's SwiGLU, the unembedding; the embedding lookup needs none)."""
    D, V, F = cfg["hidden_size"], cfg["vocab_size"], cfg["moe_intermediate_size"]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    L, E, E_all = cfg["num_hidden_layers"], cfg["num_experts"], cfg["published"]["num_experts"]
    attn = D * H * hd * 2 + D * Hkv * hd * 2
    router = D * E_all
    expert = 3 * D * F
    per_token = cfg["num_experts_per_tok"] * E / E_all
    total = 2 * V * D + L * (attn + router + E * expert) + (2 * L + 1) * D
    return {"total": total, "matmul": L * (attn + router + per_token * expert) + V * D}


def round_counts(cfg, batch_size, seq_len, local_steps, n_active) -> dict:
    """6 operations per matmul parameter per token, plus the attention
    scores and their weighted sum: 12 per layer, head dimension and key
    position per token, forward and backward, counting every key position
    as the program computes them (``bench.counts.lm_round``'s rule); the
    plane bytes of ``bench.counts`` at the held parameter count."""
    c = param_counts(cfg)
    tokens = n_active * local_steps * batch_size * seq_len
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * cfg["head_dim"] * seq_len
    return counts._planes(c["total"], local_steps, n_active, flops=(6 * c["matmul"] + attn) * tokens)


def build(config: dict, traffic: dict, chips: int) -> Cell:
    model = build_model(model_config(config))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    have = jax.eval_shape(partial(init_params, config=config), jax.random.PRNGKey(0))
    if (jax.tree_util.tree_structure(want) != jax.tree_util.tree_structure(have)
            or jax.tree_util.tree_leaves(want) != jax.tree_util.tree_leaves(have)):
        raise ValueError(f"benchmark weights do not match the program's layout:\n{want}\n{have}")
    cfg = fed_config(traffic)
    engine = FederatedEngine(cfg, federated_lm_loss(model), batch_size=traffic["batch_size"])
    data = traffic["data"]

    def make_data(seed):
        seqs = federated_lm_corpus(config["vocab_size"], cfg.num_clients,
                                   data["seqs_per_client"], data["seq_len"] + 1, seed)
        return {"client_x": seqs[..., :-1], "client_y": seqs[..., 1:]}

    def ref_loss(params, batch, num):
        return ref_moe_lm.loss(params, batch, num, config)

    def work(n_active):
        return round_counts(config, traffic["batch_size"], data["seq_len"], cfg.local_steps,
                            n_active)

    return Cell(engine=engine, fed=traffic["fed"], batch_size=traffic["batch_size"],
                chunk=traffic["chunk"], init_params=jax.jit(partial(init_params, config=config)),
                make_data=make_data, ref_loss=ref_loss, control=config["control"], work=work)
