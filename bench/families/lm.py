"""A dense decoder-only LM client on per-client Markov token corpora,
trained through ``FederatedEngine.run_rounds`` with one metrics fetch per
chunk.  The configuration's file gives the architecture in the source's
own key names."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import counts
from bench.cell import Cell
from bench.families import fed_config
from bench.gen.lm_corpus import federated_lm_corpus
from bench.reference import lm as ref_lm
from repro.configs.base import ModelConfig
from repro.core import FederatedEngine
from repro.models import build_model, federated_lm_loss


def model_config(config: dict) -> ModelConfig:
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"], n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], use_rope=True, rope_theta=config["rope_theta"],
        mlp_type="gelu", sliding_window=config["sliding_window"],
        dtype=config["activation_dtype"], param_dtype=config["parameter_dtype"])


def init_params(key, config: dict):
    """Seeded weights in the program's tree layout: normal with std
    1/sqrt(fan_in) for the layers, 0.02 for both vocabulary tables, norm
    scales stored as (scale - 1) = 0."""
    D, V, F = config["hidden_size"], config["vocab_size"], config["intermediate_size"]
    H, Hkv, hd = (config["num_attention_heads"], config["num_key_value_heads"],
                  config["head_dim"])
    L = config["num_hidden_layers"]
    k = iter(jax.random.split(key, 8))

    def normal(shape, std):
        return std * jax.random.normal(next(k), shape, jnp.float32)

    return {
        "embed": normal((V, D), 0.02),
        "final_norm": jnp.zeros((D,), jnp.float32),
        "periods": {"slot0": {
            "norm1": jnp.zeros((L, D), jnp.float32),
            "attn": {"wq": normal((L, D, H, hd), D ** -0.5),
                     "wk": normal((L, D, Hkv, hd), D ** -0.5),
                     "wv": normal((L, D, Hkv, hd), D ** -0.5),
                     "wo": normal((L, H, hd, D), (H * hd) ** -0.5)},
            "norm2": jnp.zeros((L, D), jnp.float32),
            "mlp": {"w_up": normal((L, D, F), D ** -0.5),
                    "w_down": normal((L, F, D), F ** -0.5)},
        }},
        "unembed": normal((D, V), 0.02),
    }


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
        return ref_lm.loss(params, batch, num, config)

    def work(n_active):
        return counts.lm_round(config, traffic["batch_size"], data["seq_len"],
                               cfg.local_steps, n_active)

    return Cell(engine=engine, fed=traffic["fed"], batch_size=traffic["batch_size"],
                chunk=traffic["chunk"], init_params=jax.jit(partial(init_params, config=config)),
                make_data=make_data, ref_loss=ref_loss, control=config["control"], work=work)
