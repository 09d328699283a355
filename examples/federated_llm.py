"""FedCM over a transformer LM — the cross-silo path, on a reduced config.

Each client is an organization holding a corpus with its own token
distribution (its own Markov chain mixed with a shared one = natural
heterogeneity).  FedCM federates a reduced llama3-family model across
them through the engine's fused ``run_rounds`` scan, with the same data
layout and loss adapter as the real-width client of ``chip_smoke.py``.

    PYTHONPATH=src python examples/federated_llm.py
"""
import numpy as np

import jax

from repro.configs.base import FedConfig, get_config, reduced
from repro.core import FederatedEngine
from repro.data import FederatedTokens, make_federated_lm_corpus
from repro.models import build_model, federated_lm_loss

N_CLIENTS = 8
SEQ, BATCH = 64, 4


def main(rounds: int = 20, echo: bool = True):
    """Returns the per-round local losses."""
    cfg = reduced(get_config("llama3.2-1b"))
    model = build_model(cfg)
    data = FederatedTokens.from_sequences(
        make_federated_lm_corpus(cfg.vocab_size, N_CLIENTS, 256, SEQ + 1, seed=0)
    )
    cfg_fed = FedConfig(algo="fedcm", num_clients=N_CLIENTS, cohort_size=3,
                        local_steps=4, alpha=0.1, eta_l=0.05, eta_g=1.0,
                        weight_decay=1e-4, rounds=rounds)
    eng = FederatedEngine(cfg_fed, federated_lm_loss(model), batch_size=BATCH)
    state = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    if echo:
        print(f"federating {cfg.name} (~{cfg.param_count()/1e6:.1f}M params) "
              f"across {N_CLIENTS} heterogeneous corpora with FedCM\n")
    state, ms = eng.run_rounds(state, data, rounds)
    losses = np.asarray(ms.loss)
    if echo:
        for r in range(4, rounds, 5):
            print(f"round {r+1:3d}  local-loss={losses[r]:.4f}  "
                  f"|Δ_t|={float(ms.momentum_norm[r]):.4f}  "
                  f"active={int(ms.n_active[r])}")
        print(f"\nloss {losses[0]:.3f} → {losses[-1]:.3f} "
              f"(uniform {np.log(cfg.vocab_size):.3f})")
    return losses


if __name__ == "__main__":
    losses = main()
    assert losses[-1] < losses[0]
