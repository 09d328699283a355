"""Federated data pipeline.

``FederatedData`` holds the client-partitioned dataset as *stacked* arrays
(num_clients, n_per_client, ...) so an entire cohort's K local minibatches
can be gathered as one device-friendly array per round:

    batches = fed.sample_round_batches(rng, cohort_idx, K, batch_size)
    # -> {"x": (cohort, K, B, ...), "y": (cohort, K, B)}

which the round engine consumes with vmap(client)->scan(K).  On a mesh the
cohort axis is sharded over ("pod","data").

The gathers themselves live in the module-level pure functions
``gather_round_batches`` / ``gather_full_client_batch`` (arrays in, arrays
out, fully traceable) so the fused multi-round engine
(``FederatedEngine.run_rounds``) can draw minibatches *inside* its jitted
``lax.scan`` body instead of round-tripping to the host between rounds; the
``FederatedData`` methods are thin wrappers over the same functions.

This module assumes the whole population's data fits on device as one
stacked ``(N, n_per, ...)`` array — fine up to ~1e4 clients.  Beyond that,
``repro.data.population.StreamingClientData`` is the streaming counterpart:
it materializes ONLY the sampled cohort's shards per round on the host
(deterministically re-derived from ``(seed, client_id)``), pairing with the
out-of-core ``HostPopulationStore`` engine path.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.data.dirichlet import dirichlet_partition


def gather_round_batches(
    client_x: jax.Array,  # (N, n_per_client, ...)
    client_y: jax.Array,  # (N, n_per_client)
    rng: jax.Array,
    cohort_idx: jax.Array,  # (S,) int32 client ids
    local_steps: int,
    batch_size: int,
) -> Dict[str, jax.Array]:
    """Pure, jit-safe cohort minibatch gather: (S, K, B, ...) per field.

    Sampling is with replacement at the minibatch level (standard local SGD
    on small client datasets); shapes depend only on the static (S, K, B).
    """
    S = cohort_idx.shape[0]
    n_per = client_x.shape[1]
    idx = jax.random.randint(rng, (S, local_steps, batch_size), 0, n_per)
    x = client_x[cohort_idx[:, None, None], idx]
    y = client_y[cohort_idx[:, None, None], idx]
    return {"x": x, "y": y}


def gather_full_client_batch(
    client_x: jax.Array, client_y: jax.Array, client_ids: jax.Array
) -> Dict[str, jax.Array]:
    """Pure, jit-safe full-local-dataset gather (MimeLite's x_t gradient)."""
    return {"x": client_x[client_ids], "y": client_y[client_ids]}


class FederatedData:
    def __init__(
        self,
        x: np.ndarray,
        y: np.ndarray,
        num_clients: int,
        dirichlet_alpha: float = float("inf"),
        seed: int = 0,
    ) -> None:
        parts: List[np.ndarray] = dirichlet_partition(y, num_clients, dirichlet_alpha, seed=seed)
        n_per = min(len(p) for p in parts)
        self.num_clients = num_clients
        self.n_per_client = n_per
        self.client_x = jnp.asarray(np.stack([x[p[:n_per]] for p in parts]))  # (N, n, ...)
        self.client_y = jnp.asarray(np.stack([y[p[:n_per]] for p in parts]))  # (N, n)

    def sample_round_batches(
        self,
        rng: jax.Array,
        cohort_idx: jax.Array,  # (S,) int32 client ids
        local_steps: int,
        batch_size: int,
    ) -> Dict[str, jax.Array]:
        """Gather (S, K, B, ...) minibatches for the sampled cohort.

        Sampling is with replacement at the minibatch level (standard local
        SGD on small client datasets).  jit-safe: shapes depend only on
        (S, K, B).
        """
        return gather_round_batches(
            self.client_x, self.client_y, rng, cohort_idx, local_steps, batch_size
        )

    def full_client_batch(self, client_ids: jax.Array) -> Dict[str, jax.Array]:
        """Full local dataset for given clients (used by MimeLite's full-batch
        gradient at x_t)."""
        return gather_full_client_batch(self.client_x, self.client_y, client_ids)


class FederatedTokens(NamedTuple):
    """A federated LM corpus in the engine's layout: ``client_x`` holds the
    (N, n_per_client, S) input tokens and ``client_y`` the same sequences
    shifted by one (next-token labels).  ``gather_round_batches`` indexes
    both as they are; ``repro.models.model.federated_lm_loss`` maps the
    gathered ``{"x", "y"}`` to the model's ``{"tokens", "labels"}``."""

    client_x: jax.Array
    client_y: jax.Array

    @classmethod
    def from_sequences(cls, seqs) -> "FederatedTokens":
        """``seqs`` (N, n_per_client, S + 1) int tokens."""
        seqs = jnp.asarray(seqs, jnp.int32)
        return cls(seqs[..., :-1], seqs[..., 1:])


def lm_batch_iterator(
    tokens: np.ndarray,  # (n_seqs, seq_len+1) or (n_seqs, seq_len)
    batch_size: int,
    seed: int = 0,
):
    """Infinite iterator of {"tokens": (B, S), "labels": (B, S)} for LM training.

    Labels are the inputs shifted by one; the final position predicts the
    next-sequence's first token is avoided by trimming.
    """
    rng = np.random.default_rng(seed)
    n = tokens.shape[0]
    while True:
        idx = rng.integers(0, n, size=batch_size)
        batch = tokens[idx]
        yield {
            "tokens": jnp.asarray(batch[:, :-1], dtype=jnp.int32),
            "labels": jnp.asarray(batch[:, 1:], dtype=jnp.int32),
        }
