"""Synthetic datasets (the container has no CIFAR on disk; see DESIGN.md §2).

Three generators:

* ``make_synthetic_classification`` — Gaussian-mixture vectors; linearly
  non-separable (class means + per-class rotations), learnable by an MLP.
  Stands in for CIFAR10/100 in the scaled paper reproduction.
* ``make_synthetic_images`` — tiny (C,H,W) images built from per-class
  frequency templates + noise; learnable by a small CNN.
* ``make_synthetic_lm`` — token streams from a random first-order Markov
  chain (low-entropy rows), so next-token loss has real signal; used for the
  centralized-LM example.
* ``make_federated_lm_corpus`` — one sparse Markov corpus per client over
  a real-size vocabulary (clients mix a shared chain with their own =
  natural heterogeneity); the federated LM client's data.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def make_synthetic_classification(
    n_classes: int = 10,
    dim: int = 32,
    n_train: int = 50_000,
    n_test: int = 10_000,
    noise: float = 1.0,
    separation: float = 2.0,
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Returns (x_train, y_train, x_test, y_test); x float32, y int32.

    ``separation``/``noise`` set the Bayes error: separation=2, noise=1 is
    near-separable; separation~0.9, noise~2 gives a CIFAR-like irreducible
    error band where optimizer differences are visible."""
    rng = np.random.default_rng(seed)
    means = rng.normal(size=(n_classes, dim)) * separation
    # per-class linear map to make the task non-trivial for linear models
    maps = rng.normal(size=(n_classes, dim, dim)) * (0.3 / np.sqrt(dim))

    def sample(n):
        y = rng.integers(0, n_classes, size=n).astype(np.int32)
        eps = rng.normal(size=(n, dim)).astype(np.float32)
        x = means[y] + np.einsum("nij,nj->ni", maps[y], eps) + noise * rng.normal(size=(n, dim))
        return x.astype(np.float32), y

    x_tr, y_tr = sample(n_train)
    x_te, y_te = sample(n_test)
    return x_tr, y_tr, x_te, y_te


def make_synthetic_images(
    n_classes: int = 10,
    hw: int = 8,
    channels: int = 3,
    n_train: int = 20_000,
    n_test: int = 4_000,
    noise: float = 0.6,
    seed: int = 0,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Tiny images: class template (smooth random field) + noise. NHWC float32."""
    rng = np.random.default_rng(seed)
    # smooth templates: low-frequency random fields per class
    freqs = rng.normal(size=(n_classes, channels, 3, 3))
    yy, xx = np.meshgrid(np.linspace(0, 1, hw), np.linspace(0, 1, hw), indexing="ij")
    basis = np.stack(
        [np.ones_like(xx), np.sin(2 * np.pi * xx), np.sin(2 * np.pi * yy),
         np.cos(2 * np.pi * xx), np.cos(2 * np.pi * yy), np.sin(4 * np.pi * xx),
         np.sin(4 * np.pi * yy), np.sin(2 * np.pi * (xx + yy)), np.cos(2 * np.pi * (xx - yy))],
        axis=-1,
    )  # (hw, hw, 9)
    templates = np.einsum("hwb,ncb->nchw", basis, freqs.reshape(n_classes, channels, 9))

    def sample(n):
        y = rng.integers(0, n_classes, size=n).astype(np.int32)
        x = templates[y] + noise * rng.normal(size=(n, channels, hw, hw))
        return np.transpose(x, (0, 2, 3, 1)).astype(np.float32), y  # NHWC

    x_tr, y_tr = sample(n_train)
    x_te, y_te = sample(n_test)
    return x_tr, y_tr, x_te, y_te


def make_synthetic_lm(
    vocab_size: int = 512,
    seq_len: int = 256,
    n_seqs: int = 4096,
    temperature: float = 0.3,
    seed: int = 0,
    transition: np.ndarray | None = None,
) -> np.ndarray:
    """(n_seqs, seq_len) int32 tokens from a first-order Markov chain.

    ``temperature`` controls row entropy (lower = more predictable = lower
    achievable loss).  Pass ``transition`` to share/perturb chains across
    federated clients.
    """
    rng = np.random.default_rng(seed)
    if transition is None:
        logits = rng.normal(size=(vocab_size, vocab_size)) / max(temperature, 1e-3)
        transition = _softmax(logits)
    toks = np.empty((n_seqs, seq_len), dtype=np.int32)
    state = rng.integers(0, vocab_size, size=n_seqs)
    toks[:, 0] = state
    # vectorized chain stepping via inverse-CDF sampling
    cdf = np.cumsum(transition, axis=1)
    for t in range(1, seq_len):
        u = rng.random(n_seqs)
        state = (cdf[state] < u[:, None]).sum(axis=1)
        state = np.minimum(state, vocab_size - 1)
        toks[:, t] = state
    return toks


# make_federated_lm_corpus: successors per token, probability of a step
# along the chain all clients share, Zipf exponent of token frequencies
LM_BRANCHING = 8
LM_SHARED = 0.6
LM_ZIPF = 1.1


def make_federated_lm_corpus(
    vocab_size: int,
    n_clients: int,
    n_seqs: int,
    seq_len: int,
    *,
    seed: int = 0,
) -> np.ndarray:
    """(n_clients, n_seqs, seq_len) int32 tokens, one Markov corpus per client.

    Every token has ``LM_BRANCHING`` equally likely successors, drawn with
    Zipf(``LM_ZIPF``) token frequencies as in text.  Each step follows a
    chain shared by all clients with probability ``LM_SHARED`` and the
    client's own chain otherwise; each client ranks the vocabulary in its
    own order, so the corpora are heterogeneous but related.  The chains
    are ``(V, LM_BRANCHING)`` successor tables, so a real-size vocabulary
    costs O(V·LM_BRANCHING), not the O(V²) of a dense transition matrix.
    """
    rng = np.random.default_rng(seed)
    freq = 1.0 / np.arange(1, vocab_size + 1) ** LM_ZIPF
    freq /= freq.sum()

    def successors(ranking):
        return ranking[rng.choice(vocab_size, size=(vocab_size, LM_BRANCHING), p=freq)]

    base = successors(np.arange(vocab_size))
    own = np.stack([successors(rng.permutation(vocab_size))
                    for _ in range(n_clients)])
    client = np.arange(n_clients)[:, None]
    toks = np.empty((n_clients, n_seqs, seq_len), dtype=np.int32)
    state = rng.integers(0, vocab_size, size=(n_clients, n_seqs))
    toks[..., 0] = state
    for t in range(1, seq_len):
        pick = rng.integers(0, LM_BRANCHING, size=state.shape)
        from_base = rng.random(state.shape) < LM_SHARED
        state = np.where(from_base, base[state, pick], own[client, state, pick])
        toks[..., t] = state
    return toks


def make_markov_transition(vocab_size: int, temperature: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(vocab_size, vocab_size)) / max(temperature, 1e-3)
    return _softmax(logits)


def _softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max(axis=-1, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=-1, keepdims=True)
