"""Seeded federated token corpora: one sparse Markov chain per client.

A copy of ``repro.data.synthetic.make_federated_lm_corpus`` (with its
module constants), kept here so that a change to the program cannot move
the benchmark's inputs.
"""
from __future__ import annotations

import numpy as np

BRANCHING = 8  # successors per token
SHARED = 0.6  # probability of a step along the chain all clients share
ZIPF = 1.1  # exponent of the token frequencies


def federated_lm_corpus(vocab_size, n_clients, n_seqs, seq_len, seed):
    """(n_clients, n_seqs, seq_len) int32 tokens.

    Every token has ``BRANCHING`` equally likely successors drawn with
    Zipf token frequencies; each step follows the shared chain with
    probability ``SHARED`` and the client's own chain otherwise."""
    rng = np.random.default_rng(seed)
    freq = 1.0 / np.arange(1, vocab_size + 1) ** ZIPF
    freq /= freq.sum()

    def successors(ranking):
        return ranking[rng.choice(vocab_size, size=(vocab_size, BRANCHING), p=freq)]

    base = successors(np.arange(vocab_size))
    own = np.stack([successors(rng.permutation(vocab_size)) for _ in range(n_clients)])
    client = np.arange(n_clients)[:, None]
    toks = np.empty((n_clients, n_seqs, seq_len), dtype=np.int32)
    state = rng.integers(0, vocab_size, size=(n_clients, n_seqs))
    toks[..., 0] = state
    for t in range(1, seq_len):
        pick = rng.integers(0, BRANCHING, size=state.shape)
        from_base = rng.random(state.shape) < SHARED
        state = np.where(from_base, base[state, pick], own[client, state, pick])
        toks[..., t] = state
    return toks
