"""The comparison that decides ``correct`` for a training cell.

Set-up drives the timed program through its first chunk of rounds; the
plain reference follows the same rounds from the same weights, data and
keys.  Three numbers are compared, each against a limit of its own
(``bench/limits/<cell>.json``):

- ``loss_gap``: the relative gaps of the round losses over the first
  ``LOSS_ROUNDS`` rounds, averaged (the largest of them swings with the
  third round's small loss, and a half batch then stood under ten times
  the sound runs' largest reading);
- ``momentum_gap``: the server momentum after the chunk, i.e. the last
  pseudo-gradient as the server optimizer got it;
- ``step_gap``: the parameters' change over the chunk.

A cell's limits file may leave a number out; it is then read and printed
but not compared.

The last two are taken leaf by leaf, as the gap between the program's
norm of the leaf and the reference's, over the larger of the reference's
norm of that leaf and the median leaf's; the worst leaf counts.  Leaves
whose reference momentum is under ``NOUGHT`` of the median leaf's move by
round-off alone and are left out of both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LOSS_ROUNDS = 3
NOUGHT = 1e-3
NUMBERS = ("loss_gap", "momentum_gap", "step_gap")


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in jax.tree_util.tree_leaves(tree)]


@jax.jit
def _leaf_diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]


def leaf_names(tree):
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def summarize(losses, params, momentum, params0) -> dict:
    """The readings of one side: its first round losses and the leaf norms
    of its momentum and of its parameter change."""
    return {
        "losses": [float(l) for l in losses[:LOSS_ROUNDS]],
        "leaves": leaf_names(params),
        "momentum": [float(v) for v in jax.device_get(_leaf_norms(momentum))],
        "step": [float(v) for v in jax.device_get(_leaf_diff_norms(params, params0))],
    }


def _leaf_gap(prog, ref, keep):
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    floor = np.median(ref)
    gaps = np.abs(prog - ref) / np.maximum(ref, floor)
    gaps = np.where(keep, gaps, 0.0)
    return float(np.max(gaps)) if np.all(np.isfinite(prog)) else math.inf


def readings(prog: dict, ref: dict) -> dict:
    """The three compared numbers; ``inf`` where the program gave a
    non-finite value."""
    if prog["leaves"] != ref["leaves"]:
        raise ValueError(f"leaves differ: {prog['leaves']} vs {ref['leaves']}")
    pl, rl = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    loss_gap = (float(np.mean(np.abs(pl - rl) / np.abs(rl)))
                if np.all(np.isfinite(pl)) else math.inf)
    rm = np.asarray(ref["momentum"], np.float64)
    keep = rm >= NOUGHT * np.median(rm)
    return {
        "loss_gap": loss_gap,
        "momentum_gap": _leaf_gap(prog["momentum"], ref["momentum"], keep),
        "step_gap": _leaf_gap(prog["step"], ref["step"], keep),
    }


def judge(values: dict, limits: dict) -> tuple:
    """(correct, checks): each number the cell compares beside its limit.
    A number with no limit in the cell's file is read but not compared
    (its readings left it no limit that sound runs would pass and the
    control would fail)."""
    checks = {k: {"value": values[k], "limit": limits[k]} for k in NUMBERS if k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
