"""Plain reference of FedCM rounds (arXiv:2106.10874, Algorithm 2).

Each round: split the run key into (next, cohort, batch) keys; draw the
cohort (a random order of all clients, of which the first ``capacity``
are offered; under Bernoulli participation each client is drawn with
probability cohort/num_clients, and the first n of the offered ones
train, n clipped to [1, capacity]); draw each offered client's K
minibatches of B rows with replacement from its own data.  Every active
client starts from the server's x_t and takes K steps

    g = grad f_i(x) + wd * x,    x <- x - eta_l * (alpha * g + (1 - alpha) * m_t)

with eta_l = eta_l0 * decay^t.  The server averages the active clients'
changes d_i = x_K - x_t and sets

    m_{t+1} = -mean(d) / (eta_l * K),    x_{t+1} = x_t + eta_g * mean(d).

The round's loss is the mean over active clients of each client's mean
local loss.  Leaves are kept as the model's tree; clients run one after
another, so a large model needs room for a few copies of its parameters
and no more.

``fault`` plants one fault in the reference, to read how far a broken
program would stand from it: ``"half_batch"`` takes each step's loss on
the first half of its minibatch only; ``"no_exchange"`` folds parameter
column block j of ``shards`` equal blocks from client j alone, as a
sharded fold whose exchange between chips was left out would.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.reference.numerics import numerics


def capacity(fed: dict) -> int:
    if fed["participation"] == "fixed":
        return fed["cohort_size"]
    p = fed["cohort_size"] / fed["num_clients"]
    sd = math.sqrt(fed["num_clients"] * p * (1 - p))
    return min(fed["num_clients"], int(math.ceil(fed["cohort_size"] + 5.0 * sd)))


@partial(jax.jit, static_argnames=("n_clients", "cap", "bernoulli", "p", "shape", "n_per"))
def _sample(key, *, n_clients, cap, bernoulli, p, shape, n_per):
    key, k_cohort, k_batch = jax.random.split(key, 3)
    k_perm, k_n = jax.random.split(k_cohort)
    ids = jax.random.choice(k_perm, n_clients, (cap,), replace=False)
    if bernoulli:
        n = jnp.clip(jnp.sum(jax.random.bernoulli(k_n, p, (n_clients,))), 1, cap)
    else:
        n = jnp.int32(cap)
    idx = jax.random.randint(k_batch, shape, 0, n_per)
    return key, ids, n, idx


def _flatten(tree):
    return jnp.concatenate([a.reshape(-1) for a in jax.tree_util.tree_leaves(tree)])


def _unflatten(flat, like):
    leaves, treedef = jax.tree_util.tree_flatten(like)
    out, o = [], 0
    for a in leaves:
        out.append(flat[o:o + a.size].reshape(a.shape))
        o += a.size
    return jax.tree_util.tree_unflatten(treedef, out)


def run_rounds(loss, params, client_x, client_y, key, fed: dict, batch_size: int,
               n_rounds: int, mode: str = "f32", fault=None, shards: int = 1):
    """FedCM from ``params`` for ``n_rounds``.  ``loss(params, batch, num)``
    with ``batch = {"x", "y"}``.  Returns a dict of the per-round
    ``losses`` and ``n_active`` (lists of floats) and the final
    ``params`` and ``momentum`` trees."""
    num = numerics(mode)
    K, B = fed["local_steps"], batch_size
    alpha, wd = fed["alpha"], fed["weight_decay"]
    cap = capacity(fed)

    def step_loss(x, b):
        if fault == "half_batch":
            b = {k: v[: B // 2] for k, v in b.items()}
        return loss(x, b, num)

    @jax.jit
    def local(x_t, m, xs, ys, eta_l):
        def step(x, b):
            l, g = jax.value_and_grad(step_loss)(x, {"x": b[0], "y": b[1]})
            new = jax.tree_util.tree_map(
                lambda xi, gi, mi: xi - eta_l * (alpha * (gi + wd * xi) + (1 - alpha) * mi),
                x, g, m)
            return new, l

        xK, ls = jax.lax.scan(step, x_t, (xs, ys))
        return jax.tree_util.tree_map(lambda a, b: a - b, xK, x_t), jnp.mean(ls)

    @jax.jit
    def gather(cx, cy, i, idx):
        return cx[i][idx], cy[i][idx]

    @jax.jit
    def fold(x, total, n, eta_l):
        mean = jax.tree_util.tree_map(lambda d: d / n, total)
        m = jax.tree_util.tree_map(lambda d: -d / (eta_l * K), mean)
        x = jax.tree_util.tree_map(lambda a, d: a + fed["eta_g"] * d, x, mean)
        return x, m

    x = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, x)
    losses, actives = [], []
    for t in range(n_rounds):
        key, ids, n, idx = _sample(
            key, n_clients=fed["num_clients"], cap=cap,
            bernoulli=fed["participation"] == "bernoulli",
            p=fed["cohort_size"] / fed["num_clients"], shape=(cap, K, B),
            n_per=client_x.shape[1])
        n = int(n)
        eta_l = jnp.float32(fed["eta_l"]) * jnp.float32(fed["eta_l_decay"]) ** jnp.float32(t)
        total, client_losses, deltas = None, [], []
        for j in range(n):
            xs, ys = gather(client_x, client_y, ids[j], idx[j])
            d, l = local(x, m, xs, ys, eta_l)
            client_losses.append(float(l))
            if fault == "no_exchange":
                deltas.append(_flatten(d))
            else:
                total = d if total is None else jax.tree_util.tree_map(jnp.add, total, d)
        if fault == "no_exchange":
            P = deltas[0].shape[0]
            block = -(-P // shards)
            own = jnp.concatenate([deltas[j % n][j * block:(j + 1) * block]
                                   for j in range(shards)])
            total = _unflatten(own, x)
        x, m = fold(x, total, jnp.float32(n), eta_l)
        losses.append(sum(client_losses) / n)
        actives.append(n)
    return {"losses": losses, "n_active": actives, "params": x, "momentum": m}
