"""The federated round engine.

One communication round (Algorithm 2 of the paper) is a single jitted —
and, on a mesh, pjit-sharded — program:

    sample cohort  →  broadcast (x_t, Δ_t)  →  vmap over clients of
    [lax.scan over K local steps]  →  masked-mean aggregate  →  server update

The engine is architecture-agnostic: it only sees ``loss_fn(params, batch)``
(DESIGN.md §7 — FedCM is optimizer-level).  On a TPU mesh the cohort axis is
sharded over ("pod","data") and each client's parameters may additionally be
tensor-sharded on "model"; the aggregation mean lowers to an all-reduce over
the cohort axes — the server/client message pattern of the paper becomes
collectives (DESIGN.md §3).

Participation models (§6.1 of the paper):

* ``fixed``      — exactly ``cohort_size`` clients, uniform w/o replacement.
* ``bernoulli``  — every client independently with prob cohort_size/N.  For a
  jit-static shape we draw the cohort count s ~ Binomial(N, p) (clipped to a
  capacity), take the first s entries of a random permutation, and mask the
  rest; conditioned on s this equals independent-Bernoulli participation.
  The capacity is a mean + ``cfg.bernoulli_capacity_sigma``·sd tail bound;
  rounds whose draw exceeds it are CLIPPED to capacity and the overflow
  count is surfaced as ``RoundMetrics.n_clipped`` (never silently dropped).

Streaming availability sampler (``sample_cohort_ex``): selection is driven
by a pluggable availability process on ``FedConfig`` —
``repro.data.population.availability_log_weights`` maps ``cfg.availability``
("uniform" | "zipf" | "diurnal") to per-client log weights, non-uniform
draws go through Gumbel top-k without replacement, ``bernoulli``
participation thins by per-client inclusion probabilities, and
``cfg.dropout_rate`` models stragglers by mask-only thinning AFTER
selection.  The uniform process keeps the legacy two-key draw
bitwise-identical, so pre-existing trajectories are unchanged.

Population store (``cfg.population_store``): per-client state planes
(scaffold c_i, feddyn λ_i) either live as the stacked ``(N, P)`` device
plane ("resident" — the bitwise oracle) or in a sparse host-memory
``repro.data.population.HostPopulationStore`` ("host").  The host path
runs ``run_rounds_store`` / ``run_rounds_store_async``: a host loop around
the SAME jitted round pieces, with a pure ``(C, P)`` gather-on-participation
before each round step and a scatter-on-fold after — device memory scales
with the cohort, host memory with the touched-client set, and N=1e6 is a
literal config value.  Store-backed rounds are f32-BITWISE against the
resident engine at matched cohorts (tests/test_population.py): the round
math is the same code, parameterized by ``cohort_rows``/``emit_rows``
instead of the resident plane.

Payload accounting mirrors §4.2: FedCM doubles only the DOWNLINK (x_t plus
Δ_t); uplink is one delta — unchanged from FedAvg.  SCAFFOLD pays both ways
(c down, Δc_i up); MimeLite pays an extra full-batch gradient up.

Fused multi-round engine (``run_rounds``): the paper's headline results
(Table 1, §6.1) need hundreds to thousands of rounds, and dispatching each
round as its own jit call — with host-side cohort sampling in between —
makes round *dispatch* the wall-clock bottleneck long before the math is.
``run_rounds(state, data, n_rounds)`` therefore executes N rounds as a
single ``jax.lax.scan`` whose body does everything a round needs on-device:

* cohort sampling (``sample_cohort``) from the carried rng,
* synthetic-data minibatch gathers (``repro.data.pipeline.gather_round_batches``,
  pure array-in/array-out so it traces),
* the round step itself (the same ``_round_step_impl`` the per-round path
  jits, so the two paths are numerically one implementation).

The carried ``FedState`` is donated (``donate_argnums``), so server params/
momentum/client-state buffers are updated in place across all N rounds, and
per-round ``RoundMetrics`` come back stacked ``(n_rounds, ...)``.  The
``client_sharding`` constructor arg pins the cohort axis of batches and
client states via sharding constraints in both the per-round and fused
paths.

Flat parameter plane (``cfg.use_flat_plane``, default on): params and
server momentum/second-moment are ravelled ONCE per ``run_rounds`` call
(``repro.core.flat.FlatSpec``) into contiguous ``(P,)`` buffers that carry
the round-scope state, and per-client control variates into one ``(N, P)``
plane (ONE gather and ONE scatter a round).  The K-step local scan carries
the ``(P,)`` plane: the loss reads its leaves through ``FlatSpec.unravel``
and the gradient returns to the plane as the view's backward, one
concatenate of the leaf gradients.  The client outputs ARE ``(C, P)``
planes, the shape the fold kernel reads.  The plane carries a zero tail up
to the kernels' block length (``_flat_spec``), so no launch pads or slices
a plane.  The tree path (``use_flat_plane=False``) is retained verbatim as
the numerical oracle (tests/test_flat.py) and for tensor-sharded lowering
(launch/fed_dryrun).

The algorithm layer is the declarative registry (``repro.core.registry``):
the engine consumes ONE ``AlgorithmSpec`` per run — its direction
coefficient row drives the local steps, its fold coefficient rows (+
optional pure post-step) drive the round close, and its state-plane flags
drive ``FedState`` allocation and payload accounting.  The engine contains
zero per-algorithm branches; registering a new spec makes it runnable on
every path below.

The flat engine's update phase runs through Pallas: the per-local-step
direction via ``kernels/fed_direction`` (the spec's ``DirectionRow``
becomes the SMEM coefficient vector) and the round-close masked-mean +
momentum EMA + param step via ``kernels/server_update`` (one launch per
``FoldPass``; specs with a ``server_fn`` escape hatch take a jnp
reduction of the ``(C, P)`` planes instead).  The kernels choose Mosaic or
the Pallas interpreter from the platform (``repro.kernels.interpret_mode``);
nothing else chooses.  Each kernel's ``ref.py`` is its oracle.

Async pipelined engine (``run_rounds_async``): overlapping cohorts as ONE
``lax.scan`` whose carry adds a static depth-D ring of in-flight cohort
uplinks (``repro.core.flat.CohortUplink``) and an S-deep momentum delay
line.  Iteration t launches a cohort against (current params,
S-rounds-stale momentum), rotates it into the ring, and folds the uplink
launched D−1 iterations ago through the staleness-discount-extended fused
server kernel.  ``(D=1, S=0)`` reproduces ``run_rounds`` exactly; eval can
ride inside the scan at an ``eval_every`` cadence (padded ``lax.map``) so
train-with-eval is one jitted program.

Cohort-parallel execution (``cohort_mesh`` / ``cfg.cohort_shard``): a
``("clients",)`` mesh turns the round SPMD over the client axis.  The
cohort phase runs inside ``shard_map`` — each device owns C/num_shards
clients end-to-end (local-step scans, ``fed_direction`` launches, state
gathers all device-local; ragged cohorts pad with zero-weight rows AFTER
the gathers so the rng stream is untouched) — and the server fold lowers
to the scattered kernel (``kernels/server_update/ops.scatter_fold``):
``all_to_all`` transposes the ``(C, P)`` uplink planes to plane-column
shards, each device reduces the COMPLETE cohort for its columns in the
unsharded reduction order, runs the spec's fold rows on its ``x``/``m``
chunks, and ``all_gather`` rebuilds the replicated planes.  That
transpose-first decomposition (NOT ``psum_scatter``, which would
re-associate the f32 sum) plus the server kernel's ≥2-step grid floor is
what keeps sharded execution f32-BITWISE against the unsharded engine —
for every registered algorithm, sync and async
(tests/test_cohort_shard.py).  Under ``run_rounds_async`` the ring
carries client-sharded planes, so the fold's collective sits D−1 rounds
behind the launch it consumes — the latency the overlap hides.  Flat
plane only; the spec's ``server_post_fn`` runs replicated after
the gather, and ``server_fn`` escape hatches get scattered means
(``repro.core.flat.cohort_mean_scatter``) into a replicated escape.

Fault tolerance (``cfg.fault`` / ``cfg.min_quorum``): faults are pure
config data (``repro.configs.base.FaultConfig``, drawn by
``repro.core.faults`` keyed on (seed, absolute round, client id)) spliced
between launch and fold on every path — uplink drops and straggler
deadlines thin the ``(C,)`` mask, payload corruption (NaN/Inf planes,
scaled bit-noise) rewrites delta rows, and a quarantine pass zeroes the
fold-weight row AND sanitizes the payload rows of any non-finite (or
norm-outlier) uplink so 0·NaN never reaches a reduction.  Degradation is
graceful by construction: every masked-mean denominator is guarded
(``max(n_active, 1)``), and a round whose surviving cohort falls below
``max(1, cfg.min_quorum)`` becomes a no-op — params/momentum selected
through unchanged, client-state writes suppressed — surfaced as
``RoundMetrics.quorum_skipped`` next to ``n_dropped`` / ``n_quarantined``
/ ``n_retries`` (host-store gather/scatter retries with capped
exponential backoff).  ``fault=None`` traces none of this and stays
f32-bitwise against the fault-free engine; in the async ring, faulted
planes ride the D−1 rounds to their fold like any other uplink.

Uplink compression (``cfg.compression`` / a spec's ``uplink_compression``):
wire encoding is pure config/spec data
(``repro.configs.base.CompressionConfig``, realized by
``repro.core.compress``) spliced between fault injection and fold on
every path.  Stochastic-rounded int8 and bf16 planes reach the server
fold COMPRESSED — the fused ``dequant_server_update`` kernel dequantizes
inside the accumulation pass, the async ring carries the compressed
representation (4–8× less in-flight memory at depth D), and the
cohort-sharded ``all_to_all`` moves int8/bf16 payloads instead of f32.
Top-k sparsification applies to the delta plane only, with error
feedback: the unsent remainder accumulates per client in
``FedState.residuals`` (resident ``(N, P)``) or a host residual store,
and joins that client's next uplink.  ``compression=None`` traces none
of this and stays f32-bitwise against the pre-compression engine;
payload accounting (``RoundMetrics.bytes_up``) reflects the active
encoding.
"""
from __future__ import annotations

import math
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import CompressionConfig, FedConfig
from repro.core.algorithms import (
    Algorithm,
    ClientOutputs,
    FlatClientOutputs,
    ServerState,
    client_state_init,
    get_algorithm,
    server_init,
    sparse_client_finalize,
)
from repro.core.compress import (
    QPlane,
    TopKPlane,
    as_qplane,
    compress_plane,
    decompress_plane,
    error_feedback_topk,
    plane_key,
    round_key,
    uplink_bytes_per_client,
    validate_compression,
)
from repro.core.faults import (
    corrupt_uplink,
    fault_masks,
    rows_finite,
    rows_sqnorm,
    zero_rows,
)
from repro.core.flat import (
    CohortUplink,
    FlatSpec,
    cohort_mean_scatter,
    pad_cohort,
    ring_push,
)
from repro.data.pipeline import gather_full_client_batch, gather_round_batches
from repro.data.population import (
    POPULATION_STORES,
    TransientStoreError,
    availability_log_weights,
    make_population_store,
)
from repro.kernels import plane_alignment
from repro.kernels.fed_direction.ops import flat_direction_step
from repro.kernels.server_update.ops import fused_fold, scatter_fold
from repro.sharding.rules import (
    COHORT_AXIS,
    cohort_axis_size,
    cohort_uplink_specs,
    padded_cohort,
)
from repro.utils.compat import shard_map
from repro.utils.compile_cache import key_on_metadata
from repro.utils.trees import (
    tree_axpy,
    tree_bytes,
    tree_zeros_like,
)

# Name scopes of the round program's layers (``flat.PLANE_VIEW_SCOPE`` is
# the third): each client's loss, gradient and finalize, and the round
# close with the round's metric norms.  They reach the compiled program's
# ``op_name`` metadata only, where a device trace reads them; the compiled
# code is the same with or without them.
LOCAL_STEPS_SCOPE = "fedcm.local_steps"
FOLD_SCOPE = "fedcm.fold"


class FlatMaster(NamedTuple):
    """f32 master planes carried ACROSS flat-engine calls for sub-f32 trees.

    The flat engine computes on f32 ``(P,)`` planes and rounds back to the
    leaf dtypes on exit; without this cache a bf16 model would re-round at
    every ``run_round`` boundary while ``run_rounds`` rounds once at the
    end (the divergence PR 2 documented).  ``FederatedEngine.init`` attaches
    it whenever the tree has non-f32 leaves, ``_ravel_state`` resumes from
    it, and ``_unravel_state`` refreshes it — so N× ``run_round`` agrees
    with ``run_rounds(N)`` to the same cross-program f32 noise as an f32
    model (measured ≲2e-5; the legacy behaviour differed by a bf16 ulp,
    ~4e-3, at EVERY boundary — the regression test pins the gap).  ``None``
    for all-f32 trees (the ravel is exact, nothing to preserve) and on the
    tree path."""

    params: jax.Array  # (P,) f32
    second_moment: Optional[jax.Array]  # (P,) f32, or None (spec doesn't need v)
    client_states: Optional[jax.Array]  # (N, P) f32, or None (stateless spec)


class FedState(NamedTuple):
    """Engine state.  ``master`` is an INTERNAL cache: for sub-f32 trees it
    holds the un-rounded f32 planes that ``params``/``server.second_moment``
    /``client_states`` are rounded views OF, and the engine resumes from it
    in preference to re-ravelling the leaves.  If you replace any of those
    fields externally (checkpoint restore, weight surgery), drop the cache
    — ``state._replace(params=new, master=None)`` — or the next round will
    silently continue from the cached planes instead of your edit."""

    params: Any
    server: ServerState
    client_states: Any  # stacked (N, …) or None
    rng: jax.Array
    master: Optional[FlatMaster] = None  # flat-engine f32 master planes
    # top-k error-feedback residuals: resident (N, P) f32, or None (no
    # top-k compression / host residual store carries the rows instead)
    residuals: Optional[jax.Array] = None


class RoundMetrics(NamedTuple):
    loss: jax.Array  # mean local training loss over cohort × K steps
    n_active: jax.Array
    delta_norm: jax.Array  # ‖mean Δ_i‖
    momentum_norm: jax.Array  # ‖Δ_t‖ (server momentum entering the round)
    eta_l: jax.Array
    bytes_down: jax.Array  # server→clients this round (f32 elements × 4)
    bytes_up: jax.Array  # clients→server this round
    # bernoulli draws beyond the static cohort capacity this round (clipped
    # clients sat out; 0 under "fixed" and at the default 5σ capacity)
    n_clipped: jax.Array = None
    # ---- fault-tolerance counters (0 everywhere when cfg.fault is None) --
    n_dropped: jax.Array = None  # uplinks lost to drop_rate / deadline
    n_quarantined: jax.Array = None  # uplinks zeroed by the quarantine pass
    n_retries: jax.Array = None  # host-store gather/scatter retries
    quorum_skipped: jax.Array = None  # 1.0 when survivors < max(1, min_quorum)


class AsyncRoundMetrics(NamedTuple):
    """Per-iteration metrics of the pipelined scan.  ``loss``/``n_active``/
    ``eta_l``/``momentum_norm`` describe the cohort LAUNCHED this round
    (client compute happens at launch); ``delta_norm``/``folded`` describe
    the fold — 0 during the D−1 warmup rounds while the pipeline fills.
    ``eval_acc`` is −1.0 on rounds where the in-scan eval didn't run."""

    loss: jax.Array
    n_active: jax.Array
    delta_norm: jax.Array
    momentum_norm: jax.Array  # ‖broadcast momentum‖ as the CLIENTS saw it
    eta_l: jax.Array
    bytes_down: jax.Array
    bytes_up: jax.Array
    folded: jax.Array  # 0/1: did this round fold a completed cohort
    eval_acc: jax.Array  # in-scan eval accuracy, −1.0 when not evaluated
    n_clipped: jax.Array = None  # capacity-overflow clips of the LAUNCHED cohort
    # fault counters: n_dropped/n_quarantined describe the LAUNCHED cohort
    # (faults hit the uplink at launch and ride the ring to the fold);
    # quorum_skipped describes the FOLD (0 during warmup)
    n_dropped: jax.Array = None
    n_quarantined: jax.Array = None
    n_retries: jax.Array = None
    quorum_skipped: jax.Array = None


def metrics_to_host(ms: NamedTuple) -> Dict[str, np.ndarray]:
    """Surface a (stacked) metrics tuple off-device in ONE transfer.

    A fused chunk returns ``RoundMetrics`` of stacked ``(chunk,)`` arrays;
    reading them field-by-field with ``float(...)`` costs one device sync
    each.  This fetches every non-None field in a single ``device_get``
    of the whole tuple — the ONLY host sync telemetry adds per chunk
    (REP003 stays clean: this is host-side driver code, never reachable
    from the jitted round program) — and returns ``{field: np.ndarray}``.
    Scalar fields come back as shape-``(1,)`` so callers can treat
    per-round and single-round metrics uniformly."""
    named = [(f, v) for f, v in zip(ms._fields, ms) if v is not None]
    fetched = jax.device_get(tuple(v for _, v in named))
    return {
        f: np.atleast_1d(np.asarray(v)) for (f, _), v in zip(named, fetched)
    }


def cohort_capacity(cfg: FedConfig) -> int:
    """Static cohort axis length. ``fixed``: exactly S. ``bernoulli``: a
    Binomial(N, p) tail bound — mean + ``cfg.bernoulli_capacity_sigma``·σ,
    clipped to N.  At the default 5σ, p(overflow) < 3e-7; an overflow clips
    the round's cohort and is COUNTED in ``RoundMetrics.n_clipped`` (the
    pre-store engine truncated silently — the bias the clip metric and its
    regression test now pin)."""
    if cfg.participation == "fixed":
        return cfg.cohort_size
    p = cfg.cohort_size / cfg.num_clients
    sd = math.sqrt(cfg.num_clients * p * (1 - p))
    sigma = float(getattr(cfg, "bernoulli_capacity_sigma", 5.0))
    return min(cfg.num_clients, int(math.ceil(cfg.cohort_size + sigma * sd)))


def sample_cohort_ex(rng, cfg: FedConfig, t=None):
    """Streaming availability sampler.  Returns
    ``(client_ids (C,), active_mask (C,), n_clipped ())`` with
    C = cohort_capacity and ``n_clipped`` the number of bernoulli draws
    beyond capacity this round (those clients sit the round out).

    Selection is driven by ``cfg.availability``
    (``repro.data.population.availability_log_weights``): uniform keeps the
    legacy two-key draw BITWISE (same splits, same ``jax.random.choice`` /
    scalar-p bernoulli branch — pre-existing trajectories are unchanged);
    non-uniform processes select via Gumbel top-k without replacement and
    thin by per-client inclusion probabilities ``clip(S·softmax(logw), 0, 1)``
    under ``participation="bernoulli"``.  ``cfg.dropout_rate`` then drops
    each selected client independently (straggler model) — mask-only, after
    selection, keeping ≥1 active client unless ``cfg.allow_empty_cohort``
    lets the round come up empty (it degrades to a guarded no-op fold).
    ``t`` is the round counter (may be traced; only the diurnal process
    reads it)."""
    cap = cohort_capacity(cfg)
    dropout = float(getattr(cfg, "dropout_rate", 0.0))
    if dropout > 0.0:
        k_perm, k_n, k_drop = jax.random.split(rng, 3)
    else:  # legacy split — keeps dropout-free trajectories bitwise
        k_perm, k_n = jax.random.split(rng)
        k_drop = None
    logw = availability_log_weights(cfg, t)
    if logw is None:  # uniform: the legacy draw, verbatim
        ids = jax.random.choice(k_perm, cfg.num_clients, (cap,), replace=False)
    else:
        # Gumbel top-k = weighted sampling without replacement
        g = jax.random.gumbel(k_perm, (cfg.num_clients,), dtype=jnp.float32)
        _, ids = jax.lax.top_k(logw + g, cap)
        ids = ids.astype(jnp.int32)
    n_clipped = jnp.int32(0)
    if cfg.participation == "fixed":
        mask = jnp.ones((cap,), bool)
    else:
        if logw is None:
            p = cfg.cohort_size / cfg.num_clients
            draws = jax.random.bernoulli(k_n, p, (cfg.num_clients,))
        else:
            q = jnp.clip(cfg.cohort_size * jax.nn.softmax(logw), 0.0, 1.0)
            draws = jax.random.bernoulli(k_n, q)
        s_raw = jnp.sum(draws).astype(jnp.int32)
        allow_empty = bool(getattr(cfg, "allow_empty_cohort", False))
        s = jnp.clip(s_raw, 0 if allow_empty else 1, cap)
        mask = jnp.arange(cap) < s
        n_clipped = jnp.maximum(s_raw - cap, 0)
    if dropout > 0.0:
        keep = jax.random.bernoulli(k_drop, 1.0 - dropout, (cap,))
        kept = mask & keep
        if getattr(cfg, "allow_empty_cohort", False):
            # empty rounds degrade to guarded no-op folds — let them happen
            mask = kept
        else:
            # legacy guard: a fully-dropped cohort keeps its first client
            first = mask & (jnp.arange(cap) == jnp.argmax(mask))
            mask = jnp.where(jnp.any(kept), kept, first)
    return ids, mask, n_clipped


def sample_cohort(rng, cfg: FedConfig, t=None) -> Tuple[jax.Array, jax.Array]:
    """Returns (client_ids (C,), active_mask (C,)) with C = cohort_capacity.
    Back-compat wrapper over ``sample_cohort_ex`` (drops the clip count)."""
    ids, mask, _ = sample_cohort_ex(rng, cfg, t)
    return ids, mask


def local_learning_rate(cfg: FedConfig, t) -> jax.Array:
    """Appendix C.2: exponential per-round decay of η_l."""
    return jnp.float32(cfg.eta_l) * jnp.float32(cfg.eta_l_decay) ** t.astype(jnp.float32)


def _where_tree(ok, new, old):
    """Per-leaf ``where(ok, new, old)`` — the quorum/no-op-round select.
    Bitwise inert on healthy rounds: ``jnp.where(True, new, old)`` IS
    ``new``.  ``None`` (unallocated planes) passes through."""
    if new is None:
        return None
    return jax.tree_util.tree_map(lambda a, b: jnp.where(ok, a, b), new, old)


# ----------------------------------------------------------------------
# client update
# ----------------------------------------------------------------------


def client_update(
    algo: Algorithm,
    cfg: FedConfig,
    loss_fn: Callable[[Any, Any], jax.Array],
    params,  # x_t (broadcast)
    bcast_momentum,  # Δ_t (or c for scaffold; zeros otherwise)
    client_state,  # this client's c_i / λ_i slice (or zeros pytree)
    batches,  # pytree of (K, B, …) local minibatches
    eta_l,
    full_grad_batch=None,  # MimeLite: the client's whole dataset
    unroll: bool = False,  # dry-run analysis: count every local step
) -> Tuple[ClientOutputs, jax.Array]:
    """One client's K local steps.  Returns (outputs, mean local loss).

    The spec's declarative direction row consumes the broadcast buffer and
    the client's state slice as NAMED streams — no per-algorithm packing
    (the old scaffold ``(c_i, c)`` tuple) happens here.
    """
    x0 = params

    def step(x, batch):
        loss, g = jax.value_and_grad(loss_fn)(x, batch)
        if cfg.weight_decay:
            g = tree_axpy(cfg.weight_decay, x, g)
        v = algo.direction(cfg, bcast_momentum, client_state, x, x0, g)
        # keep the carry dtype stable (bf16 params + f32 momentum promote)
        x = jax.tree_util.tree_map(
            lambda xi, vi: (xi - eta_l * vi).astype(xi.dtype), x, v
        )
        return x, loss

    xK, losses = jax.lax.scan(step, x0, batches,
                              unroll=cfg.local_steps if unroll else 1)

    full_grad = tree_zeros_like(x0)
    if algo.needs_full_grad:
        assert full_grad_batch is not None
        full_grad = jax.grad(loss_fn)(x0, full_grad_batch)

    outs = algo.client_finalize(cfg, x0, xK, client_state, bcast_momentum,
                                eta_l, full_grad)
    return outs, jnp.mean(losses)


def flat_client_update(
    algo: Algorithm,
    cfg: FedConfig,
    loss_fn: Callable[[Any, Any], jax.Array],
    spec: FlatSpec,
    x_t: jax.Array,  # (P,) broadcast round anchor (flat)
    m_t: jax.Array,  # (P,) Δ_t (or c for scaffold; zeros otherwise)
    cst_i,  # this client's c_i / λ_i as a (P,) plane row, or None
    batches,  # pytree of (K, B, …) local minibatches
    eta_l,
    full_grad_batch=None,  # MimeLite: the client's whole dataset
    unroll: bool = False,  # dry-run analysis: count every local step
):
    """One client's K local steps on the flat ``(P,)`` carry, finalized
    onto flat-engine outputs.

    The ``fed_direction`` kernel consumes flat buffers directly; the loss
    unravels the plane by slicing, which fuses on TPU, and its gradient
    returns to the plane as the view's backward, one concatenate of the
    leaf gradients.  The outputs ARE ``(P,)`` planes, giving the
    ``(C, P)`` delta plane the fused ``server_update`` kernel wants for
    free; unused planes are ``None`` (``sparse_client_finalize``).
    """
    def flat_loss(flat, batch):
        return loss_fn(spec.unravel(flat), batch)

    def step(x, batch):
        with jax.named_scope(LOCAL_STEPS_SCOPE):
            loss, g = jax.value_and_grad(flat_loss)(x, batch)
            if cfg.weight_decay:
                g = cfg.weight_decay * x + g
        x = flat_direction_step(algo, cfg, x, g, m_t, cst_i, x_t, eta_l)
        return x, loss

    xK, losses = jax.lax.scan(step, x_t, batches,
                              unroll=cfg.local_steps if unroll else 1)
    with jax.named_scope(LOCAL_STEPS_SCOPE):
        full_grad = None
        if algo.needs_full_grad:
            assert full_grad_batch is not None
            full_grad = jax.grad(flat_loss)(x_t, full_grad_batch)
        outs = sparse_client_finalize(algo, cfg, x_t, xK, cst_i,
                                      m_t, eta_l, full_grad)
    return outs, jnp.mean(losses)


# ----------------------------------------------------------------------
# engine
# ----------------------------------------------------------------------


class FederatedEngine:
    """Builds the jitted round step for (algorithm, loss_fn, data layout).

    Usage::

        eng = FederatedEngine(cfg, loss_fn)
        state = eng.init(params, rng)
        state, metrics = eng.run_rounds(state, data, n_rounds)   # fused scan
        state, metrics = eng.run_round(state, data)     # one round at a time
        # or, lower-level / dry-runnable:
        state, metrics = eng.round_step(state, batches, ids, mask, full_batches)

    ``client_sharding`` (a ``NamedSharding`` whose spec names the mesh axes
    for the cohort dimension, e.g. ``NamedSharding(mesh, P(("pod","data")))``)
    is applied as a sharding constraint to the leading axis of every
    cohort-stacked array — minibatches, gathered client states, and the
    MimeLite full batches — in both the per-round and fused paths.
    """

    def __init__(
        self,
        cfg: FedConfig,
        loss_fn: Callable[[Any, Any], jax.Array],
        batch_size: int = 50,
        client_sharding: Optional[Any] = None,  # NamedSharding for the cohort axis
        cohort_mesh: Optional[Any] = None,  # Mesh with a "clients" axis
    ) -> None:
        if not cfg.use_fused_kernel:
            raise ValueError(
                "the flat engine has one path, the Pallas kernels (the "
                "platform picks Mosaic or the interpreter): False is no "
                "longer accepted"
            )
        self.cfg = cfg
        self.algo = get_algorithm(cfg.algo)
        self.loss_fn = loss_fn
        self.batch_size = batch_size
        self.client_sharding = client_sharding
        self.analysis_unroll = False  # dry-run analysis form
        # ---- uplink compression (wire encoding, launch → fold) ----
        # cfg.compression wins; otherwise a spec that declares its own
        # wire format (registry uplink_compression) supplies the default.
        comp = getattr(cfg, "compression", None)
        if comp is None and self.algo.uplink_compression is not None:
            comp = CompressionConfig(kind=self.algo.uplink_compression)
        if comp is not None:
            validate_compression(comp)
            if not cfg.use_flat_plane:
                raise ValueError(
                    "uplink compression is a flat-plane transform (it "
                    "quantizes (C, P) cohort planes) — set "
                    "cfg.use_flat_plane=True (the tree path stays the "
                    "uncompressed oracle)"
                )
        self.compression = comp
        self.residual_population = None  # host store for top-k residuals
        # ---- population store (out-of-core client state) ----
        # "host" keeps per-client state rows in a sparse host store
        # (repro.data.population.HostPopulationStore, created by init());
        # the engine host-loops the SAME jitted round pieces with a (C, P)
        # gather before each step and a scatter after each fold.
        store = getattr(cfg, "population_store", "resident")
        if store not in POPULATION_STORES:
            raise ValueError(
                f"unknown population_store {store!r}; known: {POPULATION_STORES}"
            )
        self.population_store = store
        self.population = None  # HostPopulationStore, attached by init()
        # fail at construction, not at the first sampled round
        availability_log_weights(cfg, t=0)
        if store == "host":
            if not cfg.use_flat_plane:
                raise ValueError(
                    "population_store='host' rides the flat parameter plane "
                    "(the store gathers/scatters contiguous (C, P) rows) — "
                    "set cfg.use_flat_plane=True"
                )
            if cohort_mesh is not None or getattr(cfg, "cohort_shard", 0) > 0:
                raise ValueError(
                    "population_store='host' is host-loop execution and is "
                    "not composable with cohort-parallel shard_map — drop "
                    "cohort_mesh / set cfg.cohort_shard=0"
                )
        # ---- cohort-parallel (SPMD-over-clients) execution path ----
        # a Mesh with a "clients" axis turns every cohort phase into
        # shard_map over that axis: each device owns C/num_shards clients
        # end-to-end and the server fold becomes an explicit
        # reduce-scatter/all-gather (kernels/server_update/ops.scatter_fold).
        # cfg.cohort_shard > 0 is the data-only way to ask for it (the
        # engine builds the mesh over the first N visible devices).
        if cohort_mesh is None and getattr(cfg, "cohort_shard", 0) > 0:
            from repro.launch.mesh import make_cohort_mesh

            cohort_mesh = make_cohort_mesh(cfg.cohort_shard)
        self.cohort_mesh = cohort_mesh
        self._cohort_shards = 1
        if cohort_mesh is not None:
            if not cfg.use_flat_plane:
                raise ValueError(
                    "cohort-parallel execution runs on the flat parameter "
                    "plane — it shards (C, P) uplink planes; set "
                    "cfg.use_flat_plane=True (the tree path stays the "
                    "single-device oracle)"
                )
            if client_sharding is not None:
                raise ValueError(
                    "cohort_mesh (shard_map over clients) and "
                    "client_sharding (GSPMD cohort-axis constraints) are "
                    "alternative lowerings of the same axis — pass one"
                )
            self._cohort_shards = cohort_axis_size(cohort_mesh)
        # a device trace reads the layers by the name scopes in the
        # compiled metadata, which the persistent cache must not answer
        # from a build with other scopes (``key_on_metadata``)
        key_on_metadata()
        self._round_step = jax.jit(self._round_step_impl)
        # traced once per (shapes, n_rounds) — the compile-count regression
        # test asserts a 100-round run is ONE trace, not 100
        self.run_rounds_traces = 0
        self._run_rounds = jax.jit(
            self._run_rounds_impl,
            static_argnames=("n_rounds",),
            donate_argnums=(0,),
        )
        self.run_rounds_async_traces = 0
        self._run_rounds_async = jax.jit(
            self._run_rounds_async_impl,
            static_argnames=(
                "n_rounds", "pipeline_depth", "staleness", "eval_every",
                "predict_fn", "scan_unroll",
            ),
            donate_argnums=(0,),
        )
        # donate the state only: the pending uplinks are consumed, not
        # updated — most of their buffers have no same-shaped output to
        # alias into and donating them just trips "unusable donation"
        # warnings
        self._drain_async = jax.jit(
            self._drain_async_impl,
            static_argnames=("pipeline_depth",),
            donate_argnums=(0,),
        )

    # -------------------------------------------------- init
    def init(self, params, rng) -> FedState:
        """Allocate the FedState the registered spec requires: the stacked
        per-client planes iff ``needs_client_state``, the second-moment
        plane iff ``needs_second_moment`` — allocation is derived from the
        spec's state-plane flags, never from algorithm names.

        Under ``population_store="host"`` the per-client planes never
        touch the device: ``client_state_init`` returns None and a fresh
        ``HostPopulationStore`` is attached as ``self.population``
        (re-``init`` = a fresh population)."""
        if self.population_store != "resident" and self.algo.needs_client_state:
            self.population = make_population_store(
                self.cfg, self._flat_spec(params).plane_size
            )
        # top-k error-feedback residuals are a per-client state stream of
        # their own: resident (N, P) zeros, or a second host store whose
        # unwritten rows read as zeros (same init semantics)
        residuals = None
        if self._ef_residuals:
            size = self._flat_spec(params).plane_size
            if self.population_store == "resident":
                residuals = jnp.zeros(
                    (self.cfg.num_clients, size), jnp.float32
                )
            else:
                self.residual_population = make_population_store(
                    self.cfg, size
                )
        state = FedState(
            params=params,
            server=server_init(params, self.cfg.momentum_dtype,
                               needs_second_moment=self.algo.needs_second_moment),
            client_states=client_state_init(params, self.cfg),
            rng=rng,
            residuals=residuals,
        )
        # flat engine + sub-f32 leaves: attach the f32 master planes up
        # front so every later call sees one stable treedef (no master→
        # no-master retrace) and run_round/run_rounds share one precision
        # contract from round 0
        if self.cfg.use_flat_plane:
            try:
                spec = self._flat_spec(params)
            except TypeError:  # non-float leaves: flat path will refuse anyway
                return state
            if self._needs_master(spec):
                cst = None
                if state.client_states is not None:
                    cst = spec.ravel(state.client_states, batch_dims=1)
                sm = state.server.second_moment
                state = state._replace(master=FlatMaster(
                    params=spec.ravel(params),
                    second_moment=spec.ravel(sm) if sm is not None else None,
                    client_states=cst,
                ))
        if self.cohort_mesh is not None:
            # the sharded round returns its state replicated over the
            # cohort mesh: start it there, so the next call does not
            # retrace and recompile for a new input sharding
            state = jax.device_put(state, NamedSharding(self.cohort_mesh, P()))
        return state

    @staticmethod
    def _needs_master(spec: FlatSpec) -> bool:
        """True when rounding plane→leaves loses bits (any non-f32 leaf)."""
        return any(np.dtype(l.dtype) != np.float32 for l in spec.leaves)

    @property
    def _ef_residuals(self) -> bool:
        """True when top-k compression carries an error-feedback stream."""
        return self.compression is not None and self.compression.kind == "topk"

    def _flat_spec(self, params) -> FlatSpec:
        """The flat plane of ``params`` as this engine lays it out: its
        length is a multiple of the launches' block (``plane_alignment`` of
        the size and the cohort shards), so the kernels neither pad their
        operands nor slice their outputs."""
        spec = FlatSpec.from_tree(params)
        return spec.aligned(plane_alignment(spec.size, self._cohort_shards))

    # -------------------------------------------------- payload accounting
    def payload_bytes(self, params) -> Dict[str, int]:
        """Per-client per-round communication in bytes (§4.2 discussion)."""
        if self.compression is not None:
            spec = FlatSpec.from_tree(params)
            return self._payload_from_nbytes(spec.nbytes, spec.size)
        return self._payload_from_nbytes(tree_bytes(params))

    def _payload_from_nbytes(self, P: int, size: Optional[int] = None) -> Dict[str, int]:
        """Payload accounting from a total byte count — the flat path charges
        ``FlatSpec.nbytes`` (the wire dtypes), identical to ``tree_bytes``.
        Wire shapes are DERIVED from the spec's state-plane flags (§4.2) via
        ``AlgorithmSpec.wire_uplink_planes`` — the same accounting
        ``fed_train --list-algos`` prints per algorithm.  Under active
        compression the uplink charge is bytes-on-the-wire of the encoded
        planes (``repro.core.compress.uplink_bytes_per_client``; ``size``
        is the plane element count the flat callers provide)."""
        down = P  # x_t always goes down
        if self.algo.needs_momentum_broadcast:
            down += P  # Δ_t (fedcm/mimelite) or c (scaffold)
        # Δ_i always; +Δc_i iff the state plane goes over the wire
        # (SCAFFOLD — feddyn's λ_i never leaves the client); +full-batch
        # gradient iff needs_full_grad (MimeLite)
        if self.compression is not None and size is not None:
            up = uplink_bytes_per_client(
                self.compression, self.algo.wire_uplink_planes, size, P
            )
        else:
            up = P * len(self.algo.wire_uplink_planes)
        return {"down_per_client": down, "up_per_client": up}

    # -------------------------------------------------- cohort sharding
    def _constrain_cohort(self, tree):
        """Pin the leading (cohort) axis of every leaf to ``client_sharding``."""
        if self.client_sharding is None or tree is None:
            return tree
        mesh = self.client_sharding.mesh
        spec = self.client_sharding.spec
        cohort_axes = spec[0] if len(spec) else None

        def pin(a):
            s = NamedSharding(mesh, P(cohort_axes, *([None] * (a.ndim - 1))))
            return jax.lax.with_sharding_constraint(a, s)

        return jax.tree_util.tree_map(pin, tree)

    # -------------------------------------------------- flat plane
    def _ravel_state(self, state: FedState, spec: FlatSpec) -> FedState:
        """Tree state → flat-plane state: the ONE ravel of a run_rounds call.
        Params/second-moment become f32 ``(P,)`` planes and momentum a
        ``momentum_dtype`` plane.  Stacked per-client control variates
        become an ``(N, P)`` plane (the clients produce flat buffers, so
        gather/scatter are ONE op each).

        A carried ``state.master`` (sub-f32 trees) takes precedence over
        re-ravelling the rounded leaves: that is what makes sequential
        ``run_round`` calls bitwise-continue the f32 trajectory instead of
        re-rounding at every boundary."""
        cfg, mst = self.cfg, state.master
        sm = state.server.second_moment
        fsrv = ServerState(
            # momentum plane and tree share momentum_dtype — ravel is exact,
            # no master needed
            momentum=spec.ravel(state.server.momentum, dtype=cfg.momentum_dtype),
            second_moment=(mst.second_moment if mst is not None
                           else (spec.ravel(sm) if sm is not None else None)),
            round=state.server.round,
        )
        fcst = state.client_states
        if fcst is not None:
            fcst = (mst.client_states if mst is not None and
                    mst.client_states is not None
                    else spec.ravel(fcst, batch_dims=1))
        params = mst.params if mst is not None else spec.ravel(state.params)
        return FedState(params, fsrv, fcst, state.rng,
                        residuals=state.residuals)

    def _unravel_state(self, fstate: FedState, spec: FlatSpec) -> FedState:
        """Flat-plane state → tree state (leaf shapes AND dtypes restored).
        For sub-f32 trees the un-rounded planes ride along as ``master``."""
        cfg = self.cfg
        fsm = fstate.server.second_moment
        srv = ServerState(
            momentum=spec.unravel(fstate.server.momentum, dtype=cfg.momentum_dtype),
            second_moment=spec.unravel(fsm) if fsm is not None else None,
            round=fstate.server.round,
        )
        cst = fstate.client_states
        if cst is not None:
            cst = spec.unravel(cst)
        master = None
        if self._needs_master(spec):
            master = FlatMaster(
                params=fstate.params,
                second_moment=fstate.server.second_moment,
                client_states=fstate.client_states,
            )
        return FedState(spec.unravel(fstate.params), srv, cst, fstate.rng,
                        master, residuals=fstate.residuals)

    def _flat_cohort_pass(self, fstate: FedState, batches, ids, mask,
                          full_batches, spec: FlatSpec, m_t, eta_l,
                          cohort_rows=None):
        """The cohort's client phase on the flat plane: gather per-client
        state, vmap the K-local-step update over the cohort.  Shared
        VERBATIM by the sync round (``_flat_round_step``) and the async
        launch (``_launch_async_cohort``) — ``m_t`` is the broadcast buffer
        the clients descend against (the CURRENT momentum for sync, an
        S-rounds-stale one for the pipelined path).

        ``cohort_rows`` (store-backed path) is a pre-gathered ``(C, P)``
        f32 block from the population store, replacing the resident-plane
        gather; the per-client math downstream is identical either way.

        Returns (outs, losses, cohort_cst): cohort_cst is the (C, P)
        gathered client-state plane (None for stateless specs)."""
        cfg, algo = self.cfg, self.algo
        batches = self._constrain_cohort(batches)
        x_t = fstate.params  # (P,) f32

        cohort_cst = None
        if algo.needs_client_state:
            if cohort_rows is None:  # (N, P) plane: ONE gather
                cohort_rows = fstate.client_states[ids]
            cohort_cst = self._constrain_cohort(cohort_rows)
        full = None
        if algo.needs_full_grad:
            full = self._constrain_cohort(full_batches)

        def one_client(cst_i, batches_i, full_i):
            return flat_client_update(
                algo, cfg, self.loss_fn, spec, x_t, m_t, cst_i, batches_i,
                eta_l, full_grad_batch=full_i, unroll=self.analysis_unroll,
            )

        outs, losses = jax.vmap(one_client)(cohort_cst, batches, full)
        return outs, losses, cohort_cst

    # -------------------------------------------------- cohort-parallel
    @property
    def _sharded(self) -> bool:
        return self.cohort_mesh is not None

    def _pad_cohort(self, tree, mode: str = "edge"):
        """Pad the leading cohort axis to a multiple of the mesh's
        ``"clients"`` axis.  Applied AFTER the minibatch/state gathers —
        the rng stream and every real client's inputs stay bitwise those
        of the unsharded round.  Data pads by edge-repeat (pad clients
        compute on a real client's finite inputs — a batch-normalizing
        loss_fn on all-zero input would emit NaN, and ``0 · NaN`` poisons
        the fold); the weight row pads with exact zeros (``mode="zero"``)
        so pad rows never count."""
        target = padded_cohort(cohort_capacity(self.cfg), self._cohort_shards)
        return pad_cohort(tree, target, mode=mode)

    def _sharded_cohort_pass(self, fstate: FedState, batches, ids, mask,
                             full_batches, spec: FlatSpec, m_t, eta_l):
        """The cohort's client phase SPMD over the ``"clients"`` mesh axis:
        each device runs the K-local-step update for its C/num_shards
        clients end-to-end inside ``shard_map`` — sampling gathers happen
        before entry (replicated rng), ``fed_direction`` kernel launches
        stay device-local, and no collective runs until the fold.

        Same contract as ``_flat_cohort_pass``, with
        the cohort axis PADDED to the shard count: ``outs`` planes are
        ``(C_pad, P)`` sharded over clients, ``losses`` is ``(C_pad,)``,
        and ``cohort_cst`` is the UNpadded ``(C, P)`` gather (the
        client-state scatter consumes only real rows)."""
        cfg, algo = self.cfg, self.algo

        cohort_cst = None
        if algo.needs_client_state:
            cohort_cst = fstate.client_states[ids]  # (C, P): ONE gather
        operands = {"batches": self._pad_cohort(batches)}
        if cohort_cst is not None:
            operands["cst"] = self._pad_cohort(cohort_cst)
        if algo.needs_full_grad:
            operands["full"] = self._pad_cohort(full_batches)

        plane_keys = tuple(algo.uplink_planes)

        def shard_body(x_t, m_t, eta_l, operands):
            def one_client(cst_i, batches_i, full_i):
                return flat_client_update(
                    algo, cfg, self.loss_fn, spec, x_t, m_t, cst_i, batches_i,
                    eta_l, full_grad_batch=full_i, unroll=self.analysis_unroll,
                )

            outs, losses = jax.vmap(one_client)(
                operands.get("cst"), operands["batches"], operands.get("full")
            )
            out = {k: getattr(outs, k) for k in plane_keys}
            out["losses"] = losses
            return out

        sh, rep = P(COHORT_AXIS), P()
        out = shard_map(
            shard_body,
            mesh=self.cohort_mesh,
            in_specs=(rep, rep, rep, {k: sh for k in operands}),
            # uplink planes + the per-client loss row shard over clients —
            # derived from the registry's state-plane flags
            out_specs=cohort_uplink_specs(algo, extra=("losses",)),
            check_vma=False,
        )(fstate.params, m_t, eta_l, operands)
        outs = FlatClientOutputs(
            delta=out["delta"],
            state_delta=out.get("state_delta"),
            extra=out.get("extra"),
        )
        # replicate the per-client loss row before the metrics reduce it:
        # summing a clients-sharded (C,) array would lower to per-device
        # partial sums + all-reduce, re-associating the f32 sum away from
        # the unsharded metric (the planes stay sharded — their reductions
        # go through the scattered fold, which preserves order by design)
        losses = jax.lax.with_sharding_constraint(
            out["losses"], NamedSharding(self.cohort_mesh, P())
        )
        return outs, losses, cohort_cst

    def _sharded_round_close(self, algo, fsrv, outs, wp, n_active, x_t, eta_l,
                             discount=1.0):
        """``_fused_round_close`` under cohort sharding: the fold rows run
        through the scattered server kernel (``scatter_fold`` inside
        ``shard_map`` — all_to_all to plane columns, device-local
        full-cohort reduce, kernel launch per row, all_gather), and the
        spec's pure post-step then runs on the REPLICATED ``(P,)`` planes
        at the same program level (and with the same shapes) as the
        unsharded close — elementwise posts stay bitwise that way."""
        cfg = self.cfg
        planes = {k: getattr(outs, k) for k in algo.fold_planes}
        nsh = self._cohort_shards

        def fold_body(planes, wp, n_active, x, m, eta_l):
            return scatter_fold(
                algo, cfg, planes, wp / jnp.maximum(n_active, 1.0), n_active,
                x, m, eta_l,
                discount=discount, axis_name=COHORT_AXIS, n_shards=nsh,
            )

        sh, rep = P(COHORT_AXIS), P()
        new_x, new_m, mean_delta = shard_map(
            fold_body,
            mesh=self.cohort_mesh,
            in_specs=({k: sh for k in planes}, rep, rep, rep, rep, rep),
            out_specs=(rep, rep, rep),
            check_vma=False,
        )(planes, wp, n_active, x_t, fsrv.momentum, eta_l)
        return self._close_post(algo, fsrv, new_x, new_m, mean_delta,
                                n_active, eta_l, discount)

    def _close_post(self, algo, fsrv, new_x, new_m, mean_delta, n_active,
                    eta_l, discount):
        """Shared tail of the kernel round close (fused AND scattered):
        adopt the folded momentum, then run the spec's pure post-step on
        the replicated planes with the discount-weighted mean.  ONE
        implementation — the sync/async and sharded/unsharded closes must
        never drift in how γ reaches the post."""
        if algo.server_post_fn is None:
            return new_x, fsrv._replace(momentum=new_m), mean_delta
        # the post starts from materialized planes on every route: fused
        # into what produced them (a kernel's output on one device, an
        # all_gather on the cohort mesh), XLA:CPU contracts its mul-adds
        # differently per route, and sharded runs lose bitwise equality
        new_x, new_m, mean_delta = jax.lax.optimization_barrier(
            (new_x, new_m, mean_delta))
        dmean = mean_delta if discount == 1.0 else discount * mean_delta
        new_x, new_server = algo.server_post_fn(
            self.cfg, new_x, fsrv._replace(momentum=new_m), dmean, n_active, eta_l
        )
        return new_x, new_server, mean_delta

    def _sharded_means(self, outs, wp, n_active):
        """Masked cohort means of every uplink plane as scattered
        reductions (``cohort_mean_scatter`` inside ``shard_map``) — the
        sharded analog of the ``_masked_pmean`` calls feeding
        a ``server_fn`` escape-hatch spec.  Returns (mean_delta, mean_sd,
        mean_extra) with ``None`` for planes the spec never produced."""
        cfg = self.cfg
        agg_dt = jnp.dtype(getattr(cfg, "aggregate_dtype", "float32"))
        planes = {k: getattr(outs, k) for k in self.algo.uplink_planes
                  if getattr(outs, k) is not None}
        nsh = self._cohort_shards

        def body(planes, wp, n_active):
            return {k: cohort_mean_scatter(v, wp, n_active, COHORT_AXIS, nsh,
                                           agg_dtype=agg_dt)
                    for k, v in planes.items()}

        sh, rep = P(COHORT_AXIS), P()
        means = shard_map(
            body,
            mesh=self.cohort_mesh,
            in_specs=({k: sh for k in planes}, rep, rep),
            out_specs={k: rep for k in planes},
            check_vma=False,
        )(planes, wp, n_active)
        return means.get("delta"), means.get("state_delta"), means.get("extra")

    # -------------------------------------------------- fault tolerance
    def _quorum_ok(self, n_active):
        """Healthy-round predicate: the server fold applies only when the
        surviving cohort meets ``max(1, cfg.min_quorum)``.  The floor of 1
        is the empty-cohort guard (an all-zero weight row used to
        0/0-poison the masked mean); rounds with n_active ≥ quorum are
        bitwise unaffected (``where(True, new, old)`` is ``new``)."""
        return n_active >= jnp.float32(max(1, getattr(self.cfg, "min_quorum", 0)))

    def _inject_faults(self, t, ids, mask, outs):
        """Apply the configured fault model to one cohort's uplink, between
        launch and fold.  Returns ``(mask, outs, n_dropped, n_quarantined)``.

        Pure mask/plane transforms (repro.core.faults), keyed by
        (fault.seed, absolute round t, client id): drops/deadline thin the
        mask, corruption rewrites delta rows of surviving clients, and the
        quarantine pass both masks out and SANITIZES (exact-zeros) any
        non-finite or norm-outlier row — zeroing is load-bearing because a
        0-weight NaN row still poisons tensordot/scatter reductions.  When
        ``cfg.fault`` is None nothing here is traced: fault-free programs
        are bitwise the pre-fault engine's.  Representation-generic over
        the flat (C[, pad], P) planes and the tree path's (C, leaf…) trees;
        under cohort sharding the plane ops run on padded rows (pad rows
        carry mask=False and are never corrupted or counted)."""
        fault = getattr(self.cfg, "fault", None)
        zero = jnp.float32(0.0)
        if fault is None:
            return mask, outs, zero, zero
        C = mask.shape[0]

        def pad_mask(v):  # planes under cohort sharding carry C_pad rows
            return self._pad_cohort(v, mode="zero") if self._sharded else v

        plan = fault_masks(fault, t, ids)
        n_dropped = zero
        if fault.drop_rate > 0.0 or fault.deadline > 0.0:
            n_dropped = jnp.sum((mask & plan.drop).astype(jnp.float32))
            mask = mask & ~plan.drop
        if fault.corrupt_rate > 0.0:
            cmask = pad_mask(plan.corrupt & mask)
            nkeys = plan.noise_keys
            if nkeys is not None and self._sharded:
                nkeys = self._pad_cohort(nkeys)  # edge pad; cmask=False there
            outs = outs._replace(
                delta=corrupt_uplink(fault, cmask, nkeys, outs.delta))
        n_quar = zero
        if fault.quarantine:
            rows = (padded_cohort(cohort_capacity(self.cfg),
                                  self._cohort_shards) if self._sharded else C)
            fin = (rows_finite(outs.delta, rows)
                   & rows_finite(outs.state_delta, rows)
                   & rows_finite(outs.extra, rows))
            bad = ~fin
            mask_r = pad_mask(mask)
            if fault.quarantine_norm_mult > 0.0:
                norm = jnp.sqrt(rows_sqnorm(outs.delta, rows))
                act = mask_r & fin
                med = jnp.nanmedian(jnp.where(act, norm, jnp.nan))
                bad = bad | (act & (norm > jnp.float32(
                    fault.quarantine_norm_mult) * med))
            n_quar = jnp.sum((mask_r & bad).astype(jnp.float32))
            outs = outs._replace(
                delta=zero_rows(outs.delta, bad),
                state_delta=zero_rows(outs.state_delta, bad),
                extra=zero_rows(outs.extra, bad),
            )
            mask = mask & ~bad[:C]
        return mask, outs, n_dropped, n_quar

    def _store_io(self, fn, *args):
        """Host-store gather/scatter with capped exponential backoff on
        ``TransientStoreError``.  Returns ``(result, n_retries)``.  Retries
        re-invoke the SAME pure operation, so a run that needed retries is
        bitwise-equal to one that didn't."""
        fault = getattr(self.cfg, "fault", None)
        if fault is None:
            return fn(*args), 0
        attempt = 0
        while True:
            try:
                return fn(*args), attempt
            except TransientStoreError:
                if attempt >= fault.store_max_retries:
                    raise
                delay = min(float(fault.store_backoff_cap),
                            float(fault.store_backoff_base) * (2.0 ** attempt))
                if delay > 0.0:
                    time.sleep(delay)
                attempt += 1

    def _masked_pmean(self, x, w, n_active):
        """Masked cohort mean of one ``(C, P)`` uplink plane for a
        ``server_fn`` escape-hatch spec: one contraction to a flat ``(P,)``
        buffer (quantized to ``cfg.aggregate_dtype`` first, like every
        aggregation path).  ``None`` passes through (planes that were
        never materialized)."""
        if x is None:
            return None
        agg_dt = jnp.dtype(getattr(self.cfg, "aggregate_dtype", "float32"))
        # max(n, 1) guards the empty cohort (0/0 → NaN would poison
        # params); exact for n ≥ 1, so non-empty rounds are bitwise
        return (
            jnp.tensordot(w.astype(agg_dt), x.astype(agg_dt), axes=(0, 0))
            .astype(jnp.float32) / jnp.maximum(n_active, 1.0)
        )

    # -------------------------------------------------- uplink compression
    def _residual_rows_for(self, fstate: FedState, ids, residual_rows):
        """The cohort's error-feedback residual rows (top-k only): the
        host loop pre-gathers them (``residual_rows``); the resident path
        gathers from ``fstate.residuals`` here.  Padded to the sharded
        cohort with exact-zero rows (pad rows never transmit)."""
        if not self._ef_residuals:
            return None
        rows = residual_rows
        if rows is None:
            if fstate.residuals is None:
                raise ValueError(
                    "topk compression carries an error-feedback residual "
                    "stream — call eng.init(params, rng) so "
                    "FedState.residuals (or the host residual store) is "
                    "allocated before stepping"
                )
            rows = fstate.residuals[ids]
        if self._sharded:
            rows = self._pad_cohort(rows, mode="zero")
        return rows

    def _compress_uplink(self, t, outs, w, residual_rows, spec: FlatSpec,
                         ring: bool = False):
        """Wire-encode the cohort's uplink planes — the splice between
        fault injection and server fold on every path.  Returns
        ``(outs, new_residual_rows)`` (residual rows ``None`` except under
        top-k).  ``compression=None`` returns the uplink UNTOUCHED without
        tracing anything — compression-free programs stay f32-bitwise the
        pre-compression engine's.

        Kernel fold: int8/bf16 planes come back as :class:`QPlane`
        and reach the fold COMPRESSED (the fused dequant kernel consumes
        them; under cohort sharding the ``all_to_all`` then moves the
        int8/bf16 payload).  ``state_delta`` is additionally needed dense
        by the client-state scatter, so it is decoded immediately —
        except on the async ring (``ring=True``), where it rides
        compressed until fold time (the in-flight memory win) and
        ``_fold_async_slot`` decodes it.  Top-k sparsifies the delta
        plane only, through the error-feedback accumulator; other wire
        planes ride f32 (sparsifying a state stream without its own
        residual would bias the stored state — the registry refuses specs
        declaring it).

        ``server_fn`` escape hatch: every wire plane round-trips through
        its wire representation to dense (what arrived on the wire IS what
        the jnp reduction folds).  ``w`` is the post-fault weight row
        (padded under sharding) gating the error-feedback update: a client
        that did not transmit keeps its residual."""
        comp = self.compression
        if comp is None:
            return outs, None
        algo = self.algo
        wire = algo.wire_uplink_planes
        key = round_key(comp, t)
        kernel_fold = algo.server_fn is None

        planes = {}
        new_rows = None
        for name in ("delta", "state_delta", "extra"):
            pv = getattr(outs, name)
            if pv is None or name not in wire:
                continue
            if comp.kind == "topk":
                if name != "delta":
                    continue  # non-delta wire planes ride f32
                rep, recon, new_rows = error_feedback_topk(
                    comp, pv, residual_rows, w, spec.size
                )
                # the ring carries the sparse rep (k ≪ P in-flight);
                # everything else folds the dense decoded payload
                planes[name] = rep if (ring and kernel_fold) else recon
                continue
            rep = as_qplane(compress_plane(comp, pv, plane_key(key, name),
                                           spec.size))
            if not kernel_fold:
                # server_fn escape hatch reduces via _masked_pmean: decode
                planes[name] = decompress_plane(rep)
            elif name == "state_delta" and not ring:
                # fold consumes the decoded payload AND the client-state
                # scatter needs the same dense rows — decode once here
                planes[name] = decompress_plane(rep)
            else:
                planes[name] = rep
        return outs._replace(**planes), new_rows

    def _decode_ring_entry(self, entry: CohortUplink, spec: FlatSpec):
        """Decode a ring entry's compressed planes at fold time.  The
        sparse top-k delta densifies (the fold kernels want dense or
        QPlane); a QPlane ``state_delta`` stays compressed for the fold
        (fused dequant pass) — ``_fold_async_slot`` decodes it separately
        where the scatter needs dense rows."""
        if self.compression is None:
            return entry
        if isinstance(entry.delta, TopKPlane):
            entry = entry._replace(
                delta=decompress_plane(entry.delta, spec.plane_size)
            )
        return entry

    def _flat_round_step(self, fstate: FedState, batches, ids, mask,
                         full_batches, spec: FlatSpec, n_clipped=None,
                         cohort_rows=None, emit_rows=False,
                         residual_rows=None):
        """One round entirely on the flat plane: (P,) carry through the
        local-step scan, (C, P) cohort planes through aggregation, (N, P)
        client-state scatter.  Same math as ``_tree_round_step`` — the
        equivalence tests in tests/test_flat.py hold the two bitwise-close.

        Store-backed execution (``population_store="host"``) reuses this
        step verbatim: ``cohort_rows`` replaces the resident-plane gather
        and ``emit_rows=True`` (static) swaps the ``(N, P)`` scatter for
        returning the updated ``(C, P)`` rows as a third output — the host
        loop writes them back to the store."""
        cfg, algo = self.cfg, self.algo
        eta_l = local_learning_rate(cfg, fstate.server.round)
        x_t = fstate.params  # (P,) f32
        m_t = fstate.server.momentum  # (P,) momentum_dtype
        if cohort_rows is not None:
            outs, losses, cohort_cst = self._flat_cohort_pass(
                fstate, batches, ids, mask, full_batches, spec, m_t, eta_l,
                cohort_rows=cohort_rows,
            )
        else:
            cohort_pass = (self._sharded_cohort_pass if self._sharded
                           else self._flat_cohort_pass)
            outs, losses, cohort_cst = cohort_pass(
                fstate, batches, ids, mask, full_batches, spec, m_t, eta_l
            )

        # fault injection + quarantine sit between launch and fold — a
        # no-op (untraced) when cfg.fault is None
        mask, outs, n_dropped, n_quar = self._inject_faults(
            fstate.server.round, ids, mask, outs
        )

        # masked cohort means, reduced straight to flat (P,) buffers
        # (_masked_pmean; unused planes are None — never materialized,
        # never reduced, where the tree path pays for both)
        w = mask.astype(jnp.float32)
        n_active = jnp.sum(w)
        # cohort-parallel: pad rows carry zero weight — trailing +0.0
        # terms keep every reduction bitwise the unsharded one's
        wp = self._pad_cohort(w, mode="zero") if self._sharded else w
        use_kernel = algo.server_fn is None

        # wire encoding between fault injection and fold — untraced when
        # cfg.compression is None (see _compress_uplink); under sharding
        # the encode runs OUTSIDE shard_map on the full padded planes
        new_res_rows = None
        if self.compression is not None:
            res_rows = self._residual_rows_for(fstate, ids, residual_rows)
            outs, new_res_rows = self._compress_uplink(
                fstate.server.round, outs, wp, res_rows, spec,
            )

        fsrv = fstate.server
        with jax.named_scope(FOLD_SCOPE):
            if use_kernel and self._sharded:
                new_params, new_server, mean_delta = self._sharded_round_close(
                    algo, fsrv, outs, wp, n_active, x_t, eta_l
                )
                new_server = new_server._replace(round=fsrv.round + 1)
            elif use_kernel:
                new_params, new_server, mean_delta = self._fused_round_close(
                    algo, fsrv, outs, w, n_active, x_t, eta_l
                )
                new_server = new_server._replace(round=fsrv.round + 1)
            else:
                if self._sharded:  # a spec with a server_fn escape hatch
                    mean_delta, mean_sd, mean_extra = self._sharded_means(
                        outs, wp, n_active
                    )
                else:
                    mean_delta = self._masked_pmean(outs.delta, w, n_active)
                    mean_sd = self._masked_pmean(outs.state_delta, w, n_active)
                    mean_extra = self._masked_pmean(outs.extra, w, n_active)
                new_params, new_server = algo.server_update(
                    cfg, x_t, fsrv, mean_delta, mean_sd, mean_extra,
                    n_active, eta_l,
                )

            # graceful degradation: a below-quorum (or empty) cohort carries
            # params/momentum through unchanged — the guarded denominators
            # already kept the fold finite, the select makes it a no-op (the
            # round counter still advances; client-state writes are
            # suppressed via the zeroed scatter weights)
            ok = self._quorum_ok(n_active)
            new_params = _where_tree(ok, new_params, x_t)
            new_server = new_server._replace(
                momentum=_where_tree(ok, new_server.momentum, fsrv.momentum),
                second_moment=_where_tree(ok, new_server.second_moment,
                                          fsrv.second_moment),
            )
        w_sc = w * ok.astype(jnp.float32)

        # scatter updated client states back (only active cohort members):
        # ONE scatter on the (N, P) plane (sharded planes are padded — only
        # real rows scatter).  Store-backed (emit_rows): the SAME per-row
        # update, emitted as (C, P) rows for the host scatter instead.
        new_cst = fstate.client_states
        rows_out = None
        if algo.needs_client_state:
            C = ids.shape[0]
            upd = cohort_cst + outs.state_delta[:C] * w_sc[:, None]
            if emit_rows:
                rows_out = upd
            else:
                new_cst = fstate.client_states.at[ids].set(upd)

        # the error-feedback residual is CLIENT-side state: it tracks what
        # the client did not transmit, so it updates whenever the client
        # transmitted — independent of the fold-time quorum decision
        new_res = fstate.residuals
        if new_res_rows is not None and new_res is not None and not emit_rows:
            C = ids.shape[0]
            new_res = new_res.at[ids].set(new_res_rows[:C])

        pay = self._payload_from_nbytes(spec.nbytes, spec.size)
        metrics = RoundMetrics(
            loss=jnp.sum(losses * wp) / jnp.maximum(n_active, 1.0),
            n_active=n_active,
            delta_norm=_flat_norm(mean_delta),
            momentum_norm=_flat_norm(m_t),
            eta_l=eta_l,
            bytes_down=n_active * jnp.float32(pay["down_per_client"]),
            bytes_up=n_active * jnp.float32(pay["up_per_client"]),
            n_clipped=(jnp.float32(0.0) if n_clipped is None
                       else n_clipped.astype(jnp.float32)),
            n_dropped=n_dropped,
            n_quarantined=n_quar,
            n_retries=jnp.float32(0.0),
            quorum_skipped=1.0 - ok.astype(jnp.float32),
        )
        new_state = FedState(new_params, new_server, new_cst, fstate.rng,
                             residuals=new_res)
        if emit_rows:
            C = ids.shape[0]
            res_out = None if new_res_rows is None else new_res_rows[:C]
            return new_state, metrics, rows_out, res_out
        return new_state, metrics

    def _fused_round_close(self, algo, fsrv, outs, w, n_active, x_t, eta_l,
                           discount=1.0):
        """Round-close via the fused server kernel: the spec's fold rows
        execute as ``server_update`` passes over the ``(C, P)`` uplink
        planes (``kernels/server_update/ops.fused_fold``), then the spec's
        optional pure post-step runs on the resulting flat planes —
        array-polymorphic, so FedAdam's preconditioner is the same code on
        both paths.

        ``discount`` is the staleness weight γ the async engine applies to
        folded in-flight cohorts — it rides the kernel's SMEM coefficient
        row (1.0 for the sync path: a f32 multiply by 1.0 is exact).  The
        returned ServerState keeps the caller's round counter (sync bumps
        it, the async fold is launch-aligned)."""
        cfg = self.cfg
        planes = {"delta": outs.delta, "state_delta": outs.state_delta,
                  "extra": outs.extra}
        new_x, new_m, mean_delta = fused_fold(
            algo, cfg, planes, w / jnp.maximum(n_active, 1.0), n_active,
            x_t, fsrv.momentum, eta_l, discount=discount,
        )
        return self._close_post(algo, fsrv, new_x, new_m, mean_delta,
                                n_active, eta_l, discount)

    # -------------------------------------------------- round
    def _round_step_impl(self, state: FedState, batches, ids, mask, full_batches):
        if self.cfg.use_flat_plane:
            spec = self._flat_spec(state.params)
            fstate = self._ravel_state(state, spec)
            fstate, metrics = self._flat_round_step(
                fstate, batches, ids, mask, full_batches, spec
            )
            return self._unravel_state(fstate, spec), metrics
        return self._tree_round_step(state, batches, ids, mask, full_batches)

    def _tree_round_step(self, state: FedState, batches, ids, mask, full_batches,
                         n_clipped=None):
        cfg, algo = self.cfg, self.algo
        eta_l = local_learning_rate(cfg, state.server.round)

        batches = self._constrain_cohort(batches)
        full_batches = self._constrain_cohort(full_batches)

        # gather per-client states for the cohort (stale entries untouched)
        if algo.needs_client_state:
            cohort_cst = jax.tree_util.tree_map(lambda a: a[ids], state.client_states)
        else:
            cohort_cst = jax.tree_util.tree_map(
                lambda p: jnp.zeros((ids.shape[0], *p.shape), p.dtype), state.params
            )
        cohort_cst = self._constrain_cohort(cohort_cst)

        def one_client(cst_i, batches_i, full_i):
            return client_update(
                algo, cfg, self.loss_fn, state.params, state.server.momentum,
                cst_i, batches_i, eta_l, full_grad_batch=full_i,
                unroll=self.analysis_unroll,
            )

        outs, losses = jax.vmap(one_client)(cohort_cst, batches, full_batches)

        # fault injection + quarantine between launch and fold (untraced
        # when cfg.fault is None — the oracle stays the oracle)
        mask, outs, n_dropped, n_quar = self._inject_faults(
            state.server.round, ids, mask, outs
        )

        # masked cohort mean (bernoulli: only active entries count)
        w = mask.astype(jnp.float32)
        n_active = jnp.sum(w)

        agg_dt = jnp.dtype(getattr(cfg, "aggregate_dtype", "float32"))

        def mmean(tree):
            # max(n, 1): empty-cohort guard, exact for n ≥ 1
            return jax.tree_util.tree_map(
                lambda a: (
                    jnp.tensordot(w.astype(agg_dt), a.astype(agg_dt), axes=(0, 0))
                    .astype(jnp.float32) / jnp.maximum(n_active, 1.0)
                ),
                tree,
            )

        mean_delta = mmean(outs.delta)
        mean_sd = mmean(outs.state_delta)
        mean_extra = mmean(outs.extra)

        new_params, new_server = algo.server_update(
            cfg, state.params, state.server, mean_delta, mean_sd, mean_extra,
            n_active, eta_l,
        )

        # below-quorum / empty round → no-op fold (see _flat_round_step)
        ok = self._quorum_ok(n_active)
        new_params = _where_tree(ok, new_params, state.params)
        new_server = new_server._replace(
            momentum=_where_tree(ok, new_server.momentum,
                                 state.server.momentum),
            second_moment=_where_tree(ok, new_server.second_moment,
                                      state.server.second_moment),
        )
        w_sc = w * ok.astype(jnp.float32)

        # scatter updated client states back (only active cohort members)
        new_cst = state.client_states
        if algo.needs_client_state:
            def scatter(a, d):
                upd = a[ids] + d * w_sc.reshape((-1,) + (1,) * (d.ndim - 1)).astype(a.dtype)
                return a.at[ids].set(upd)

            new_cst = jax.tree_util.tree_map(scatter, state.client_states, outs.state_delta)

        pay = self.payload_bytes(state.params)
        metrics = RoundMetrics(
            loss=jnp.sum(losses * w) / jnp.maximum(n_active, 1.0),
            n_active=n_active,
            delta_norm=_tree_norm(mean_delta),
            momentum_norm=_tree_norm(state.server.momentum),
            eta_l=eta_l,
            bytes_down=n_active * jnp.float32(pay["down_per_client"]),
            bytes_up=n_active * jnp.float32(pay["up_per_client"]),
            n_clipped=(jnp.float32(0.0) if n_clipped is None
                       else n_clipped.astype(jnp.float32)),
            n_dropped=n_dropped,
            n_quarantined=n_quar,
            n_retries=jnp.float32(0.0),
            quorum_skipped=1.0 - ok.astype(jnp.float32),
        )
        return FedState(new_params, new_server, new_cst, state.rng), metrics

    def round_step(self, state, batches, ids, mask, full_batches=None):
        if full_batches is None:
            # zero-size placeholder with the right treedef for vmap
            full_batches = jax.tree_util.tree_map(
                lambda b: b[:, 0], batches
            )  # (C, B, …) dummy; unused unless needs_full_grad
        return self._round_step(state, batches, ids, mask, full_batches)

    # -------------------------------------------------- data-driven round
    def _sample_round(self, rng, client_x, client_y, t):
        """rng threading + cohort sampling + minibatch/(MimeLite) full-batch
        gathers for one round.  ``t`` is the round counter the availability
        process may read (diurnal).  Returns
        (advanced-rng, batches, ids, mask, full, n_clipped)."""
        rng, k_cohort, k_batch = jax.random.split(rng, 3)
        ids, mask, n_clipped = sample_cohort_ex(k_cohort, self.cfg, t)
        raw = gather_round_batches(
            client_x, client_y, k_batch, ids, self.cfg.local_steps, self.batch_size
        )
        batches = self._to_loss_batches(raw)
        if self.algo.needs_full_grad:
            full = self._to_loss_batches(
                gather_full_client_batch(client_x, client_y, ids)
            )
        else:
            # (C, B, ...) dummy with the right treedef for vmap; unused
            # unless needs_full_grad
            full = jax.tree_util.tree_map(lambda b: b[:, 0], batches)
        return rng, batches, ids, mask, full, n_clipped

    def _prepare_round(self, state: FedState, client_x, client_y):
        """Per-round setup shared VERBATIM by ``run_round`` and the
        ``run_rounds`` scan body: rng threading, cohort sampling, minibatch
        and (MimeLite) full-batch gathers.  One implementation is what
        makes the two paths' trajectories identical — don't fork it.

        Returns (state-with-advanced-rng, batches, ids, mask, full,
        n_clipped).
        """
        rng, batches, ids, mask, full, n_clipped = self._sample_round(
            state.rng, client_x, client_y, state.server.round
        )
        return state._replace(rng=rng), batches, ids, mask, full, n_clipped

    def run_round(self, state: FedState, data) -> Tuple[FedState, RoundMetrics]:
        """Samples cohort + minibatches from a FederatedData and steps."""
        if self.population_store == "host":
            state, ms = self.run_rounds_store(state, data, 1)
            return state, jax.tree_util.tree_map(lambda a: a[0], ms)
        state, batches, ids, mask, full, n_clipped = self._prepare_round(
            state, data.client_x, data.client_y
        )
        state, metrics = self.round_step(state, batches, ids, mask, full)
        # round_step's public signature predates the clip metric — stamp it
        # here so run_round/run_rounds report identically
        return state, metrics._replace(n_clipped=n_clipped.astype(jnp.float32))

    # -------------------------------------------------- fused multi-round
    def run_rounds(self, state: FedState, data, n_rounds: int) -> Tuple[FedState, RoundMetrics]:
        """Execute ``n_rounds`` communication rounds as ONE jitted lax.scan.

        Cohort sampling and minibatch drawing happen inside the scan body
        (no host round-trips), the carried ``FedState`` is donated, and the
        per-round metrics come back stacked with a leading ``(n_rounds,)``
        axis.  Numerically equivalent to calling ``run_round`` ``n_rounds``
        times (same rng threading, same ``_round_step_impl``); the
        equivalence test in tests/test_run_rounds.py holds all algorithms
        to that.  Sub-f32 param leaves on the flat plane now agree at the
        SAME tolerance: both paths carry the same f32 master planes
        (``FedState.master``) across round boundaries and only the
        returned leaf views are rounded — ``run_round`` no longer
        re-rounds the carried state each boundary (the PR-2 divergence
        this closes; the bf16 regression test in tests/test_run_rounds.py
        pins the contract).

        The input ``state`` may be donated to the computation — use the
        returned state, not the argument, afterwards.
        """
        if n_rounds < 1:
            raise ValueError(f"n_rounds must be >= 1, got {n_rounds}")
        if self.population_store == "host":
            return self.run_rounds_store(state, data, n_rounds)
        return self._run_rounds(state, data.client_x, data.client_y, n_rounds=n_rounds)

    def _run_rounds_impl(self, state: FedState, client_x, client_y, n_rounds: int):
        self.run_rounds_traces += 1  # python side effect: counts traces only

        if self.cfg.use_flat_plane:
            # ravel ONCE for the whole N-round program; the scan carries
            # (P,)/(N,P) planes and unravels once at the end
            spec = self._flat_spec(state.params)
            fstate = self._ravel_state(state, spec)

            def flat_body(st, _):
                st, batches, ids, mask, full, n_clipped = self._prepare_round(
                    st, client_x, client_y
                )
                return self._flat_round_step(st, batches, ids, mask, full, spec,
                                             n_clipped)

            fstate, metrics = jax.lax.scan(flat_body, fstate, None, length=n_rounds)
            return self._unravel_state(fstate, spec), metrics

        def body(st, _):
            st, batches, ids, mask, full, n_clipped = self._prepare_round(
                st, client_x, client_y
            )
            return self._tree_round_step(st, batches, ids, mask, full, n_clipped)

        return jax.lax.scan(body, state, None, length=n_rounds)

    # -------------------------------------------------- async pipelined rounds
    def run_rounds_async(
        self,
        state: FedState,
        data,
        n_rounds: int,
        *,
        pipeline_depth: Optional[int] = None,
        staleness: Optional[int] = None,
        eval_every: int = 0,
        eval_data: Optional[Tuple[Any, Any]] = None,
        predict_fn: Optional[Callable[[Any, Any], jax.Array]] = None,
        eval_batch_size: int = 1000,
        drain: bool = True,
        scan_unroll: int = 1,
    ) -> Tuple[FedState, AsyncRoundMetrics]:
        """Overlapping-cohort (stale-momentum) FedCM: ONE pipelined lax.scan.

        Every scan iteration LAUNCHES one cohort against the current params
        and a broadcast momentum that is ``staleness`` rounds stale, pushes
        its uplink — cohort delta plane plus per-algorithm extras
        (``repro.core.flat.CohortUplink``) — into a depth-``pipeline_depth``
        ring carried by the scan, and FOLDS the oldest in-flight cohort
        into the server state.  A folded cohort is therefore
        ``pipeline_depth − 1`` rounds old: its clients descended from
        params the server has since moved past — exactly the
        delayed/partial aggregation client-level momentum is robust to
        (Cheng et al. 2023), with the fold weighted by the FedACG-style
        discount ``cfg.staleness_discount ** (depth−1)`` carried into the
        fused server kernel's SMEM coefficient row.

        ``pipeline_depth=1, staleness=0`` IS the sync schedule: the slot
        pushed at iteration t is popped at iteration t, the discount is
        γ⁰ = 1, and the trajectory matches ``run_rounds`` exactly (the
        equivalence test in tests/test_run_rounds.py holds all six
        algorithms to it).

        The first ``pipeline_depth − 1`` iterations fold nothing (pipeline
        fill — unrolled launch-only steps that grow the ring to its static
        depth; ``metrics.folded`` is 0 there), and with ``drain=True``
        (default) the cohorts still in flight at the end are folded by a
        fixed-size epilogue dispatch so no client work is discarded —
        ``n_rounds`` launches, ``n_rounds`` folds, still zero host
        round-trips (the epilogue's operands never leave the device;
        keeping it in the main program makes XLA clone the whole scan
        body around the final carry, measurably slower than a second
        dispatch).

        ``eval_every > 0`` moves evaluation device-resident INSIDE the scan
        (requires ``predict_fn`` and ``eval_data=(x_test, y_test)``): every
        eval_every-th iteration runs the padded ``lax.map`` eval on the
        post-fold params, so a full train-with-eval run is ONE jitted
        program with zero host round-trips; off-cadence rounds report
        ``eval_acc = −1.0``.

        ``scan_unroll`` unrolls the steady scan body (static): the ring
        rotation materializes at the loop boundary once per UNROLLED
        GROUP instead of once per round — within a group the fold reads
        the previous launch's uplink as straight dataflow.  ``2`` wins
        ~8% per round on the CPU update-bound benchmark at D≥2; compile
        time scales with the factor (the sync scan has no ring boundary
        and keeps unroll=1).

        Requires ``cfg.use_flat_plane`` (the ring is a flat-plane carry).
        The input ``state`` may be donated — use the returned state.
        """
        cfg = self.cfg
        depth = cfg.pipeline_depth if pipeline_depth is None else pipeline_depth
        stale = cfg.staleness if staleness is None else staleness
        if n_rounds < 1:
            raise ValueError(f"n_rounds must be >= 1, got {n_rounds}")
        if depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {depth}")
        if stale < 0:
            raise ValueError(f"staleness must be >= 0, got {stale}")
        if not cfg.use_flat_plane:
            raise ValueError(
                "run_rounds_async requires cfg.use_flat_plane=True — the "
                "in-flight cohort ring is a flat-plane carry (the tree path "
                "stays the sync oracle)"
            )
        if self.population_store == "host":
            if eval_every:
                raise ValueError(
                    "population_store='host' runs the async ring as a host "
                    "loop — in-scan eval is unavailable; eval between calls"
                )
            return self.run_rounds_store_async(
                state, data, n_rounds, pipeline_depth=depth, staleness=stale,
                drain=drain,
            )
        xb = yb = wb = None
        if eval_every:
            if predict_fn is None or eval_data is None:
                raise ValueError(
                    "eval_every > 0 needs predict_fn and eval_data=(x, y)"
                )
            xb, yb, wb = _pad_eval_batches(eval_data[0], eval_data[1], eval_batch_size)
        state, pending, metrics = self._run_rounds_async(
            state, data.client_x, data.client_y, xb, yb, wb,
            n_rounds=n_rounds, pipeline_depth=depth, staleness=stale,
            eval_every=eval_every,
            predict_fn=predict_fn if eval_every else None,
            scan_unroll=scan_unroll,
        )
        if drain and len(pending):
            state = self._drain_async(state, pending, pipeline_depth=depth)
        return state, metrics

    def _run_rounds_async_impl(
        self, state: FedState, client_x, client_y, xb, yb, wb, *,
        n_rounds: int, pipeline_depth: int, staleness: int, eval_every: int,
        predict_fn, scan_unroll: int = 1,
    ):
        self.run_rounds_async_traces += 1  # python side effect: trace count
        cfg, algo = self.cfg, self.algo
        D, S = pipeline_depth, staleness

        spec = self._flat_spec(state.params)
        fstate = self._ravel_state(state, spec)
        # momentum delay line: slot t mod S holds the broadcast buffer as it
        # was ENTERING round t−S (read-before-write); seeded with the
        # initial momentum so the first S rounds see round-0 state.  Only
        # algorithms that broadcast momentum (fedcm/mimelite Δ_t, scaffold
        # c) feel S at all.
        mhist = None
        if S > 0 and algo.needs_momentum_broadcast:
            mhist = jnp.tile(fstate.server.momentum[None], (S, 1))
        # FedACG-style lookahead weight of a fold that is D−1 rounds stale —
        # STATIC (depth is static), so γ = 1 costs nothing on the sync path
        discount = float(cfg.staleness_discount) ** (D - 1)
        pay = self._payload_from_nbytes(spec.nbytes, spec.size)

        def in_scan_eval(t, x_plane):
            if not eval_every or predict_fn is None:
                return jnp.float32(-1.0)

            def do_eval(xp):
                params = spec.unravel(xp)

                def one(args):
                    bx, by, bw = args
                    logits = predict_fn(params, bx)
                    hits = (jnp.argmax(logits, -1) == by).astype(jnp.float32)
                    return jnp.sum(hits * bw)

                return jnp.sum(jax.lax.map(one, (xb, yb, wb))) / jnp.sum(wb)

            if isinstance(t, int):  # unrolled warmup step: cadence is static
                return do_eval(x_plane) if (t + 1) % eval_every == 0 \
                    else jnp.float32(-1.0)
            return jax.lax.cond(
                jnp.mod(t + 1, eval_every) == 0, do_eval,
                lambda xp: jnp.float32(-1.0), x_plane,
            )

        def step(fst, pending, mhist, t, fold: bool):
            """One pipelined iteration.  ``fold`` is STATIC: the D−1
            warmup steps (pipeline fill — nothing old enough to fold) only
            grow the ring; every steady step rotates it — the popped
            uplink is by construction D−1 rounds old."""
            r0 = fst.server.round
            fst, batches, ids, mask, full, n_clipped = self._prepare_round(
                fst, client_x, client_y
            )
            if mhist is None:
                m_used = fst.server.momentum
            else:
                sm = jnp.mod(t, S)
                m_used = jax.lax.dynamic_index_in_dim(mhist, sm, 0, keepdims=False)
                mhist = jax.lax.dynamic_update_index_in_dim(
                    mhist, fst.server.momentum, sm, 0
                )
            (entry, n_active, loss, n_dropped, n_quar,
             res_rows) = self._launch_async_cohort(
                fst, m_used, batches, ids, mask, full, spec
            )
            if res_rows is not None:  # top-k residuals update at launch
                C = ids.shape[0]
                fst = fst._replace(
                    residuals=fst.residuals.at[ids].set(res_rows[:C])
                )
            if fold:
                oldest, pending = ring_push(pending, entry)
                fst, mean_norm, q_skip = self._fold_async_slot(
                    fst, oldest, spec, discount
                )
            else:
                pending = (*pending, entry)
                mean_norm = jnp.float32(0.0)
                q_skip = jnp.float32(0.0)
            # round counter is LAUNCH-aligned (η_l schedule stays in step
            # with the sync engine regardless of pipeline fill)
            fst = fst._replace(server=fst.server._replace(round=r0 + 1))
            metrics = AsyncRoundMetrics(
                loss=loss,
                n_active=n_active,
                delta_norm=mean_norm,
                momentum_norm=_flat_norm(m_used),
                eta_l=entry.eta_l,
                bytes_down=n_active * jnp.float32(pay["down_per_client"]),
                bytes_up=n_active * jnp.float32(pay["up_per_client"]),
                folded=jnp.float32(1.0 if fold else 0.0),
                eval_acc=in_scan_eval(t, fst.params),
                n_clipped=n_clipped.astype(jnp.float32),
                n_dropped=n_dropped,
                n_quarantined=n_quar,
                n_retries=jnp.float32(0.0),
                quorum_skipped=q_skip,
            )
            return fst, pending, mhist, metrics

        # pipeline fill: D−1 launch-only steps, UNROLLED — they grow the
        # ring tuple, whose structure must be static before the scan
        pending: Tuple[CohortUplink, ...] = ()
        fill_metrics = []
        warmup = min(D - 1, n_rounds)
        for t in range(warmup):
            fstate, pending, mhist, m = step(fstate, pending, mhist, t, fold=False)
            fill_metrics.append(m)

        def body(carry, t):
            fst, pending, mh = carry
            fst, pending, mh, m = step(fst, pending, mh, t, fold=True)
            return (fst, pending, mh), m

        (fstate, pending, mhist), metrics = jax.lax.scan(
            body, (fstate, pending, mhist), jnp.arange(warmup, n_rounds),
            unroll=scan_unroll,
        )
        if fill_metrics:
            fill = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *fill_metrics
            )
            metrics = jax.tree_util.tree_map(
                lambda a, b: jnp.concatenate([a, b], axis=0), fill, metrics
            )
        return self._unravel_state(fstate, spec), pending, metrics

    def _drain_async_impl(self, state: FedState,
                          pending: Tuple[CohortUplink, ...], *,
                          pipeline_depth: int):
        """Pipeline flush: fold the ≤ D−1 cohorts still in flight at the
        end of a ``run_rounds_async`` scan, oldest first.  A separate
        dispatch ON PURPOSE: feeding the scan's final (state, ring)
        carries into fold arithmetic inside the same program makes XLA
        clone the entire scan body around the last iteration — one
        fixed-size epilogue program is cheaper than that, and its operands
        never leave the device."""
        spec = self._flat_spec(state.params)
        fstate = self._ravel_state(state, spec)
        # the same staleness weight the in-scan folds used (depth, not
        # len(pending): a shorter-than-depth run still launched at the
        # configured overlap)
        discount = float(self.cfg.staleness_discount) ** (pipeline_depth - 1)
        for entry in pending:
            fstate, _, _ = self._fold_async_slot(fstate, entry, spec, discount)
        return self._unravel_state(fstate, spec)

    def _launch_async_cohort(self, fstate: FedState, m_used, batches, ids,
                             mask, full, spec: FlatSpec, cohort_rows=None,
                             residual_rows=None):
        """Client phase of one pipelined iteration: run the cohort against
        (current params, stale momentum) and pack its uplink as a ring
        entry.  The outputs already ARE ``(C, P)`` planes and ride raw (the
        fused server kernel wants the cohort axis).

        Returns (entry, n_active, cohort masked-mean loss, n_dropped,
        n_quarantined) — the fault counters of the launched cohort (the
        injected faults ride the ring with the entry).

        Cohort-parallel: the pass runs SPMD over the ``"clients"`` axis
        and the ring entry's planes are the PADDED ``(C_pad, P)`` shards
        (``ids``/``w`` padded to match; pad rows weigh zero) — the ring
        then carries each device's own clients until the scattered fold
        consumes them D−1 rounds later, which is what gives the
        reduce-scatter D−1 rounds of compute to hide behind."""
        cfg, algo = self.cfg, self.algo
        eta_l = local_learning_rate(cfg, fstate.server.round)
        if cohort_rows is not None:  # store-backed: pre-gathered host rows
            outs, losses, _ = self._flat_cohort_pass(
                fstate, batches, ids, mask, full, spec, m_used, eta_l,
                cohort_rows=cohort_rows,
            )
        else:
            cohort_pass = (self._sharded_cohort_pass if self._sharded
                           else self._flat_cohort_pass)
            outs, losses, _ = cohort_pass(
                fstate, batches, ids, mask, full, spec, m_used, eta_l
            )
        # faults hit the uplink AT LAUNCH (drops/corruption happen on the
        # wire, not in the ring): the quarantined/thinned planes then ride
        # the ring D−1 rounds to their fold
        mask, outs, n_dropped, n_quar = self._inject_faults(
            fstate.server.round, ids, mask, outs
        )
        w = mask.astype(jnp.float32)
        n_active = jnp.sum(w)
        wp = self._pad_cohort(w, mode="zero") if self._sharded else w

        # wire encoding happens AT LAUNCH, like the faults above: the ring
        # carries the compressed representation (the in-flight memory win)
        # and the error-feedback residual updates when the client
        # transmits, not D−1 rounds later at the fold
        new_res_rows = None
        if self.compression is not None:
            res_rows = self._residual_rows_for(fstate, ids, residual_rows)
            outs, new_res_rows = self._compress_uplink(
                fstate.server.round, outs, wp, res_rows, spec, ring=True,
            )

        entry = CohortUplink(
            delta=outs.delta,
            state_delta=outs.state_delta,
            extra=outs.extra,
            ids=(self._pad_cohort(ids) if self._sharded else ids).astype(jnp.int32),
            w=wp,
            eta_l=eta_l,
        )
        loss = jnp.sum(losses * wp) / jnp.maximum(n_active, 1.0)
        return entry, n_active, loss, n_dropped, n_quar, new_res_rows

    def _fold_async_slot(self, fstate: FedState, entry: CohortUplink,
                         spec: FlatSpec, discount, fold_rows=None,
                         emit_rows=False):
        """Server phase of one pipelined iteration: fold ONE ring entry —
        masked cohort mean, staleness-discounted momentum EMA + param step,
        client-state scatter — into the current flat state.  Every entry
        is a real launch (the unrolled pipeline fill means the ring never
        holds placeholders), so there is no validity masking to pay.  Uses
        the entry's LAUNCH-time η_l (the deltas were computed with it).
        Leaves the round counter alone — it is launch-aligned (see the
        scan body).

        Store-backed execution: ``fold_rows`` is the fold-time ``(C, P)``
        gather from the population store (the resident path gathers the
        plane HERE, at fold time — D−1 rounds after launch — so the host
        loop gathers at the same point) and ``emit_rows=True`` returns the
        updated rows instead of scattering into a resident plane.

        Returns (new_fstate, ‖mean Δ‖ of the folded cohort,
        quorum_skipped), plus the updated ``(C, P)`` rows when
        ``emit_rows``.  Quorum is enforced HERE — at fold time — because
        the surviving weight row is only final once the faulted entry
        leaves the ring."""
        cfg, algo = self.cfg, self.algo
        # sparse top-k deltas densify here, at fold time; QPlane planes
        # stay compressed into the fused dequant fold below
        entry = self._decode_ring_entry(entry, spec)
        w = entry.w  # (C_pad,) under cohort sharding — pad rows weigh 0
        n_active = jnp.sum(w)
        x_t = fstate.params
        fsrv = fstate.server
        use_kernel = algo.server_fn is None

        with jax.named_scope(FOLD_SCOPE):
            if use_kernel and self._sharded:
                new_params, new_server, mean_delta = self._sharded_round_close(
                    algo, fsrv, entry, w, n_active, x_t, entry.eta_l,
                    discount=discount,
                )
            elif use_kernel:
                new_params, new_server, mean_delta = self._fused_round_close(
                    algo, fsrv, entry, w, n_active, x_t, entry.eta_l,
                    discount=discount,
                )
            else:
                if self._sharded:
                    # scattered reductions of the ring's sharded (C_pad, P)
                    # planes feeding the spec's server_fn escape hatch
                    mean_delta, mean_sd, mean_extra = self._sharded_means(
                        entry, w, n_active
                    )
                else:
                    # a spec whose round-close is a ``server_fn`` escape
                    # hatch: reduce the raw (C, P) planes exactly as the
                    # sync round does
                    mean_delta = self._masked_pmean(entry.delta, w, n_active)
                    mean_sd = self._masked_pmean(entry.state_delta, w, n_active)
                    mean_extra = self._masked_pmean(entry.extra, w, n_active)
                # the γ=1 sync fold stays bitwise: spec.server_update skips the
                # statically-1.0 discount multiply
                new_params, new_server = algo.server_update(
                    cfg, x_t, fsrv, mean_delta, mean_sd, mean_extra,
                    n_active, entry.eta_l, discount=discount,
                )
                new_server = new_server._replace(round=fsrv.round)

            # below-quorum / empty fold → no-op (see _flat_round_step); the
            # zeroed weights also suppress the client-state writes below
            ok = self._quorum_ok(n_active)
            new_params = _where_tree(ok, new_params, x_t)
            new_server = new_server._replace(
                momentum=_where_tree(ok, new_server.momentum, fsrv.momentum),
                second_moment=_where_tree(ok, new_server.second_moment,
                                          fsrv.second_moment),
            )
        w = w * ok.astype(jnp.float32)
        skipped = 1.0 - ok.astype(jnp.float32)

        # scatter the folded cohort's client-state updates (stale entries
        # of non-participants untouched).  A ring-compressed state plane
        # decodes HERE — the scatter adopts exactly the dequantized rows
        # the fold consumed
        sd_e = entry.state_delta
        if isinstance(sd_e, QPlane):
            sd_e = decompress_plane(sd_e)
        new_cst = fstate.client_states
        rows_out = None
        if algo.needs_client_state:
            # padded ring rows are dropped BEFORE the scatter: a pad id (0)
            # colliding with a real cohort member would make the
            # duplicate-index .set nondeterministic
            C = cohort_capacity(cfg)
            ids_r, w_r = entry.ids[:C], w[:C]
            rows = fold_rows if emit_rows else fstate.client_states[ids_r]
            upd = rows + sd_e[:C] * w_r[:, None]
            if emit_rows:
                rows_out = upd
            else:
                new_cst = fstate.client_states.at[ids_r].set(upd)

        new_state = FedState(new_params, new_server, new_cst, fstate.rng,
                             residuals=fstate.residuals)
        if emit_rows:
            return new_state, _flat_norm(mean_delta), skipped, rows_out
        return new_state, _flat_norm(mean_delta), skipped

    # -------------------------------------------------- store-backed rounds
    def _store_jits(self, spec: FlatSpec):
        """Jitted per-round pieces of the store-backed host loops, cached
        per FlatSpec.  The pieces ARE the resident engine's round functions
        (``_sample_round``/``_flat_round_step``/``_launch_async_cohort``/
        ``_fold_async_slot``) parameterized by host-gathered rows — sharing
        the traced math verbatim is what makes the store path f32-bitwise
        against the resident oracle at matched cohorts."""
        cache = getattr(self, "_store_jit_cache", None)
        if cache is None:
            cache = self._store_jit_cache = {}
        if spec in cache:
            return cache[spec]

        def sample_device(fst, client_x, client_y):
            # device-resident FederatedData: the resident scan body's
            # sampler, verbatim (same rng threading → matched cohorts)
            return self._prepare_round(fst, client_x, client_y)

        def sample_ids(rng, t):
            # streaming data: sample only the cohort on device; the batch
            # key degrades to a host seed for the on-demand generator
            rng, k_cohort, k_batch = jax.random.split(rng, 3)
            ids, mask, n_clipped = sample_cohort_ex(k_cohort, self.cfg, t)
            seed = jax.random.randint(k_batch, (), 0, jnp.int32(2**31 - 1))
            return rng, ids, mask, n_clipped, seed

        def step(fst, batches, ids, mask, full, n_clipped, rows, res_rows):
            if rows is None and res_rows is None:
                # stateless, uncompressed-or-residual-free: nothing to emit
                fst, m = self._flat_round_step(
                    fst, batches, ids, mask, full, spec, n_clipped
                )
                return fst, m, None, None
            return self._flat_round_step(
                fst, batches, ids, mask, full, spec, n_clipped,
                cohort_rows=rows, emit_rows=True, residual_rows=res_rows,
            )

        def launch(fst, m_used, batches, ids, mask, full, rows, res_rows):
            return self._launch_async_cohort(
                fst, m_used, batches, ids, mask, full, spec,
                cohort_rows=rows, residual_rows=res_rows,
            )

        def fold(fst, entry, fold_rows, discount):
            if fold_rows is None:
                fst, norm, q_skip = self._fold_async_slot(
                    fst, entry, spec, discount
                )
                return fst, norm, q_skip, None
            return self._fold_async_slot(
                fst, entry, spec, discount, fold_rows=fold_rows, emit_rows=True
            )

        cache[spec] = {
            "sample_device": jax.jit(sample_device),
            "sample_ids": jax.jit(sample_ids),
            "step": jax.jit(step),
            "launch": jax.jit(launch),
            # discount is a static python float (rides SMEM coefficients)
            "fold": jax.jit(fold, static_argnums=(3,)),
        }
        return cache[spec]

    def _host_sample(self, jits, fstate: FedState, data, device_data: bool):
        """One round's cohort + batches under the host loop.  Device-
        resident ``FederatedData`` goes through the resident sampler
        verbatim (bitwise-matched cohorts AND batches); streaming data
        (``repro.data.population.StreamingClientData``) samples ids on
        device and generates only the cohort's minibatches on the host."""
        if device_data:
            return jits["sample_device"](fstate, data.client_x, data.client_y)
        rng, ids, mask, n_clipped, seed = jits["sample_ids"](
            fstate.rng, fstate.server.round
        )
        ids_np = np.asarray(ids)
        raw = data.host_round_batches(
            ids_np, int(seed), self.cfg.local_steps, self.batch_size
        )
        batches = self._to_loss_batches(
            {k: jnp.asarray(v) for k, v in raw.items()}
        )
        if self.algo.needs_full_grad:
            full = self._to_loss_batches(
                {k: jnp.asarray(v) for k, v in data.host_full_batches(ids_np).items()}
            )
        else:
            full = jax.tree_util.tree_map(lambda b: b[:, 0], batches)
        return fstate._replace(rng=rng), batches, ids, mask, full, n_clipped

    def _require_store(self):
        if self.population is None:
            # init() attaches the store; a hand-built FedState lands here
            raise RuntimeError(
                "population store missing — call eng.init(params, rng) "
                "before store-backed rounds"
            )
        return self.population

    def _residual_store(self):
        """The host-side residual row store (top-k under ``"host"``), or
        ``None`` when residuals are resident / compression carries none."""
        if not self._ef_residuals or self.population_store == "resident":
            return None
        if self.residual_population is None:
            raise RuntimeError(
                "residual store missing — call eng.init(params, rng) "
                "before store-backed rounds with topk compression"
            )
        return self.residual_population

    def run_rounds_store(self, state: FedState, data, n_rounds: int):
        """Sync engine for ``population_store="host"``: a host loop of the
        jitted round step with a store gather before and scatter after each
        round.  No ``(N, ·)`` device array exists at any point — only the
        ``(C, P)`` cohort block — so N is bounded by host memory over
        TOUCHED clients, not device memory over the population.

        ``data`` may be a device-resident ``FederatedData`` (the bitwise-
        oracle pairing used by tests) or a ``StreamingClientData`` whose
        shards generate on demand (the N=1e6 path).

        ``cfg.store_prefetch`` (default on) double-buffers the host side:
        round t+1's cohort sampling, minibatch generation, and optimistic
        store gather run on a background thread while round t's device
        step executes; rows round t scattered after the optimistic gather
        are re-gathered at consumption (the cohort overlap is tiny at
        fleet scale).  The device work, its inputs, and the rng chain are
        IDENTICAL to the synchronous loop — the prefetch-on/off bitwise
        test pins the contract (only ``n_retries`` may differ under
        injected store chaos: the patch gathers shift the failure
        stream)."""
        cfg = self.cfg
        if n_rounds < 1:
            raise ValueError(f"n_rounds must be >= 1, got {n_rounds}")
        spec = self._flat_spec(state.params)
        jits = self._store_jits(spec)
        fstate = self._ravel_state(state, spec)
        device_data = hasattr(data, "client_x")
        stateful = self.algo.needs_client_state
        store = self._require_store() if stateful else None
        res_store = self._residual_store()
        if getattr(cfg, "store_prefetch", True) and n_rounds > 1:
            fstate, metrics = self._store_loop_prefetch(
                fstate, jits, data, device_data, store, res_store, n_rounds
            )
        else:
            fstate, metrics = self._store_loop_sync(
                fstate, jits, data, device_data, store, res_store, n_rounds
            )
        state = self._unravel_state(fstate, spec)
        return state, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *metrics)

    def _store_loop_sync(self, fstate, jits, data, device_data, store,
                         res_store, n_rounds):
        """The synchronous host loop — sample, gather, step, scatter, one
        round at a time.  The bitwise oracle for the prefetched loop."""
        metrics = []
        for _ in range(n_rounds):
            fstate, batches, ids, mask, full, n_clipped = self._host_sample(
                jits, fstate, data, device_data
            )
            ids_np = np.asarray(ids)
            rows = res_rows = None
            retries = 0
            if store is not None:
                got, r_g = self._store_io(store.gather, ids_np)
                rows = jnp.asarray(got)
                retries += r_g
            if res_store is not None:
                got, r_g = self._store_io(res_store.gather, ids_np)
                res_rows = jnp.asarray(got)
                retries += r_g
            fstate, m, new_rows, new_res = jits["step"](
                fstate, batches, ids, mask, full, n_clipped, rows, res_rows
            )
            if store is not None:
                _, r_s = self._store_io(
                    store.scatter, ids_np, np.asarray(new_rows)
                )
                retries += r_s
            if res_store is not None:
                _, r_s = self._store_io(
                    res_store.scatter, ids_np, np.asarray(new_res)
                )
                retries += r_s
            if retries:  # stamp host-side; device path stamped 0
                m = m._replace(n_retries=jnp.float32(retries))
            metrics.append(m)
        return fstate, metrics

    def _store_loop_prefetch(self, fstate, jits, data, device_data, store,
                             res_store, n_rounds):
        """Double-buffered host loop: a one-worker executor runs round
        t+1's ``_host_sample`` + optimistic store gather while round t's
        jitted step runs on device.  Safe by construction:

        * the sampler reads ONLY (rng, round counter) — both known before
          the step (the step never advances rng, and the counter advances
          by exactly 1) — so the prefetched cohort/batches are bitwise the
          synchronous loop's;
        * store ops serialize on a lock (gathers never observe a torn
          scatter), and rows the current round scatters after the
          optimistic gather are re-gathered at consumption
          (``intersect1d`` of consecutive cohorts) — every step consumes
          exactly the post-scatter rows the synchronous loop would."""
        lock = threading.Lock()

        def sample_and_gather(probe):
            nf, batches, ids, mask, full, n_clipped = self._host_sample(
                jits, probe, data, device_data
            )
            ids_np = np.asarray(ids)
            rows = res_rows = None
            retries = 0
            with lock:
                if store is not None:
                    got, r = self._store_io(store.gather, ids_np)
                    rows, retries = got, retries + r
                if res_store is not None:
                    got, r = self._store_io(res_store.gather, ids_np)
                    res_rows, retries = got, retries + r
            return (nf.rng, batches, ids, ids_np, mask, full, n_clipped,
                    rows, res_rows, retries)

        metrics = []
        ex = ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="store-prefetch")
        try:
            pending = ex.submit(sample_and_gather, fstate)
            prev_ids = None  # cohort scattered since the pending gather began
            for t in range(n_rounds):
                (rng, batches, ids, ids_np, mask, full, n_clipped, rows,
                 res_rows, retries) = pending.result()
                fstate = fstate._replace(rng=rng)
                if prev_ids is not None:
                    # patch rows the previous round's scatter invalidated
                    overlap = np.intersect1d(ids_np, prev_ids)
                    if overlap.size:
                        pos = {int(c): i for i, c in enumerate(ids_np)}
                        sel = np.array([pos[int(c)] for c in overlap])
                        with lock:
                            if store is not None:
                                got, r = self._store_io(store.gather, overlap)
                                rows[sel], retries = got, retries + r
                            if res_store is not None:
                                got, r = self._store_io(
                                    res_store.gather, overlap
                                )
                                res_rows[sel], retries = got, retries + r
                # round t+1's host work overlaps the device step below
                if t + 1 < n_rounds:
                    probe = fstate._replace(server=fstate.server._replace(
                        round=fstate.server.round + 1
                    ))
                    pending = ex.submit(sample_and_gather, probe)
                fstate, m, new_rows, new_res = jits["step"](
                    fstate, batches, ids, mask, full, n_clipped,
                    None if rows is None else jnp.asarray(rows),
                    None if res_rows is None else jnp.asarray(res_rows),
                )
                with lock:
                    if store is not None:
                        _, r = self._store_io(
                            store.scatter, ids_np, np.asarray(new_rows)
                        )
                        retries += r
                    if res_store is not None:
                        _, r = self._store_io(
                            res_store.scatter, ids_np, np.asarray(new_res)
                        )
                        retries += r
                prev_ids = (ids_np if (store is not None
                                       or res_store is not None) else None)
                if retries:
                    m = m._replace(n_retries=jnp.float32(retries))
                metrics.append(m)
        finally:
            ex.shutdown(wait=True)
        return fstate, metrics

    def _host_fold(self, jits, fstate: FedState, entry: CohortUplink,
                   discount: float, store, stateful: bool):
        """Fold one ring entry under the host loop: fold-time store gather
        (mirroring the resident fold's plane gather D−1 rounds after
        launch), the jitted fold, and the row scatter back.  Returns
        (fstate, mean_norm, quorum_skipped, store retries)."""
        retries = 0
        if stateful:
            ids_np = np.asarray(entry.ids)
            got, r_g = self._store_io(store.gather, ids_np)
            frows = jnp.asarray(got)
            fstate, mean_norm, q_skip, new_rows = jits["fold"](
                fstate, entry, frows, discount
            )
            _, r_s = self._store_io(store.scatter, ids_np, np.asarray(new_rows))
            retries = r_g + r_s
        else:
            fstate, mean_norm, q_skip, _ = jits["fold"](
                fstate, entry, None, discount
            )
        return fstate, mean_norm, q_skip, retries

    def run_rounds_store_async(
        self, state: FedState, data, n_rounds: int, *,
        pipeline_depth: Optional[int] = None, staleness: Optional[int] = None,
        drain: bool = True,
    ):
        """Async overlapping-cohort engine for ``population_store="host"``:
        the resident scan's schedule — launch against (current params,
        S-stale momentum), ring of D in-flight uplinks, fold the oldest,
        launch-aligned round counter — replayed as a host loop with store
        gathers/scatters at exactly the resident gather/scatter points.
        The ring's ``state_delta`` planes are ``(C, P)`` (never ``(N, ·)``).
        ``(D, S)`` semantics, warmup, discount γ^(D−1), and drain order
        match ``run_rounds_async`` entry for entry."""
        cfg, algo = self.cfg, self.algo
        D = cfg.pipeline_depth if pipeline_depth is None else pipeline_depth
        S = cfg.staleness if staleness is None else staleness
        if n_rounds < 1:
            raise ValueError(f"n_rounds must be >= 1, got {n_rounds}")
        if D < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {D}")
        if S < 0:
            raise ValueError(f"staleness must be >= 0, got {S}")
        spec = self._flat_spec(state.params)
        jits = self._store_jits(spec)
        fstate = self._ravel_state(state, spec)
        device_data = hasattr(data, "client_x")
        stateful = algo.needs_client_state
        store = self._require_store() if stateful else None
        mhist = None
        if S > 0 and algo.needs_momentum_broadcast:
            mhist = [fstate.server.momentum for _ in range(S)]
        discount = float(cfg.staleness_discount) ** (D - 1)
        pay = self._payload_from_nbytes(spec.nbytes, spec.size)
        res_store = self._residual_store()
        ring = []
        metrics = []
        for t in range(n_rounds):
            r0 = fstate.server.round
            fstate, batches, ids, mask, full, n_clipped = self._host_sample(
                jits, fstate, data, device_data
            )
            if mhist is None:
                m_used = fstate.server.momentum
            else:  # S-deep delay line, read-before-write at slot t mod S
                sm = t % S
                m_used = mhist[sm]
                mhist[sm] = fstate.server.momentum
            rows = res_rows = None
            retries = 0
            if stateful:
                got, r_g = self._store_io(store.gather, np.asarray(ids))
                rows = jnp.asarray(got)
                retries += r_g
            if res_store is not None:
                got, r_g = self._store_io(res_store.gather, np.asarray(ids))
                res_rows = jnp.asarray(got)
                retries += r_g
            entry, n_active, loss, n_dropped, n_quar, new_res = jits["launch"](
                fstate, m_used, batches, ids, mask, full, rows, res_rows
            )
            if res_store is not None:  # residuals update at launch
                _, r_s = self._store_io(
                    res_store.scatter, np.asarray(ids), np.asarray(new_res)
                )
                retries += r_s
            ring.append(entry)
            fold_now = len(ring) >= D
            if fold_now:
                fstate, mean_norm, q_skip, r_f = self._host_fold(
                    jits, fstate, ring.pop(0), discount, store, stateful
                )
                retries += r_f
            else:  # pipeline fill: launch-only
                mean_norm = jnp.float32(0.0)
                q_skip = jnp.float32(0.0)
            # launch-aligned round counter, as in the resident scan body
            fstate = fstate._replace(
                server=fstate.server._replace(round=r0 + 1)
            )
            metrics.append(AsyncRoundMetrics(
                loss=loss,
                n_active=n_active,
                delta_norm=mean_norm,
                momentum_norm=_flat_norm(m_used),
                eta_l=entry.eta_l,
                bytes_down=n_active * jnp.float32(pay["down_per_client"]),
                bytes_up=n_active * jnp.float32(pay["up_per_client"]),
                folded=jnp.float32(1.0 if fold_now else 0.0),
                eval_acc=jnp.float32(-1.0),
                n_clipped=n_clipped.astype(jnp.float32),
                n_dropped=n_dropped,
                n_quarantined=n_quar,
                n_retries=jnp.float32(retries),
                quorum_skipped=q_skip,
            ))
        if drain:  # flush in-flight cohorts, oldest first
            for entry in ring:
                fstate, _, _, _ = self._host_fold(
                    jits, fstate, entry, discount, store, stateful
                )
            ring = []
        state = self._unravel_state(fstate, spec)
        return state, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *metrics)

    @staticmethod
    def _to_loss_batches(raw):
        """{"x","y"} → loss_fn batch dict (pass-through for custom dicts).

        Must stay traceable: ``run_rounds`` calls it inside a jitted scan.
        """
        return raw


def _tree_norm(t):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree_util.tree_leaves(t)]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.float32(0.0)


def _flat_norm(x):
    """‖x‖₂ of one flat plane — same formulation as ``_tree_norm`` so flat
    and tree metrics agree bitwise for single-buffer input.  The round's
    metric norms count to the round close's scope."""
    with jax.named_scope(FOLD_SCOPE):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


# ----------------------------------------------------------------------
# evaluation
# ----------------------------------------------------------------------


def _pad_eval_batches(x, y, batch_size: int):
    """Pad + reshape a test set to ``(n_batches, B, …)`` with a 0/1 weight
    plane so padded rows never count — the shared prep of the host-side
    ``make_eval_fn`` and the in-scan eval of ``run_rounds_async``."""
    x, y = jnp.asarray(x), jnp.asarray(y)
    n = x.shape[0]
    nb = max(1, -(-n // batch_size))
    pad = nb * batch_size - n
    xp = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    yp = jnp.pad(y, ((0, pad),))
    w = (jnp.arange(nb * batch_size) < n).astype(jnp.float32)

    def rs(a):
        return a.reshape((nb, batch_size) + a.shape[1:])

    return rs(xp), rs(yp), rs(w)


def make_eval_fn(predict_fn: Callable[[Any, Any], jax.Array], batch_size: int = 1000):
    """predict_fn(params, x) -> logits.  Returns eval(params, x, y) -> acc.

    Device-resident: the whole test set is evaluated by ONE jitted
    ``lax.map`` over padded ``(n_batches, B, …)`` batches — a single
    dispatch and a single device→host sync per call, instead of one of each
    per 1000 examples.  (The old per-batch python loop stalled ``fed_train``
    between fused ``run_rounds`` chunks.)  Padding rows carry zero weight,
    so the returned accuracy is exact for any n.  Retraces only when the
    padded shape changes, i.e. once per dataset.
    """

    @jax.jit
    def _evaluate(params, xb, yb, wb):
        def one(args):
            x, y, w = args
            logits = predict_fn(params, x)
            return jnp.sum((jnp.argmax(logits, -1) == y).astype(jnp.float32) * w)

        hits = jax.lax.map(one, (xb, yb, wb))
        return jnp.sum(hits) / jnp.sum(wb)

    def evaluate(params, x, y):
        xb, yb, wb = _pad_eval_batches(x, y, batch_size)
        return float(_evaluate(params, xb, yb, wb))

    return evaluate
