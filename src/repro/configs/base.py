"""Config system: model / federated / shape configs + registry.

Every assigned architecture lives in ``src/repro/configs/<id>.py`` exposing a
module-level ``CONFIG: ModelConfig``.  ``get_config(name)`` resolves it;
``reduced(cfg)`` produces the CPU smoke-test variant of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Tuple


@dataclass(frozen=True)
class YarnRope:
    """YaRN scaling of rotary embeddings, as HF ``rope_type: "yarn"``
    defines it: each frequency blends its interpolated (÷ ``factor``) and
    extrapolated value along a linear ramp between the dimensions whose
    wavelengths fit ``beta_fast`` and ``beta_slow`` turns in
    ``original_max_position_embeddings``; ``attention_factor`` multiplies
    cos and sin."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float
    beta_slow: float
    attention_factor: float


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None  # default: d_model // n_heads

    # --- attention variants ---
    use_rope: bool = True
    rope_theta: float = 10000.0
    # YaRN scaling of the full-attention layers' RoPE
    rope_yarn: Optional[YarnRope] = None
    qk_norm: bool = False
    sliding_window: Optional[int] = None  # window for "local" attention layers
    # (n_local, n_global) per repeating period; None = all-global.
    local_global_pattern: Optional[Tuple[int, int]] = None

    # --- mlp ---
    mlp_type: str = "gated_silu"  # gated_silu | gelu
    tie_embeddings: bool = False

    # --- MoE ---
    n_experts: int = 0  # routed experts: the router's outputs
    # experts this layer holds, ids [0, n_experts_held): its share of an
    # expert-parallel deployment.  0 = all of them
    n_experts_held: int = 0
    top_k: int = 0
    # choices over capacity_factor × the mean load of an expert are
    # dropped; None = dropless (every choice of a held expert is computed)
    capacity_factor: Optional[float] = 1.25
    moe_every: int = 1  # a layer is MoE iff (layer_idx % moe_every == moe_every-1)
    shared_expert: bool = False
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2

    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 64

    # --- hybrid (zamba2-style): shared attention block every N layers ---
    attn_every: int = 0  # 0 = never; >0: layer i is (shared) attention iff i % attn_every == attn_every-1

    # --- encoder-decoder ---
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0

    # --- modality frontend (stubbed per assignment) ---
    input_mode: str = "tokens"  # tokens | embeddings

    # --- numerics ---
    dtype: str = "float32"  # activation dtype ("bfloat16" on TPU target)
    param_dtype: str = "float32"

    # --- provenance ---
    source: str = ""

    @property
    def padded_vocab(self) -> int:
        """Embedding/unembedding table rows — the assigned vocab rounded up
        to 256 so the vocab dim shards over any production mesh axis (an
        unshardable 256206-row unembed costs a 31 GiB/chip logits tensor).
        Token ids stay < vocab_size; the pad rows are dead weight."""
        return -(-self.vocab_size // 256) * 256

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim is not None else self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def ssm_heads(self) -> int:
        return (self.ssm_expand * self.d_model) // self.ssm_head_dim

    def is_moe_layer(self, layer_idx: int) -> bool:
        if self.n_experts == 0:
            return False
        return layer_idx % self.moe_every == self.moe_every - 1

    def is_attn_layer(self, layer_idx: int) -> bool:
        """hybrid family: which decoder layers are (shared) attention blocks."""
        if self.family != "hybrid":
            return True
        return self.attn_every > 0 and layer_idx % self.attn_every == self.attn_every - 1

    def is_global_attn_layer(self, layer_idx: int) -> bool:
        """local:global pattern — global layers attend fully."""
        if self.local_global_pattern is None:
            return self.sliding_window is None
        n_local, n_global = self.local_global_pattern
        period = n_local + n_global
        return layer_idx % period >= n_local

    # ------------------------------------------------------------------
    # parameter count estimate (for MODEL_FLOPS = 6*N*D in the roofline)
    # ------------------------------------------------------------------
    def param_count(self, active_only: bool = False) -> int:
        D = self.d_model
        hd = self.resolved_head_dim if self.n_heads > 0 else 0
        n = 0
        # embeddings
        emb = self.vocab_size * D
        n += emb if self.tie_embeddings else 2 * emb

        def attn_params() -> int:
            q = D * self.n_heads * hd
            kv = 2 * D * self.n_kv_heads * hd
            o = self.n_heads * hd * D
            return q + kv + o

        def mlp_params(d_ff: int) -> int:
            if self.mlp_type == "gated_silu":
                return 3 * D * d_ff
            return 2 * D * d_ff

        def mamba_params() -> int:
            d_inner = self.ssm_expand * D
            nheads = self.ssm_heads
            # in_proj -> [z, x, B, C, dt]
            zxbcdt = 2 * d_inner + 2 * self.ssm_state + nheads
            in_p = D * zxbcdt
            conv = (d_inner + 2 * self.ssm_state) * self.ssm_conv
            out_p = d_inner * D
            head = 2 * nheads  # A_log, D skip
            return in_p + conv + out_p + head

        layers = self.n_layers
        if self.family in ("ssm",):
            n += layers * mamba_params()
        elif self.family == "hybrid":
            n_attn = sum(1 for i in range(layers) if self.is_attn_layer(i))
            n_mamba = layers - n_attn
            n += n_mamba * mamba_params()
            # shared attention block: counted once (weights shared)
            n += attn_params() + mlp_params(self.d_ff)
        else:
            for i in range(layers):
                n += attn_params()
                if self.is_moe_layer(i):
                    e = self.experts_held
                    if active_only:
                        e = self.top_k + (1 if self.shared_expert else 0)
                    n += e * mlp_params(self.d_ff) + D * self.n_experts  # + router
                    if self.shared_expert and not active_only:
                        n += mlp_params(self.d_ff)
                else:
                    n += mlp_params(self.d_ff)
        if self.is_encoder_decoder:
            # encoder layers: self-attn + mlp; decoder additionally cross-attn
            n += self.n_encoder_layers * (attn_params() + mlp_params(self.d_ff))
            n += self.n_layers * attn_params()  # cross attention in decoder
        return n


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524288, 1),
}


@dataclass(frozen=True)
class FaultConfig:
    """Fault injection as pure config data (seeded, reproducible).

    Every fault is a mask/plane transform applied between cohort launch
    and server fold, drawn from a PRNG chain keyed by
    ``(seed, absolute round, client id)`` — NOT by cohort slot — so a
    client's fate in a round is invariant to sampler placement and a
    kill/resume replays the identical fault sequence.  ``fault=None`` on
    :class:`FedConfig` traces no fault code at all: those paths stay
    f32-bitwise against the fault-free engine.
    """

    # per-client per-round probability the uplink is lost entirely
    drop_rate: float = 0.0
    # straggler deadline model: client round time ~ LogNormal(0, σ) in
    # units of the median client; a client slower than ``deadline`` misses
    # the round (its uplink is treated as dropped).  0 = no deadline.
    deadline: float = 0.0
    straggler_sigma: float = 0.5
    # payload corruption: per-client probability the uplink delta plane
    # arrives corrupted, and how — "nan"/"inf" overwrite the row with that
    # value (a dead-accelerator payload); "noise" adds relative Gaussian
    # bit-noise of scale ``noise_scale × |value|`` (a flaky-link payload).
    corrupt_rate: float = 0.0
    corrupt_mode: str = "nan"  # nan | inf | noise
    noise_scale: float = 1.0
    # transient host-store failures: gather/scatter raise
    # TransientStoreError with this probability; the engine retries with
    # capped exponential backoff (base·2^attempt, capped, then re-raise
    # after max_retries).  Retries never change math — a run with store
    # failures is bitwise-equal to one without.
    store_failure_rate: float = 0.0
    store_max_retries: int = 6
    store_backoff_base: float = 0.02
    store_backoff_cap: float = 0.5
    # uplink quarantine: zero the fold-weight row (and sanitize the
    # payload rows to exact zeros, so 0·NaN never reaches a reduction) of
    # any client whose uplink is non-finite; when quarantine_norm_mult
    # > 0 also quarantine finite rows whose ‖Δ‖ exceeds
    # mult × median(‖Δ‖ of the surviving cohort) — a norm-outlier fence.
    quarantine: bool = True
    quarantine_norm_mult: float = 0.0
    # fault-stream seed — independent of FedConfig.seed so the same
    # trajectory can be replayed under different fault realizations
    seed: int = 0


@dataclass(frozen=True)
class CompressionConfig:
    """Uplink compression as pure config data (repro.core.compress).

    Every compressed representation is a plane transform applied to the
    cohort uplink between client launch and server fold — on the sync,
    async-ring, cohort-sharded, and host-store paths alike — so the
    f32 ``(C, P)`` uplink never has to exist on the wire (or in the
    async ring).  ``compression=None`` on :class:`FedConfig` traces no
    compression code at all: those paths stay f32-bitwise against the
    uncompressed engine.

    Kinds:
      ``"int8"`` — per-row absmax-scaled stochastic-rounded int8
                   (unbiased: E[dequant(q)] = x); 1 byte/element + one
                   f32 scale per client row.
      ``"bf16"`` — round-to-nearest-even bfloat16; 2 bytes/element.
      ``"topk"`` — magnitude top-k sparsification (k = topk_frac·P)
                   with error-feedback residuals: what a client does
                   not send this round is carried in a per-client
                   residual plane and added to its next uplink.  The
                   residual stream rides the population machinery
                   (resident ``(N, P)`` plane or host store) and is
                   checkpointed with the run.
    """

    kind: str = "int8"  # int8 | bf16 | topk
    # fraction of plane elements kept per client row under "topk"
    topk_frac: float = 0.01
    # stochastic-rounding stream seed — independent of FedConfig.seed
    # and keyed by absolute round, so kill/resume replays the identical
    # quantization noise and cohort-sharded runs agree with unsharded
    seed: int = 0


@dataclass(frozen=True)
class FedConfig:
    """Federated round configuration (paper §6.1 defaults)."""

    # any name in the algorithm registry (repro.core.registry) — builtins:
    # fedcm | fedavg | fedadam | scaffold | feddyn | mimelite | fedavgm |
    # fedadagrad | fedyogi | fedacg; resolved (and validated) by
    # get_algorithm at engine construction.  ``--list-algos`` on
    # launch/fed_train prints each spec's state planes + kernel routing.
    algo: str = "fedcm"
    num_clients: int = 100
    cohort_size: int = 10  # |S|
    local_steps: int = 10  # K
    alpha: float = 0.1  # FedCM / FedAdam server beta1-like; FedDyn reg strength reuses own field
    eta_l: float = 0.1
    eta_g: float = 1.0
    eta_l_decay: float = 0.998  # exponential decay per round (appendix C.2)
    weight_decay: float = 1e-3
    # FedAdam
    adam_beta2: float = 0.99
    adam_tau: float = 1e-2
    # FedDyn
    feddyn_alpha: float = 0.01
    # FedProx: proximal strength μ of the registered "fedprox" spec
    # (local direction v = g + μ·(x − x_t) — a pure c_x DirectionRow)
    fedprox_mu: float = 0.01
    # FedACG-style server acceleration: lookahead/momentum coefficient λ of
    # the registered "fedacg" spec (m' = λ·m + Δ_{t+1}; the server steps
    # along Δ_{t+1} + λ·m')
    acg_lambda: float = 0.85
    # participation model: "fixed" = exactly cohort_size w/o replacement,
    # "bernoulli" = each client independently with prob cohort_size/num_clients
    participation: str = "fixed"
    rounds: int = 100
    seed: int = 0
    # server momentum Δ_t storage/broadcast dtype — bf16 halves the extra
    # FedCM downlink (§4.2) and the per-local-step momentum gathers (§Perf C)
    momentum_dtype: str = "float32"
    # cohort-aggregation dtype: the Δ mean over the (pod, data) axes is an
    # all-reduce of a params-shaped tree — bf16 halves its bytes (production
    # FL systems quantize aggregation much harder than this)
    aggregate_dtype: str = "float32"
    # flat parameter plane (repro.core.flat): ravel params/momentum/client
    # state ONCE per run_rounds call and carry (P,)/(C,P)/(N,P) buffers
    # through the local-step scan, cohort vmap, aggregation, and server
    # update.  The tree path (False) is kept as the numerical oracle and
    # for tensor-sharded lowering (launch/fed_dryrun pins it off: a flat
    # concat of model-sharded leaves would force all-gathers).
    use_flat_plane: bool = True
    # the flat engine has one path: the fused Pallas kernels
    # (kernels/fed_direction, kernels/server_update), run by Mosaic on a
    # TPU and by the Pallas interpreter elsewhere.  True is the only
    # accepted value (FederatedEngine refuses False); the field goes when
    # the benchmark's traffic files stop naming it.
    use_fused_kernel: bool = True
    # async pipelined engine (engine.run_rounds_async): number of cohorts
    # in flight.  1 = the sync schedule (each cohort folds the round it
    # launches); D > 1 overlaps D cohorts — a fold is D−1 rounds stale.
    pipeline_depth: int = 1
    # rounds of momentum staleness the clients descend against (the
    # broadcast Δ_t / c is read from an S-deep delay line).  0 = current.
    staleness: int = 0
    # FedACG-style per-round-of-staleness discount γ: a fold that is
    # (pipeline_depth−1) rounds stale is weighted γ^(depth−1) — rides the
    # fused server kernel's SMEM coefficient row.  1.0 = no discount.
    staleness_discount: float = 1.0
    # cohort-parallel execution: number of devices to shard the client
    # axis over (engine builds a ("clients",) mesh over the first N
    # visible devices and runs the cohort via shard_map; the fold lowers
    # to a reduce-scatter/all-gather).  0 = single-device execution.
    # Requires use_flat_plane.  An explicit mesh can
    # instead be passed as FederatedEngine(..., cohort_mesh=...).
    cohort_shard: int = 0
    # ---- population store / streaming availability (million-client axis) --
    # Where per-client state planes (scaffold c_i, feddyn λ_i) live:
    #   "resident" — the stacked (N, P) device plane (the bitwise oracle),
    #   "host"     — a sparse host-memory store (repro.data.population);
    #                the engine gathers a (C, P) block on participation and
    #                scatters updated rows back after the fold, so device
    #                memory scales with the COHORT and host memory with the
    #                set of touched clients, never with N.  N=1e6 becomes a
    #                literal config value.  Requires use_flat_plane.
    population_store: str = "resident"
    # availability process driving the streaming cohort sampler:
    #   "uniform" — every client equally likely (the legacy draw, kept
    #               bitwise-identical to the pre-store sampler),
    #   "zipf"    — traffic skew w_i ∝ (i+1)^-zipf_exponent,
    #   "diurnal" — time-of-day sinusoid over the round counter; client i
    #               peaks at phase i/N of a diurnal_period-round "day".
    availability: str = "uniform"
    zipf_exponent: float = 1.1
    diurnal_period: float = 24.0  # rounds per simulated day
    diurnal_amplitude: float = 0.8  # 0 = uniform, →1 = full day/night swing
    # straggler model: each SELECTED client independently drops out of the
    # round with this probability (mask-only thinning after selection; a
    # fully-dropped cohort keeps its first client so n_active ≥ 1).
    dropout_rate: float = 0.0
    # bernoulli cohort capacity = mean + σ·sd tail bound.  5σ makes the
    # static pad overflow ~never (p < 3e-7); either way an overflow is now
    # COUNTED in RoundMetrics.n_clipped instead of silently truncated.
    bernoulli_capacity_sigma: float = 5.0
    # ---- fault tolerance ------------------------------------------------
    # fault injection model (None = no fault code traced; see FaultConfig)
    fault: Optional[FaultConfig] = None
    # minimum surviving cohort for the server fold to apply: when fewer
    # than max(1, min_quorum) clients survive drops + quarantine, the
    # round becomes a no-op — params/momentum carried unchanged, client
    # state writes suppressed, RoundMetrics.quorum_skipped = 1.  The
    # implicit floor of 1 is the empty-cohort guard (an all-zero weight
    # row used to 0/0-poison the masked mean with NaN).
    min_quorum: int = 0
    # let sample_cohort_ex produce an EMPTY cohort (bernoulli draw of 0 /
    # total dropout) instead of force-keeping one client.  Safe now that
    # empty rounds degrade to guarded no-ops; default off preserves the
    # legacy keep-first sampler bitwise.
    allow_empty_cohort: bool = False
    # ---- uplink compression --------------------------------------------
    # uplink compression model (None = no compression code traced; see
    # CompressionConfig).  Requires use_flat_plane — the transforms are
    # flat-plane ops; the tree path stays the uncompressed oracle.
    compression: Optional[CompressionConfig] = None
    # host-store loop double-buffering: prefetch the NEXT round's cohort
    # sample + host batch generation (and, optimistically, its store
    # gather) on a background thread while the current round runs on
    # device.  Bitwise-identical to the synchronous loop — overlapping
    # rows are re-gathered after the scatter they depend on.
    store_prefetch: bool = True


@dataclass(frozen=True)
class TrainConfig:
    """Centralized training driver config."""

    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    remat: str = "none"  # none | full | dots
    seed: int = 0


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------
ARCH_IDS = [
    "starcoder2-7b",
    "llama4-maverick-400b-a17b",
    "seamless-m4t-large-v2",
    "dbrx-132b",
    "zamba2-7b",
    "llama3.2-1b",
    "qwen3-14b",
    "gemma3-12b",
    "chameleon-34b",
    "mamba2-1.3b",
    "mellum2-12b-a2.5b",
]

_MODULE_FOR: Dict[str, str] = {
    "starcoder2-7b": "starcoder2_7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "dbrx-132b": "dbrx_132b",
    "zamba2-7b": "zamba2_7b",
    "llama3.2-1b": "llama3_2_1b",
    "qwen3-14b": "qwen3_14b",
    "gemma3-12b": "gemma3_12b",
    "chameleon-34b": "chameleon_34b",
    "mamba2-1.3b": "mamba2_1_3b",
    "mellum2-12b-a2.5b": "mellum2_12b_a2_5b",
}


def get_config(name: str) -> ModelConfig:
    key = name.replace("_", "-") if name not in _MODULE_FOR else name
    if key not in _MODULE_FOR:
        # allow passing module-style names too
        for k, mod in _MODULE_FOR.items():
            if mod == name:
                key = k
                break
    if key not in _MODULE_FOR:
        raise KeyError(f"unknown architecture {name!r}; known: {ARCH_IDS}")
    mod = importlib.import_module(f"repro.configs.{_MODULE_FOR[key]}")
    return mod.CONFIG


def list_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family variant for CPU smoke tests.

    ≤2 layers, d_model ≤ 512, ≤4 experts — per the assignment contract.
    """
    d_model = min(cfg.d_model, 256)
    n_heads = min(cfg.n_heads, 4)
    if n_heads > 0:
        head_dim = max(d_model // n_heads, 32)
        n_kv = min(cfg.n_kv_heads, n_heads)
        if n_heads % n_kv != 0:
            n_kv = 1
    else:  # attention-free (ssm)
        head_dim = None
        n_kv = 0
    updates = dict(
        name=cfg.name + "-reduced",
        n_layers=2,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=min(cfg.d_ff, 512) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        dtype="float32",
        param_dtype="float32",
    )
    if cfg.n_experts:
        updates["n_experts"] = min(cfg.n_experts, 4)
        updates["top_k"] = min(cfg.top_k, 2)
        updates["moe_every"] = min(cfg.moe_every, 2)
        if cfg.n_experts_held:  # keep a share: half of the routed experts
            updates["n_experts_held"] = min(cfg.n_experts_held, updates["n_experts"] // 2)
    if cfg.family in ("ssm", "hybrid"):
        updates["ssm_state"] = min(cfg.ssm_state, 16)
        updates["ssm_head_dim"] = 32
        updates["ssm_chunk"] = 16
        if cfg.family == "hybrid":
            updates["n_layers"] = 2
            updates["attn_every"] = 2  # layer 1 is the shared attention block
    if cfg.is_encoder_decoder:
        updates["n_encoder_layers"] = 2
    if cfg.sliding_window is not None:
        updates["sliding_window"] = min(cfg.sliding_window, 8)
    if cfg.local_global_pattern is not None:
        updates["local_global_pattern"] = (1, 1)
    return replace(cfg, **updates)


def to_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)
