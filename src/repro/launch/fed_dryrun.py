import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""FedCM round-step dry-run — §Perf hillclimb C (the paper's technique).

Lowers ONE full federated round (Algorithm 2) of a llama3-family LM on the
production mesh: cohort-parallel over the "data" axis, each client's model
tensor-sharded over "model", FSDP parameter storage.  The broadcast of
(x_t, Δ_t) and the Δ-aggregation — the paper's server/client messages —
become XLA collectives whose bytes this dry-run measures.

A fixed 4-layer depth keeps the per-layer compute small so the ROUND
structure (momentum gathers, delta reduction, server update) dominates the
measurement — that structure is what FedCM adds over FedAvg and what the
hillclimb optimizes.  All scans (K local steps, layers, cohort vmap) are
unrolled for honest cost analysis.

    PYTHONPATH=src python -m repro.launch.fed_dryrun [--algo fedavg]
        [--momentum-dtype bfloat16] [--cohort 16] [--k 2] [--variant tag]
"""
import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import FedConfig, get_config
from repro.core.algorithms import get_algorithm, server_init
from repro.core.engine import FederatedEngine, FedState
from repro.launch.hlo_stats import collective_stats, op_census
from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh, n_chips
from repro.launch.steps import _ns
from repro.models import build_model
from repro.sharding.rules import param_specs
from repro.utils.compat import set_mesh

ARTIFACT_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun_fed"

N_LAYERS = 4
BATCH = 8
SEQ = 1024


def _tensor_sharded_reason(spec_tree) -> "str | None":
    """Why the flat plane cannot serve this lowering, or None if it can.

    The flat engine's single (P,) concatenate is only free when every leaf
    is replicated along non-cohort axes; a leaf partitioned over the
    tensor-parallel "model" axis would have to be all-gathered into the
    plane every round.
    """
    leaves = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P)
    )
    for spec in leaves:
        for part in spec:
            names = part if isinstance(part, (tuple, list)) else (part,)
            if "model" in names:
                return ("params are tensor-sharded over the 'model' axis — "
                        "a flat (P,) concatenate would all-gather them; "
                        "using the per-leaf tree path")
    return None


def build_and_lower(
    mesh,
    *,
    algo: str = "fedcm",
    cohort: int = 16,
    local_steps: int = 2,
    momentum_dtype: str = "float32",
    param_dtype: str = "float32",
    aggregate_dtype: str = "float32",
):
    base = get_config("llama3.2-1b")
    cfg = dataclasses.replace(base, n_layers=N_LAYERS, name="llama3-fedround",
                              param_dtype=param_dtype)
    model = build_model(cfg)

    def loss_fn(params, batch):
        loss, _ = model.loss_fn(params, batch, scan_unroll=64)
        return loss

    p_sds = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    # flat plane vs tree path is decided by the LOWERING, not hard-coded:
    # the flat engine concatenates every leaf into one (P,) buffer, which
    # for tensor-sharded ("model"-axis) leaves would force all-gathers of
    # the sharded dimensions — so only then fall back to the per-leaf tree
    # path, and say so.  The fallback is PER-AXIS: losing the flat plane
    # to "model"-sharded params does NOT surrender cohort parallelism —
    # the tree path keeps the client axis sharded over "data" via the
    # engine's cohort-axis sharding constraints (client_sharding below)
    # plus the batch in_shardings.  The flat case lowers the engine's one
    # path, the Pallas kernels, which take their interpreter form on a
    # host without a TPU.
    probe_specs = param_specs(p_sds, cfg, mesh)
    flat_fallback_reason = _tensor_sharded_reason(probe_specs)
    use_flat = flat_fallback_reason is None
    if not use_flat:
        print(f"fed_dryrun: use_flat_plane=False ({flat_fallback_reason}; "
              f"cohort axis stays sharded over 'data')")

    fed = FedConfig(
        algo=algo, num_clients=4096, cohort_size=cohort, local_steps=local_steps,
        alpha=0.1, eta_l=0.05, eta_g=1.0, participation="fixed",
        weight_decay=1e-4, momentum_dtype=momentum_dtype,
        aggregate_dtype=aggregate_dtype,
        use_flat_plane=use_flat,
    )
    eng = FederatedEngine(
        fed, loss_fn,
        # cohort-axis sharding survives the flat-plane fallback: pin the
        # leading axis of every cohort-stacked array to the "data" axis
        client_sharding=_ns(mesh, P("data")),
    )
    eng.analysis_unroll = True
    pd = jnp.dtype(param_dtype)
    p_sds = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, pd)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, p_sds
    )
    # state planes derive from the registered spec's flags — a spec without
    # a second moment never allocates (or shards) the extra plane
    algo_spec = get_algorithm(algo)
    srv_sds = jax.eval_shape(lambda: server_init(
        p_sds, momentum_dtype,
        needs_second_moment=algo_spec.needs_second_moment))
    state_sds = FedState(
        params=p_sds, server=srv_sds, client_states=None,
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    batches_sds = {
        "tokens": jax.ShapeDtypeStruct((cohort, local_steps, BATCH, SEQ), jnp.int32),
        "labels": jax.ShapeDtypeStruct((cohort, local_steps, BATCH, SEQ), jnp.int32),
    }
    ids_sds = jax.ShapeDtypeStruct((cohort,), jnp.int32)
    mask_sds = jax.ShapeDtypeStruct((cohort,), jnp.bool_)
    full_sds = jax.tree_util.tree_map(lambda b: jax.ShapeDtypeStruct(
        (b.shape[0], *b.shape[2:]), b.dtype), batches_sds)

    p_spec = param_specs(p_sds, cfg, mesh)
    srv_spec = type(srv_sds)(
        momentum=p_spec,
        second_moment=p_spec if srv_sds.second_moment is not None else None,
        round=P(),
    )
    state_spec = FedState(params=p_spec, server=srv_spec, client_states=None, rng=P())
    batch_spec = jax.tree_util.tree_map(
        lambda _: P("data", None, None, None), batches_sds
    )
    full_spec = jax.tree_util.tree_map(lambda _: P("data", None, None), full_sds)

    metrics_spec = jax.tree_util.tree_map(lambda _: P(), {
        "loss": 0, "n_active": 0, "delta_norm": 0, "momentum_norm": 0,
        "eta_l": 0, "bytes_down": 0, "bytes_up": 0, "n_clipped": 0,
        "n_dropped": 0, "n_quarantined": 0, "n_retries": 0,
        "quorum_skipped": 0})
    from repro.core.engine import RoundMetrics
    fn = jax.jit(
        eng._round_step_impl,
        in_shardings=(
            _ns(mesh, state_spec), _ns(mesh, batch_spec),
            _ns(mesh, P()), _ns(mesh, P()), _ns(mesh, full_spec),
        ),
        # FSDP out_shardings: the cohort-mean Δ aggregation then lowers to
        # reduce-scatter instead of all-reduce (hillclimb C iteration 2)
        out_shardings=(
            _ns(mesh, state_spec),
            _ns(mesh, RoundMetrics(**metrics_spec)),
        ),
        donate_argnums=(0,),
    )
    with set_mesh(mesh):
        lowered = fn.lower(state_sds, batches_sds, ids_sds, mask_sds, full_sds)
        compiled = lowered.compile()
    return compiled, cfg, fed, flat_fallback_reason


def run(variant: str, *, algo="fedcm", cohort=16, local_steps=2,
        momentum_dtype="float32", param_dtype="float32",
        aggregate_dtype="float32", multi_pod=False, quiet=False, save=True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    compiled, cfg, fed, flat_reason = build_and_lower(
        mesh, algo=algo, cohort=cohort, local_steps=local_steps,
        momentum_dtype=momentum_dtype, param_dtype=param_dtype,
        aggregate_dtype=aggregate_dtype,
    )
    t1 = time.time()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll_bytes = float(coll["total"]["bytes"])
    result = {
        "variant": variant,
        "algo": algo,
        "cohort": cohort,
        "local_steps": local_steps,
        "momentum_dtype": momentum_dtype,
        "param_dtype": param_dtype,
        "mesh": "multi_pod_2x16x16" if multi_pod else "single_pod_16x16",
        "chips": n_chips(mesh),
        # the RESOLVED mesh + which axis carries the cohort: the flat-plane
        # fallback is per-axis (tensor-sharded params disable only the
        # (P,) plane; cohort parallelism stays on "data")
        "resolved_mesh": {
            "axes": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
        },
        "cohort_axis": "data",
        "cohort_parallelism": "gspmd-constraint",
        "use_flat_plane": fed.use_flat_plane,
        "flat_fallback_reason": flat_reason,
        "compile_seconds": round(t1 - t0, 2),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_bytes,
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        "memory_temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0) or 0),
        "roofline": {
            "compute_s": flops / PEAK_FLOPS_BF16,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": coll_bytes / ICI_BW,
        },
    }
    if save:
        ARTIFACT_DIR.mkdir(parents=True, exist_ok=True)
        (ARTIFACT_DIR / f"fedround_{variant}.json").write_text(json.dumps(result, indent=1))
    if not quiet:
        r = result["roofline"]
        print(f"== fed round [{variant}] algo={algo} cohort={cohort} K={local_steps} "
              f"mdtype={momentum_dtype} ==")
        print(f"  compile {result['compile_seconds']}s  temp={result['memory_temp_bytes']/2**30:.2f}GiB")
        print(f"  FLOPs={flops:.3e} bytes={bytes_acc:.3e} coll={coll_bytes:.3e}")
        print(f"  roofline: compute={r['compute_s']*1e3:.2f}ms memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms")
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--algo", default="fedcm")
    ap.add_argument("--cohort", type=int, default=16)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--momentum-dtype", default="float32")
    ap.add_argument("--param-dtype", default="float32")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--variant", default="baseline")
    args = ap.parse_args()
    run(args.variant, algo=args.algo, cohort=args.cohort, local_steps=args.k,
        momentum_dtype=args.momentum_dtype, param_dtype=args.param_dtype,
        multi_pod=args.multi_pod)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
