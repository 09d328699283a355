"""Serving driver: batched prefill → decode with a KV/SSM cache.

A minimal continuous-batching-style server loop: a batch of prompts is
prefilled in one forward pass (emitting the cache), then tokens are decoded
step-by-step with the jitted serve step.  Greedy sampling (temperature 0)
by default; ``--temperature`` enables categorical sampling.

The decode loop itself is the reusable ``serve_loop`` consumed by the
fleet driver (``repro.fleet.driver``): it polls a ``params_provider``
BETWEEN decode steps and hot-swaps the served params at a step boundary,
so a checkpoint published mid-generation lands atomically — an in-flight
decode step always runs against exactly one complete version, never a
torn mix of two (the publisher's pointer protocol guarantees each loaded
version is complete; the step-boundary swap guarantees no step straddles
two).

``--ckpt DIR`` loads published params (``repro.fleet.publisher`` layout:
``LATEST.json`` + ``step_<v>.msgpack``) into the server instead of random
init — the params a ``fed_train --serve`` run publishes.  ``--follow``
keeps watching the directory and hot-swaps new versions as they publish.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-1.3b \
        --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, get_config, reduced
from repro.data.synthetic import make_synthetic_lm
from repro.models import build_model
from repro.utils.compile_cache import use_compile_cache


@dataclass
class ServeStats:
    """What the serving loop did — the fleet CI smoke asserts on these
    (and the driver folds them into the telemetry ``serve_summary`` row)."""

    steps: int = 0  # decode steps executed
    sessions: int = 0  # completed sessions (prefill→gen sequences)
    swaps: int = 0  # hot-swaps taken (any step boundary)
    swaps_mid_session: int = 0  # swaps taken while a session was decoding
    swap_steps: List[int] = field(default_factory=list)  # global step at swap
    versions: List[int] = field(default_factory=list)  # version per swap
    served_version: int = 0  # version of the params currently served
    t_active_s: float = 0.0  # wall time spent inside sessions


def serve_loop(
    params: Any,
    decode_step: Callable[[Any, Any, int], Any],
    *,
    begin_session: Optional[Callable[[Any, int], Any]] = None,
    end_session: Optional[Callable[[Any, Any], None]] = None,
    params_provider: Optional[Any] = None,
    steps_per_session: int,
    max_sessions: Optional[int] = 1,
    stop_event: Optional[threading.Event] = None,
    on_swap: Optional[Callable[[int, ServeStats], None]] = None,
    on_step: Optional[Callable[[ServeStats], None]] = None,
    idle_sleep_s: float = 0.0,
    step_sleep_s: float = 0.0,
    version: int = 0,
) -> Tuple[Any, ServeStats]:
    """Run serving sessions, hot-swapping params between decode steps.

    ``decode_step(params, state, i)`` advances one decode step;
    ``begin_session(params, s)`` builds a fresh session state (prefill);
    ``end_session(params, state)`` closes one (e.g. block_until_ready).
    ``params_provider.poll()`` — when given — is called before EVERY
    decode step and must return ``None`` (unchanged) or a complete
    ``(version, params, meta)``; the swap is a single reference
    assignment at the step boundary, so the ``decode_step`` call that
    follows sees the new version in full and the one that preceded it saw
    the old version in full: atomic under decode load by construction.

    Runs until ``max_sessions`` sessions completed (``None`` = forever) or
    ``stop_event`` is set (checked between steps, so a stop request never
    kills a decode step mid-flight).  Returns the final (possibly swapped)
    params and the stats."""
    stats = ServeStats(served_version=version)

    def _swap(step_in_session: int) -> None:
        nonlocal params
        if params_provider is None:
            return
        got = params_provider.poll()
        if got is None:
            return
        new_version, new_params, _meta = got
        params = new_params
        stats.served_version = new_version
        stats.swaps += 1
        if step_in_session > 0:
            stats.swaps_mid_session += 1
        stats.swap_steps.append(stats.steps)
        stats.versions.append(new_version)
        if on_swap is not None:
            on_swap(new_version, stats)

    while max_sessions is None or stats.sessions < max_sessions:
        if stop_event is not None and stop_event.is_set():
            break
        t0 = time.perf_counter()
        _swap(0)
        state = begin_session(params, stats.sessions) if begin_session else None
        for i in range(steps_per_session):
            if stop_event is not None and stop_event.is_set():
                break
            if i > 0:
                _swap(i)
            state = decode_step(params, state, i)
            stats.steps += 1
            if on_step is not None:
                on_step(stats)
            if step_sleep_s > 0:
                # paced decoding: keeps the session live across wall-clock
                # time (so publishes land MID-session — the under-load swap
                # path) and yields the core to the co-resident training scan
                time.sleep(step_sleep_s)
        else:
            if end_session is not None:
                end_session(params, state)
            stats.sessions += 1
        stats.t_active_s += time.perf_counter() - t0
        if idle_sleep_s > 0:
            # yield the core between sessions (the fleet driver shares the
            # host with the training scan; serving must not starve it)
            time.sleep(idle_sleep_s)
    return params, stats


def load_ckpt_params(path: str, template: Any, *, follow: bool = False):
    """Resolve ``--ckpt`` → ``(version, params, provider-or-None)``.

    ``path`` is a publisher directory (``LATEST.json`` pointer) or a
    single ``step_<v>.msgpack`` payload file from one."""
    from repro.fleet.publisher import ParamsWatch, load_published

    if os.path.isdir(path):
        watcher = ParamsWatch(path, template=template)
        try:
            got = watcher.poll()
        except KeyError as e:
            raise SystemExit(
                f"--ckpt {path}: published params do not match this serving "
                f"model's template ({e}) — the directory was published by a "
                "different model (e.g. a fed_train classifier run, not "
                f"--arch)"
            ) from e
        if got is None:
            raise FileNotFoundError(
                f"--ckpt {path}: no LATEST.json — nothing published yet"
            )
        version, params, _ = got
        return version, params, (watcher if follow else None)
    d, name = os.path.split(path)
    if not (name.startswith("step_") and name.endswith(".msgpack")):
        raise ValueError(
            f"--ckpt {path}: expected a publisher directory or a "
            "step_<version>.msgpack payload"
        )
    version = int(name[len("step_"):-len(".msgpack")])
    version, params, _ = load_published(d, template, version)
    return version, params, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-1b", choices=ARCH_IDS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default="",
                    help="serve published params (fleet publisher dir or "
                         "step_<v>.msgpack) instead of random init")
    ap.add_argument("--follow", action="store_true",
                    help="with --ckpt DIR: keep watching for new published "
                         "versions and hot-swap them between decode steps")
    ap.add_argument("--sessions", type=int, default=1,
                    help="prefill→decode sessions to run (continuous "
                         "serving = more than one)")
    args = ap.parse_args(argv)
    if args.follow and not args.ckpt:
        ap.error("--follow watches the --ckpt directory — add --ckpt DIR")
    use_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced(cfg)
    if cfg.is_encoder_decoder:
        return _serve_encdec(cfg, args)

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    version, provider = 0, None
    if args.ckpt:
        version, params, provider = load_ckpt_params(
            args.ckpt, params, follow=args.follow
        )
        print(f"serving published params: version {version} from {args.ckpt}")
    prompts = jnp.asarray(
        make_synthetic_lm(cfg.vocab_size, args.prompt_len, args.batch, seed=args.seed),
        jnp.int32,
    )
    max_len = args.prompt_len + args.gen

    # ---- prefill: run the prompt once, emitting per-layer K/V / SSM state
    prefill = jax.jit(lambda p, t: model.apply(p, t, return_cache=True))
    decode = jax.jit(model.decode_step, donate_argnums=(2,))

    def merge(dst, src):
        if dst.ndim >= 3 and src.ndim == dst.ndim and dst.shape[2] >= src.shape[2] and dst.shape[:2] == src.shape[:2]:
            return jax.lax.dynamic_update_slice(
                dst, src.astype(dst.dtype), (0,) * dst.ndim
            )
        return src.astype(dst.dtype)  # ssm/conv states replace wholesale

    def sample(lg, key):
        if args.temperature <= 0:
            return jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return jax.random.categorical(key, lg[:, -1] / args.temperature)[:, None].astype(jnp.int32)

    timings = {"prefill": 0.0, "decode": 0.0}
    last = {"gen": None}

    def begin_session(p, s):
        t0 = time.time()
        logits, pre_cache, _ = prefill(p, prompts)
        jax.block_until_ready(logits)
        timings["prefill"] += time.time() - t0
        # copy the prefill cache into a max_len decode buffer
        cache = model.init_cache(p, args.batch, max_len)
        cache = jax.tree_util.tree_map(merge, cache, pre_cache)
        rng = jax.random.PRNGKey(args.seed + 1 + s)
        tok = sample(logits, rng)
        return {"tok": tok, "cache": cache, "rng": rng,
                "out": [tok], "t0": time.time()}

    def decode_step(p, st, i):
        pos = jnp.int32(args.prompt_len + i)
        logits, cache = decode(p, st["tok"], st["cache"], pos)
        rng, key = jax.random.split(st["rng"])
        tok = sample(logits, key)
        st["out"].append(tok)
        return {**st, "tok": tok, "cache": cache, "rng": rng}

    def end_session(p, st):
        jax.block_until_ready(st["tok"])
        timings["decode"] += time.time() - st["t0"]
        last["gen"] = jnp.concatenate(st["out"], axis=1)

    _, stats = serve_loop(
        params, decode_step,
        begin_session=begin_session, end_session=end_session,
        params_provider=provider,
        steps_per_session=args.gen - 1, max_sessions=args.sessions,
        version=version,
    )

    gen = last["gen"]
    n = max(stats.sessions, 1)
    t_prefill, t_decode = timings["prefill"] / n, timings["decode"] / n
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen} sessions={stats.sessions}")
    print(f"prefill: {t_prefill*1e3:.1f} ms  ({args.batch*args.prompt_len/max(t_prefill,1e-9):.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms  ({args.batch*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    if provider is not None or stats.swaps:
        print(f"hot-swaps: {stats.swaps} (served version {stats.served_version})")
    print("sample generations (first 16 tokens):")
    for b in range(min(args.batch, 4)):
        print("  ", np.asarray(gen[b, :16]).tolist())
    return 0


def _serve_encdec(cfg, args) -> int:
    """Seamless-style: encode source frames once, decode target tokens."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        version, params, _ = load_ckpt_params(args.ckpt, params)
        print(f"serving published params: version {version} from {args.ckpt}")
    from repro.models import encdec

    src = jax.random.normal(
        jax.random.PRNGKey(args.seed + 2), (args.batch, args.prompt_len, cfg.d_model)
    )
    t0 = time.time()
    enc_out = jax.jit(lambda p, s: encdec.encode(p, s, cfg=cfg))(params, src)
    cache = encdec.init_decode_cache(params, cfg, args.batch, args.gen, enc_out)
    jax.block_until_ready(enc_out)
    t_enc = time.time() - t0

    decode = jax.jit(
        lambda p, t, c, pos: encdec.decode_step(p, t, c, pos, cfg=cfg),
        donate_argnums=(2,),
    )
    tok = jnp.zeros((args.batch, 1), jnp.int32)  # BOS
    outs = []
    t0 = time.time()
    for i in range(args.gen):
        logits, cache = decode(params, tok, cache, jnp.int32(i))
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        outs.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.time() - t0
    gen = jnp.concatenate(outs, axis=1)
    print(f"encdec arch={cfg.name}: encode {t_enc*1e3:.1f}ms, "
          f"decode {t_dec*1e3:.1f}ms ({args.batch*args.gen/max(t_dec,1e-9):.0f} tok/s)")
    print("sample:", np.asarray(gen[0, :16]).tolist())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
