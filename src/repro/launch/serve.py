"""Serving launcher: run the paged serving engine with batched requests.

This is the end-to-end serving driver: it builds a reduced model of the
selected architecture, registers aLoRA (and optionally vanilla-LoRA
baseline) adapters, replays a batch of multi-turn base→adapter requests
through the engine, and prints per-stage latency + cache-hit metrics.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-3.2-8b \
      --requests 8 --prompt-len 128

``--scale chip`` serves one chip's share of the architecture's stated
deployment at its published widths (``configs.get_chip_share``; on a TPU
v5e) with the KV pool sized by ``CHIP_ENGINE_CFG``, instead of the
reduced CPU model.  Compiled programs persist in JAX's compile cache
(``launch/compile_cache.py``).

``--replicas N`` scales the same workload out over N in-process engine
replicas behind the cache-affinity router (``serving/router.py``) —
each replica gets its own pools, prefix cache and adapter slots, and
every submission is placed by aLoRA-aligned prefix locality.
``--route {affinity,round_robin}`` selects the placement policy
(round_robin is the blind baseline); with ``--replicas 1`` the router
tier is skipped entirely and the engine is driven directly.

``--trace-out FILE`` exports the aLoRA run's trace rings (every
replica's, plus the router's, when a fleet ran) as a Perfetto timeline
— load it at https://ui.perfetto.dev to see submit/retire overlap and
per-request queue→prefill→decode lifecycles.  ``--metrics-out FILE``
writes the same run's counters as a Prometheus text snapshot.  Schema:
``docs/observability.md``.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_chip_share, get_reduced
from repro.core.alora import (PAPER_ALORA_RANK, PAPER_LORA_RANK,
                              AdapterSpec, init_adapter_weights)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.obs import prometheus_text, write_perfetto
from repro.serving import Engine, EngineConfig, fmt_speedups, speedup_table
from repro.serving import pipelines as P
from repro.serving.router import POLICIES, Router


# One v5e chip (15.75 GiB usable) serving granite-3.2-8b's 20-layer share
# (8.4 GB of bf16 weights): 2,048 blocks x 16 tokens of bf16 K/V over 20
# layers is 2.5 GiB, and a 128-token step at a 2k context needs about
# 1.4 GiB of temporaries on the jnp attention path.
CHIP_ENGINE_CFG = EngineConfig(num_blocks=2048, max_running=8,
                               max_batched_tokens=128)


# Served adapters draw B at 8x ``init_adapter_weights``' scale (a power
# of two, exact in bf16).  At the init scale an adapter moved
# granite-3.2-8b's logits at most 0.58 std on a v5e, so the tokens of a
# dropped adapter sat within 0.27 std of the sound ones, next to bf16
# rounding's 0.12; at 8x a dropped adapter changed 106 of 128 tokens.
ADAPTER_GAIN = 8


def build_adapters(cfg, kind: str, n_adapters: int):
    """``[(spec, weights)]`` of ``n_adapters`` seeded adapters named
    ``intrinsic{i}``: aLoRA (rank 32, invoked by tokens 3, 4, 5) or
    vanilla LoRA (rank 8) as in the paper's setup, B scaled by
    ``ADAPTER_GAIN``."""
    rank = PAPER_ALORA_RANK if kind == "alora" else PAPER_LORA_RANK
    inv = tuple(range(3, 6)) if kind == "alora" else None

    def weights(i):
        w = init_adapter_weights(jax.random.key(100 + i), cfg, rank)
        return {seg: {k: v * ADAPTER_GAIN if k.startswith("b") else v
                      for k, v in ws.items()} for seg, ws in w.items()}

    return [(AdapterSpec(f"intrinsic{i}", rank=rank, invocation_tokens=inv),
             weights(i)) for i in range(n_adapters)]


def build_engine(cfg, params, kind: str, n_adapters: int = 1,
                 engine_cfg: EngineConfig = EngineConfig(),
                 replicas: int = 1, route: str = "affinity"):
    """One engine, or — with ``replicas > 1`` — a Router over N
    identically-built replicas (drop-in for the pipeline drivers)."""
    adapters = build_adapters(cfg, kind, n_adapters)

    def mk() -> Engine:
        return Engine(cfg, params, adapters=adapters,
                      engine_cfg=engine_cfg)

    if replicas <= 1:
        return mk()
    return Router([mk() for _ in range(replicas)], policy=route)


def collect_tracers(eng):
    """Every tracer a serving tier carries: per-replica engine tracers
    plus the router's own when a fleet ran."""
    if isinstance(eng, Router):
        return [e.tracer for e in eng.replicas] + [eng.tracer]
    return [eng.tracer]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3.2-8b")
    ap.add_argument("--scale", choices=("reduced", "chip"),
                    default="reduced",
                    help="reduced: tiny float32 model (CPU); chip: one "
                         "chip's share at published widths (TPU)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--eval-len", type=int, default=16)
    ap.add_argument("--adapters", type=int, default=1)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the affinity router "
                         "(1 = no router tier)")
    ap.add_argument("--route", choices=POLICIES, default="affinity",
                    help="placement policy with --replicas > 1")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the aLoRA run's Perfetto timeline JSON "
                         "here (load at https://ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write the aLoRA run's counters here in the "
                         "Prometheus text exposition format")
    args = ap.parse_args()

    enable_compile_cache()
    if args.scale == "chip":
        cfg, engine_cfg = get_chip_share(args.arch), CHIP_ENGINE_CFG
    else:
        cfg, engine_cfg = get_reduced(args.arch), EngineConfig()
    tier = f" x{args.replicas} replicas ({args.route})" \
        if args.replicas > 1 else ""
    print(f"serving {args.scale} {cfg.name} ({cfg.arch_type}){tier}")
    params = init_params(jax.random.key(0), cfg)

    results = {}
    for kind in ("lora", "alora"):
        # warmup pass compiles all jit buckets, then a fresh engine
        # measures with cold caches but warm code
        for seed in (123, 0):
            eng = build_engine(cfg, params, kind, args.adapters,
                               engine_cfg=engine_cfg,
                               replicas=args.replicas, route=args.route)
            names = [f"intrinsic{i}" for i in range(args.adapters)]
            res = P.base_adapter(
                eng, adapter_names=names, prompt_len=args.prompt_len,
                gen_len=args.gen_len, eval_len=args.eval_len,
                batch=args.requests, feed_back_to_base=True, seed=seed)
        results[kind] = (eng, res)
        for stage in ("base", "eval", "final"):
            m = res.stage_metrics(eng, stage)
            print(f"  {kind:5s} {stage:5s} e2e={m.means['e2e']:.3f}s "
                  f"ttft={m.means['ttft']:.4f}s "
                  f"prefill={m.means['prefill']:.4f}s "
                  f"decode={m.means['decode']:.3f}s "
                  f"hit={m.means['cache_hit_frac']:.2f}")
        if isinstance(eng, Router):
            per = [sum(1 for p in eng.placements if p.replica == i)
                   for i in range(len(eng.replicas))]
            print(f"  {kind:5s} fleet hit={eng.kv_hit_rate():.2f} "
                  f"placements/replica={per}")

    sp = speedup_table(results["lora"][1].stage_metrics(
        results["lora"][0], "eval"),
        results["alora"][1].stage_metrics(results["alora"][0], "eval"))
    print("adapter-evaluation speedups (LoRA baseline / aLoRA):",
          fmt_speedups(sp))

    if args.trace_out or args.metrics_out:
        trs = collect_tracers(results["alora"][0])
        if args.trace_out:
            write_perfetto(args.trace_out, trs)
            print(f"wrote Perfetto timeline -> {args.trace_out} "
                  "(load at https://ui.perfetto.dev)")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(prometheus_text(trs))
            print(f"wrote Prometheus counters -> {args.metrics_out}")


if __name__ == "__main__":
    main()
