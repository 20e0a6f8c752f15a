"""Serving launcher: batched decode for LM archs / scoring for BERT4Rec /
subgraph-match query serving through the repro.api session layer.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch bert4rec --shape serve_p99
  PYTHONPATH=src python -m repro.launch.serve --arch match --dataset yeast \\
      --scale 0.05 --n-queries 32
  PYTHONPATH=src python -m repro.launch.serve --arch match --serve-loop \\
      --dataset yeast --qps 50 --n-queries 64

The default --arch match mode is a closed-loop batch: all queries exist up
front and match_many drains them as one superbatch. --serve-loop instead
runs the always-on MatchService open loop: requests arrive on a seeded
Poisson schedule at --qps (independent of completions), pass through
admission control (bounded inbox + deadline-budget shedding), and are
bucketed/dispatched deadline-aware. See docs/serving.md.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.distributed import policy
from repro.distributed.sharding import sharding_ctx
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.api import build_bundle


def serve_match(args) -> None:
    """Match-query serving: one Dataset preprocessed at startup, a Matcher
    with a warm plan cache serving the query stream (each distinct query
    shape compiles once; repeats are cache hits)."""
    from repro.api import Dataset, MatchOptions, Matcher

    dataset = Dataset.synthetic(args.dataset, scale=args.scale)
    matcher = Matcher(dataset, MatchOptions(engine=args.engine,
                                            limit=args.limit))
    queries = [dataset.random_query(args.query_size, seed=s)
               for s in range(args.n_queries)]
    t0 = time.perf_counter()
    outs = matcher.match_many(queries)
    dt = time.perf_counter() - t0
    total = sum(o.count for o in outs)
    info = matcher.cache_info()
    print(f"served {len(outs)} queries against {dataset!r} in {dt:.2f}s "
          f"({len(outs) / dt:.1f} qps) — {total} embeddings")
    print(f"engines: { {e: sum(1 for o in outs if o.engine == e) for e in ('ref', 'vector')} } "
          f"plan cache: hits={info.hits} misses={info.misses}")


def serve_match_loop(args) -> None:
    """Open-loop match serving through the always-on MatchService:
    requests arrive on a seeded Poisson schedule at --qps whether or not
    earlier ones finished, so under overload the admission controller
    sheds with a typed Overloaded ticket instead of queueing without
    bound. Prints the open-loop summary (sustained qps, p50/p99 latency,
    shed rate) plus service counters. `--workers N` executes buckets on N
    out-of-process workers (crash/hang isolation — a wedged or killed
    worker costs one bucket retry, not the service) and reports the pool's
    lifecycle counters alongside the service stats."""
    from repro.api import Dataset, MatchOptions
    from repro.runtime.service import (MatchService, ServiceConfig,
                                       arrival_schedule, open_loop)

    dataset = Dataset.synthetic(args.dataset, scale=args.scale)
    queries = [dataset.random_query(args.query_size, seed=s)
               for s in range(min(args.n_queries, 16))]
    svc = MatchService(dataset, config=ServiceConfig(
        inbox_capacity=max(64, args.n_queries), workers=args.workers),
        options=MatchOptions(engine=args.engine, limit=args.limit))
    try:
        # warm the plan caches so the measured loop isn't dominated by
        # compiles (with a pool this warms the workers' caches too)
        for q in queries:
            svc.submit(q, limit=args.limit, force=True)
        svc.drain()
        svc.reset_stats()
        workload = [dict(query=queries[i % len(queries)], limit=args.limit)
                    for i in range(args.n_queries)]
        schedule = arrival_schedule(args.n_queries, args.qps, seed=args.seed)
        s = open_loop(svc, workload, schedule)
        print(f"open loop vs {dataset!r}: offered {s['offered']} @ "
              f"{args.qps:.1f} qps → completed {s['completed']} "
              f"shed {s['shed']} failed {s['failed']} "
              f"(sustained {s['qps_sustained']:.1f} qps)")
        print(f"latency p50 {s['p50_s'] * 1e3:.1f}ms "
              f"p99 {s['p99_s'] * 1e3:.1f}ms "
              f"shed_rate {s['shed_rate']:.3f} makespan {s['makespan_s']:.2f}s")
        print(f"service stats: {svc.stats}")
        if svc.pool is not None:
            print(f"worker pool ({svc.pool.size} workers): {svc.pool.stats}")
    finally:
        svc.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--shape", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=16)
    # --arch match (subgraph-match serving) options
    ap.add_argument("--dataset", default="yeast")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--n-queries", type=int, default=32)
    ap.add_argument("--query-size", type=int, default=6)
    ap.add_argument("--limit", type=int, default=100_000)
    ap.add_argument("--engine", default="auto",
                    choices=["ref", "vector", "auto"])
    ap.add_argument("--serve-loop", action="store_true",
                    help="open-loop MatchService mode (--arch match only): "
                         "Poisson arrivals at --qps through admission "
                         "control instead of a single closed-loop batch")
    ap.add_argument("--qps", type=float, default=50.0,
                    help="offered arrival rate for --serve-loop")
    ap.add_argument("--workers", type=int, default=0,
                    help="out-of-process executor workers for --serve-loop "
                         "(0 = inline execution in the service process)")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-schedule seed for --serve-loop")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "match":
        if args.serve_loop:
            serve_match_loop(args)
        else:
            serve_match(args)
        return

    mesh = make_local_mesh()
    bundle = build_bundle(args.arch, reduced=True)
    params = bundle.init_fn(jax.random.PRNGKey(0))

    if bundle.family == "recsys":
        shape = args.shape or "serve_p99"
        rules = policy.activation_rules(bundle.cfg, mesh, "serve",
                                        batch=args.batch)
        with sharding_ctx(mesh, rules):
            serve = jax.jit(bundle.steps["serve"])
            batch = bundle.make_inputs(shape)
            vals, idx = serve(params, batch)
        print(f"scored batch {batch['ids'].shape} → top10 {idx.shape}")
        return

    # LM decode loop
    rules = policy.activation_rules(bundle.cfg, mesh, "decode",
                                    batch=args.batch)
    max_len = args.tokens + 8
    from repro.nn import transformer as T
    caches = T.lm_init_caches(bundle.cfg, args.batch, max_len,
                              dtype=jnp.float32)
    lengths = jnp.zeros((args.batch,), jnp.int32)
    token = jnp.ones((args.batch,), jnp.int32)
    with sharding_ctx(mesh, rules):
        step = jax.jit(bundle.steps["decode"], donate_argnums=(1,))
        t0 = time.perf_counter()
        out = []
        for _ in range(args.tokens):
            logits, caches = step(params, caches,
                                  {"token": token, "lengths": lengths})
            token = jnp.argmax(logits, -1).astype(jnp.int32)
            lengths = lengths + 1
            out.append(token)
        jax.block_until_ready(out[-1])
    dt = time.perf_counter() - t0
    print(f"decoded {args.tokens} tokens × batch {args.batch} in {dt:.2f}s "
          f"({args.tokens * args.batch / dt:.1f} tok/s)")
    print("sample:", [int(t[0]) for t in out][:10])


if __name__ == "__main__":
    main()
