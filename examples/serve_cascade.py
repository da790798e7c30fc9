"""Serving example: a MIXED request stream ("does this frame contain
a?" / "...contain b?") over a resident frame corpus, served by the
shard-aware AsyncCascadeService (DESIGN.md §10): requests hash-route to
per-shard device queues, a deadline wheel flushes bucketed batches,
labels commit to shard-owned virtual columns (re-asked frames answer
with zero model invocations), and pooled pyramid levels are shared
across concepts through the cross-query representation cache.

  PYTHONPATH=src python examples/serve_cascade.py [--requests 256]
      [--shards 4] [--repeat 0.4] [--sync] [--host]

``--sync`` falls back to the synchronous-polling CascadeService
(serve/batcher.py) — the pre-§10 serving path, kept as the baseline
benchmarks/bench_serve.py prices the async subsystem against.
``--host`` drives the async service with the wall-clock event host
(serve/host.py, DESIGN.md §12.1): a timer-parked daemon thread fires
deadline flushes autonomously, so the client never calls ``poll()``.
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import TahomaCNNConfig  # noqa: E402
from repro.core.executor import calibrate_capacity  # noqa: E402
from repro.core.pipeline import build_cascade_service, train_cnn  # noqa: E402
from repro.core.transforms import Representation, apply_transform  # noqa: E402
from repro.data.synthetic import DEFAULT_PREDICATES, make_corpus  # noqa: E402
from repro.engine.scan import CompiledCascade  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.cnn import cnn_predict_proba  # noqa: E402
from repro.serve.batcher import Request  # noqa: E402


def build_cascade(spec, batch_size: int, *, hw: int = 32, steps: int = 150,
                  n_train: int = 300):
    """Train a 2-level cascade (small gray@16 -> full rgb@hw) for one
    predicate and package it as a CompiledCascade."""
    x, y = make_corpus(spec, n_train + 130, hw=hw, seed=0)
    tr_x, tr_y = x[:n_train], y[:n_train]
    rep_fast = Representation(16, "gray")
    rep_full = Representation(hw, "rgb")
    fast_arch = TahomaCNNConfig(1, 8, 16, input_hw=16, input_channels=1)
    full_arch = TahomaCNNConfig(2, 16, 32, input_hw=hw, input_channels=3)
    p_fast = train_cnn(fast_arch, np.asarray(
        apply_transform(jnp.asarray(tr_x), rep_fast)), tr_y, steps=steps)
    p_full = train_cnn(full_arch, np.asarray(
        apply_transform(jnp.asarray(tr_x), rep_full)), tr_y,
        steps=steps + 50)
    # calibrate level-2 capacity from the observed uncertain fraction
    # (a sync-batcher knob: the async service runs full-width levels)
    s = np.asarray(cnn_predict_proba(p_fast, apply_transform(
        jnp.asarray(x[n_train:]), rep_fast)))
    unc = float(((s > 0.2) & (s < 0.8)).mean())
    cap = calibrate_capacity(unc, batch_size)
    print(f"  {spec.name}: uncertain fraction {unc:.2f} -> "
          f"level-2 capacity {cap}")
    return CompiledCascade(
        concept=spec.name, cascade_id=("serve-2level", spec.name),
        reps=[rep_fast, rep_full],
        model_fns=[lambda z, p=p_fast: cnn_predict_proba(p, z),
                   lambda z, p=p_full: cnn_predict_proba(p, z)],
        thresholds=[(0.2, 0.8), (None, None)], capacities=[cap])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--shards", type=int, default=None,
                    help="shard-queue count (default: one per device)")
    ap.add_argument("--repeat", type=float, default=0.4,
                    help="fraction of requests re-asking an earlier frame")
    ap.add_argument("--pace", type=float, default=0.002,
                    help="inter-arrival gap in seconds (0 = burst); a "
                         "paced stream lets deadlines fire and deliveries "
                         "land mid-stream, so re-asked frames hit the "
                         "virtual columns")
    ap.add_argument("--sync", action="store_true",
                    help="legacy synchronous batcher (serve/batcher.py)")
    ap.add_argument("--host", action="store_true",
                    help="drive the async service with the wall-clock "
                         "event host (no caller poll())")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test scale (CI)")
    args = ap.parse_args()
    if args.tiny:
        args.requests = min(args.requests, 48)
        args.batch_size = min(args.batch_size, 16)
    steps = 40 if args.tiny else 150

    specs = (DEFAULT_PREDICATES[1], DEFAULT_PREDICATES[4])
    print("training one 2-level cascade per predicate...")
    cascades = {s.name: build_cascade(s, args.batch_size, steps=steps)
                for s in specs}

    # resident candidate corpus + ground truth per concept
    n_corpus = max(args.requests, 64)
    frames = {s.name: make_corpus(s, n_corpus, hw=32, seed=9)
              for s in specs}
    corpus = np.concatenate([frames[s.name][0] for s in specs])
    offset = {s.name: i * n_corpus for i, s in enumerate(specs)}

    mode = "sync" if args.sync else "async"
    service = build_cascade_service(
        corpus, cascades, mode=mode, shards=args.shards,
        batch_size=args.batch_size, max_wait_s=0.005)
    print(f"serving mode: {mode}"
          + ("" if args.sync else
             f"  ({service.n_shards} shard queues over "
             f"{len(set(service.devices))} devices)"))
    if mode == "async":
        n = service.warmup()      # no compile stalls under live traffic
        print(f"warmed {n} executables")
    host = None
    if args.host and mode == "async":
        from repro.serve import EventHost
        host = EventHost(service).start()
        print("event host started (deadlines fire without caller poll)")

    # mixed stream: each request asks about ONE predicate's concept;
    # a --repeat fraction re-asks an already-served frame (interactive
    # sessions revisit hot frames — the cross-query reuse scenario)
    rng = np.random.default_rng(13)
    results = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        spec = specs[i % len(specs)]
        fresh = i < 8 or rng.uniform() >= args.repeat
        j = (i if fresh else int(rng.integers(0, i))) // len(specs)
        row = offset[spec.name] + j
        r = Request(i, row if mode == "async"
                    else jnp.asarray(corpus[row]))
        (host or service).submit(spec.name, r)
        results.append((spec.name, j, r))
        if host is None:
            service.poll()
        if args.pace:
            time.sleep(args.pace)
    if host is not None:
        host.wait_idle(60.0)      # event-driven: no poll, no drain
        host.stop()
    else:
        service.drain()
    dt = time.perf_counter() - t0

    lat = np.array(service.latencies()) * 1e3
    print(f"\nserved {args.requests} mixed requests in {dt:.2f}s "
          f"({args.requests / dt:.0f} img/s)")
    for c in service.concepts:
        y = frames[c][1]
        acc = np.mean([int(r.result) == int(y[j])
                       for cc, j, r in results if cc == c])
        if mode == "async":
            st = service.stats[c]
            print(f"  {c}: batches={st.batches} "
                  f"store_hits={st.store_hits} "
                  f"padded={st.padded_slots} accuracy={acc:.3f}")
        else:
            st = service.stats[c]
            print(f"  {c}: batches={st.batches} "
                  f"padded={st.padded_slots} accuracy={acc:.3f}")
    if mode == "async":
        summ = service.summary()
        print(f"store hit rate {summ['store_hit_rate']:.2f}  "
              f"repcache hit rate "
              f"{summ['repcache']['hit_rate']:.2f}  "
              f"deadline/size/drain flushes "
              f"{summ['deadline_flushes']}/{summ['size_flushes']}"
              f"/{summ['drain_flushes']}")
        p = summ["latency_ms"]
        print(f"latency p50={p['p50']}ms p95={p['p95']}ms "
              f"p99={p['p99']}ms  queue depth max="
              f"{summ['queue_depth']['max']}  in-flight max="
              f"{summ['in_flight']['max']}")
    else:
        print(f"latency p50={np.percentile(lat, 50):.1f}ms "
              f"p99={np.percentile(lat, 99):.1f}ms")


if __name__ == "__main__":
    use_compile_cache()
    main()
