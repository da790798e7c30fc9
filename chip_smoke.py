"""Main-path smoke run on a TPU: train, plan, scan, serve.

    python chip_smoke.py              # one chip: planned scan + service
    python chip_smoke.py --chips 4    # four chips: the sharded scan only

One process drives every chip it uses. The default run trains a small
A x F grid per concept through ``initialize_system`` on 224 px frames,
joint-plans a 3-predicate query over an 8192-frame 224 px corpus with a
``cam`` column, scans it with ``ScanEngine`` (chunk 256, the compiled
Pallas pyramid+stage-0 kernel), checks the row set against
``naive_scan`` on the same chip, then serves a few hundred mixed
2-concept requests through ``AsyncCascadeService`` and checks every
label against the scan's decided virtual columns. ``--chips 4`` runs the
same query over the same corpus through ``ShardedScanEngine``, one shard
per chip, and checks it against the single-device engine and
``naive_scan``; it trains one grid model per concept (``SHARDED``).

All data comes from ``--seed``; nothing is read from disk. Any mismatch
or failed phase raises, and the script refuses to run anywhere but on a
TPU. Timings it prints are informal, not benchmark metrics. On success
the last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, replace
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclass(frozen=True)
class Size:
    hw: int = 224            # the paper's input size
    n_train: int = 1536      # frames per concept for initialize_system
    steps: int = 120         # training steps per grid model
    # the A x F grid per concept, from the reduced grid
    # (configs/tahoma_cnn.py) on the 224 -> 56 -> 28 pyramid:
    # (conv layers, conv nodes, dense nodes) and (hw divisor, colour);
    # initialize_system adds the full-size trusted model
    archs: tuple = ((1, 8, 16), (2, 16, 32))
    reps: tuple = ((8, "rgb"), (4, "gray"))
    n_query: int = 8192      # queried corpus
    block: int = 256         # corpus frames generated per call
    chunk: int = 256         # scan chunk
    requests: int = 384      # served requests
    batch: int = 64          # service batch size


# --chips 4 runs on a host whose compile cache the one-chip runs cannot
# fill (the cache key holds the topology), and every model shape costs
# TPU compiles of seconds each at four times the chip cost. It keeps the
# corpus, the query and the scan; its grid is one model per concept.
SHARDED = replace(Size(), n_train=768, archs=((1, 8, 16),),
                  reps=((8, "rgb"),))


def log(msg: str) -> None:
    print(msg, flush=True)


def training_frames(specs, size: Size, seed: int):
    from repro.data.synthetic import make_corpus

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(specs)) as pool:
        corpora = list(pool.map(
            lambda spec: make_corpus(spec, size.n_train, hw=size.hw,
                                     seed=seed), specs))
    log(f"training frames: {len(specs)} x {size.n_train} made in "
        f"{time.perf_counter() - t0:.1f} s")
    return corpora


def train_systems(specs, corpora, size: Size, seed: int):
    from repro.configs.base import TahomaCNNConfig
    from repro.core.pipeline import initialize_system
    from repro.core.transforms import Representation
    from repro.data.synthetic import three_way_split

    archs = [TahomaCNNConfig(*a) for a in size.archs]
    reps = [Representation(size.hw // d, c) for d, c in size.reps]
    systems = {}
    for spec, (x, y) in zip(specs, corpora):
        t0 = time.perf_counter()
        systems[spec.name] = initialize_system(
            *three_way_split(x, y, seed=seed + 1), archs, reps,
            steps=size.steps, seed=seed)
        log(f"  {spec.name}: system initialized in "
            f"{time.perf_counter() - t0:.1f} s")
    return systems


def query_corpus(specs, size: Size, seed: int):
    """``n_query`` frames from make_multi_corpus, ``block`` per call and
    several calls at once (numpy releases the GIL in the bulk of it; the
    generator's float64 scratch stays a few GB)."""
    import numpy as np

    from repro.data.synthetic import make_multi_corpus

    images = np.empty((size.n_query, size.hw, size.hw, 3), np.float32)
    labels = np.empty((size.n_query, len(specs)), np.int32)

    def fill(i):
        lo = i * size.block
        hi = min(lo + size.block, size.n_query)
        images[lo:hi], labels[lo:hi] = make_multi_corpus(
            specs, hi - lo, hw=size.hw, seed=seed * 1000 + 7 + i,
            positive_rate=0.4)

    t0 = time.perf_counter()
    blocks = range(-(-size.n_query // size.block))
    with ThreadPoolExecutor(min(32, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, blocks))
    log(f"corpus: {len(images)} frames of {size.hw}x{size.hw}x3 "
        f"({images.nbytes / 2**30:.2f} GiB as float32), generated in "
        f"{time.perf_counter() - t0:.1f} s")
    metadata = {"cam": np.arange(size.n_query) % 2}
    return images, labels, metadata


def plan(systems, specs, metadata):
    from repro.engine import PredicateClause, QuerySpec, plan_query

    spec = QuerySpec(metadata_eq={"cam": 0},
                     predicates=[PredicateClause(s.name, min_accuracy=0.8)
                                 for s in specs])
    return plan_query(systems, spec, scenario="CAMERA", metadata=metadata,
                      joint=True)


def check_kernel_compiled(casc, size: Size) -> None:
    """The ingest program the engine builds here by default
    (``use_kernel=None``) must hold the compiled Pallas kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core.executor import make_fused_ingest
    from repro.kernels import resolve_interpret

    caps = [size.chunk] * (len(casc.model_fns) - 1)
    ingest = make_fused_ingest(casc.model_fns, casc.thresholds, casc.reps,
                               caps, (), stage0=casc.stage0)
    text = ingest.lower(jax.ShapeDtypeStruct(
        (size.chunk, size.hw, size.hw, 3), jnp.float32)).as_text()
    compiled = "tpu_custom_call" in text
    log(f"ingest program: tpu_custom_call={compiled} "
        f"interpret={resolve_interpret(None)} "
        f"stage0={casc.stage0.rep.name}")
    if not compiled:
        raise AssertionError("the engine's ingest program does not contain "
                             "the compiled Pallas kernel")


def check_kernel_exact(casc, images, size: Size) -> None:
    """One chunk through the kernel and its unfused reference: pooled
    levels bit-identical, scores close."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.image_transform import fused_pyramid_stage0
    from repro.kernels.ref import fused_pyramid_stage0_ref

    levels = sorted({r.resolution for r in casc.reps} - {size.hw})
    x = jnp.asarray(images[:size.chunk])
    got_l, got_s = fused_pyramid_stage0(x, levels, casc.stage0.params,
                                        casc.stage0.rep)
    ref_l, ref_s = fused_pyramid_stage0_ref(x, levels, casc.stage0.params,
                                            casc.stage0.rep)
    exact = all(np.array_equal(np.asarray(got_l[r]), np.asarray(ref_l[r]))
                for r in levels)
    dev = float(np.max(np.abs(np.asarray(got_s) - np.asarray(ref_s))))
    log(f"kernel vs unfused reference on {size.chunk} frames: levels "
        f"{levels} bit-identical={exact}, max |score diff|={dev:.3g}")
    if not exact:
        raise AssertionError("kernel pyramid levels differ from "
                             "materialize_pyramid")


def scan_phase(images, metadata, physical, size: Size):
    import numpy as np

    from repro.core.pipeline import build_scan_engine
    from repro.engine import naive_scan

    engine = build_scan_engine(images, metadata, chunk=size.chunk)
    t0 = time.perf_counter()
    res = engine.execute(physical.cascades, physical.metadata_eq)
    cold = time.perf_counter() - t0
    engine.reset_cache()
    t0 = time.perf_counter()
    warm_res = engine.execute(physical.cascades, physical.metadata_eq)
    warm = time.perf_counter() - t0
    log(f"scan (informal wall clock, not a benchmark metric): cold "
        f"{cold:.2f} s incl. compiles, warm {warm:.2f} s over "
        f"{res.stats.rows_scanned} rows")
    ref = naive_scan(images, physical.cascades, metadata,
                     physical.metadata_eq, chunk=size.chunk)
    identical = (np.array_equal(res.indices, ref)
                 and np.array_equal(warm_res.indices, ref))
    log(f"rows returned: {len(res.indices)}  identical rows: {identical}")
    for st in res.stats.stages:
        log(f"  {st.concept}: {st.rows_in} in -> {st.rows_evaluated} "
            f"evaluated ({st.batches} batches)")
    if not identical:
        raise AssertionError("ScanEngine rows differ from naive_scan")
    return engine


def serve_phase(images, engine, physical, size: Size, seed: int):
    import numpy as np

    from repro.core.pipeline import build_cascade_service
    from repro.serve.batcher import Request

    served = physical.cascades[:2]
    decided = {c.concept: np.where(engine.store.column(c.key) >= 0)[0]
               for c in served}
    service = build_cascade_service(
        images, {c.concept: c for c in served}, mode="async",
        batch_size=size.batch, max_wait_s=0.005)
    log(f"service warmed {service.warmup()} executables")
    rng = np.random.default_rng(seed + 13)
    asked = []
    t0 = time.perf_counter()
    for i in range(size.requests):
        casc = served[i % 2]
        row = int(rng.choice(decided[casc.concept]))
        req = Request(i, row)
        service.submit(casc.concept, req)
        asked.append((casc, row, req))
        service.poll()
    service.drain()
    wall = time.perf_counter() - t0
    want = [int(engine.store.column(c.key)[row]) for c, row, _ in asked]
    got = [r.result for _, _, r in asked]
    identical = got == want
    p = service.summary()["latency_ms"]
    log(f"served {len(asked)} mixed requests ({', '.join(c.concept for c in served)}) "
        f"in {wall:.2f} s (informal); latency p50={p['p50']} ms "
        f"p99={p['p99']} ms")
    log(f"service labels identical to the scan's virtual columns: "
        f"{identical}")
    if not identical:
        bad = sum(g != w for g, w in zip(got, want))
        raise AssertionError(f"{bad} served labels differ from the scan")


def sharded_phase(images, metadata, physical, size: Size, shards: int):
    import numpy as np

    from repro.core.pipeline import build_scan_engine
    from repro.engine import naive_scan

    sharded = build_scan_engine(images, metadata, shards=shards,
                                chunk=size.chunk)
    t0 = time.perf_counter()
    res = sharded.execute(physical.cascades, physical.metadata_eq)
    wall = time.perf_counter() - t0
    st = res.stats
    log(f"sharded scan: {st.plan.describe()} backend={st.backend} "
        f"devices={st.n_devices} supersteps={st.supersteps} "
        f"({wall:.2f} s incl. compiles, informal)")
    for i, sh in enumerate(st.shards):
        log(f"  shard {i}: block on {st.staged_devices.get(i)}  "
            f"{sh.rows_scanned} rows -> {sh.rows_evaluated} evaluated")
    placed = {st.staged_devices.get(i) for i in range(shards)}
    if None in placed or len(placed) != shards:
        raise AssertionError(f"shard blocks are not on {shards} distinct "
                             f"devices: {st.staged_devices}")
    single = build_scan_engine(images, metadata, chunk=size.chunk).execute(
        physical.cascades, physical.metadata_eq)
    ref = naive_scan(images, physical.cascades, metadata,
                     physical.metadata_eq, chunk=size.chunk)
    same_single = np.array_equal(res.indices, single.indices)
    same_naive = np.array_equal(res.indices, ref)
    log(f"rows returned: {len(res.indices)}  identical to ScanEngine: "
        f"{same_single}  identical to naive_scan: {same_naive}")
    if not (same_single and same_naive):
        raise AssertionError("sharded rows differ from the references")


def run(size: Size, *, chips: int = 1, seed: int = 0) -> None:
    """Every phase of the smoke run on whatever backend JAX has; main()
    makes sure that is a TPU."""
    from repro.data.synthetic import DEFAULT_PREDICATES

    specs = DEFAULT_PREDICATES[:3]
    corpora = training_frames(specs, size, seed)
    # the query corpus is host work in numpy, which drops the GIL: make
    # it while the models train and compile. Not beside the training
    # frames' generation: the two float64 scratches together overran a
    # one-chip host's 40 GiB.
    with ThreadPoolExecutor(1) as pool:
        corpus = pool.submit(query_corpus, specs, size, seed)
        t0 = time.perf_counter()
        systems = train_systems(specs, corpora, size, seed)
        log(f"trained {sum(len(s.bank.entries) for s in systems.values())} "
            f"models for {', '.join(s.name for s in specs)} in "
            f"{time.perf_counter() - t0:.1f} s")
        images, _, metadata = corpus.result()
    physical = plan(systems, specs, metadata)
    log(physical.explain(n_rows=len(images)))
    if chips > 1:
        sharded_phase(images, metadata, physical, size, chips)
        return
    check_kernel_compiled(physical.cascades[0], size)
    check_kernel_exact(physical.cascades[0], images, size)
    engine = scan_phase(images, metadata, physical, size)
    serve_phase(images, engine, physical, size, seed)


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded scan, one shard per "
                         "chip, against its references")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({len(devices)} device(s))", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={len(devices)}")
    log(f"compile cache: {cache_dir}")
    from repro.tracing import COMPILES

    run(SHARDED if args.chips > 1 else Size(), chips=args.chips,
        seed=args.seed)
    log(f"compile seconds: {COMPILES.report()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
