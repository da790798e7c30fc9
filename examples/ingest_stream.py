"""Streaming ingest-time indexing end to end (DESIGN.md §14):

  camera stream -> IngestPipeline (temporal skip detector + stage-0
  candidate-concept index) -> indexed queries + index-seeded serving

1. train one TAHOMA system per concept and plan a multi-predicate query
   (the planned cascades are the physical cascades the index keys on);
2. ingest a simulated camera stream chunk-by-chunk: near-duplicate
   frames are skip-aliased to their reference frame and never scored;
   each reference frame gets one cheap stage-0 rung per concept (one
   shared pyramid per chunk via the fused ingest program), yielding
   exact stage-0 decided labels + an approximate candidate set;
3. query three ways and compare row sets + rows evaluated:
   cold scan | indexed 'exact' (bit-identical row set guaranteed — the
   exactness escape hatch re-verifies skip-aliased rows) | indexed
   'approx' (alias labels + candidate pruning at a measured-recall
   knob);
4. seed an AsyncCascadeService from the index: ingest-decided rows are
   answered at submit with zero model invocations (store_hits).

  PYTHONPATH=src python examples/ingest_stream.py [--tiny] [--no-skip]
                                                  [--frames N]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.configs.base import TahomaCNNConfig  # noqa: E402
from repro.core.pipeline import (build_cascade_service,  # noqa: E402
                                 build_ingest_pipeline, build_scan_engine,
                                 initialize_system)
from repro.core.transforms import Representation  # noqa: E402
from repro.data.synthetic import (DEFAULT_PREDICATES, make_camera_stream,  # noqa: E402
                                  make_corpus, three_way_split)
from repro.engine import (PredicateClause, QuerySpec,  # noqa: E402
                          plan_query)
from repro.engine.ingest import indexed_execute  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=960,
                    help="camera-stream length")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--min-accuracy", type=float, default=0.8)
    ap.add_argument("--no-skip", action="store_true",
                    help="disable the temporal-difference skip detector")
    ap.add_argument("--top-k", type=int, default=None,
                    help="cap each frame's candidate set to the top-K "
                         "stage-0 margins (Focus-style)")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test scale (CI)")
    args = ap.parse_args()

    hw = 32
    if args.tiny:
        specs = DEFAULT_PREDICATES[:2]
        n_train, steps = 200, 40
        n_frames = min(args.frames, 384)
        reps = [Representation(8, "gray"), Representation(16, "gray"),
                Representation(hw, "rgb")]
    else:
        specs = DEFAULT_PREDICATES[:3]
        n_train, steps = 360, 100
        n_frames = args.frames
        reps = [Representation(8, "gray"), Representation(8, "rgb"),
                Representation(16, "gray"), Representation(16, "rgb"),
                Representation(hw, "gray"), Representation(hw, "rgb")]
    archs = [TahomaCNNConfig(1, 8, 16)]

    print(f"== predicates: {', '.join(s.name for s in specs)} ==")
    print("initializing one TAHOMA system per concept...")
    t0 = time.time()
    systems = {}
    for spec in specs:
        x, y = make_corpus(spec, n_train, hw=hw, seed=0)
        systems[spec.name] = initialize_system(
            *three_way_split(x, y, seed=1), archs, reps, steps=steps)
    print(f"  trained in {time.time() - t0:.0f}s")

    # plan FIRST: the ingest index keys labels by the planned physical
    # cascades (CompiledCascade.key)
    spec_q = QuerySpec(metadata_eq={}, predicates=[
        PredicateClause(s.name, min_accuracy=args.min_accuracy)
        for s in specs])
    plan = plan_query(systems, spec_q, joint=True)

    frames, truth, scene = make_camera_stream(specs, n_frames, hw=hw,
                                              seed=7)
    print(f"\n== ingest: {n_frames} frames, {scene.max() + 1} scenes ==")
    pipe = build_ingest_pipeline(plan.cascades, n_frames,
                                 chunk=args.chunk, skip=not args.no_skip,
                                 top_k=args.top_k)
    t0 = time.perf_counter()
    ids = np.arange(n_frames)
    for lo in range(0, n_frames, args.chunk):    # simulated arrival
        pipe.ingest(frames[lo:lo + args.chunk], ids[lo:lo + args.chunk])
    t_ingest = time.perf_counter() - t0
    st = pipe.stats
    print(f"  {st.frames} frames in {t_ingest:.2f}s: {st.skipped} "
          f"skip-aliased, {st.refs} scored ({st.stage0_scores} stage-0 "
          f"scores), {st.decided_labels} labels decided exactly at "
          f"ingest")

    # -------------------------------------------------- three queries --
    def query(index_mode=None):
        eng = build_scan_engine(frames, chunk=args.chunk)
        if index_mode is None:
            t0 = time.perf_counter()
            res = eng.execute(plan.cascades, {})
            return res, time.perf_counter() - t0
        p = plan_query(systems, spec_q, joint=True, index=pipe.index,
                       index_mode=index_mode)
        t0 = time.perf_counter()
        res = indexed_execute(eng, p)
        return res, time.perf_counter() - t0

    cold, t_cold = query()
    exact, t_exact = query("exact")
    approx, t_approx = query("approx")
    print(f"\n== query: {' AND '.join(s.name for s in specs)} ==")
    explain = plan_query(systems, spec_q, joint=True, index=pipe.index,
                         index_mode="approx").explain(n_rows=n_frames)
    print(next(ln for ln in explain.splitlines() if "ingest index" in ln))
    print(f"  cold scan:      {len(cold.indices)} rows, "
          f"{cold.stats.rows_evaluated} rows evaluated, {t_cold:.2f}s")
    kept = 100 * (1 - exact.stats.rows_evaluated
                  / max(cold.stats.rows_evaluated, 1))
    print(f"  indexed exact:  {len(exact.indices)} rows, "
          f"{exact.stats.rows_evaluated} rows evaluated "
          f"(-{kept:.0f}%), {t_exact:.2f}s | bit-identical: "
          f"{np.array_equal(exact.indices, cold.indices)}")
    kept = 100 * (1 - approx.stats.rows_evaluated
                  / max(cold.stats.rows_evaluated, 1))
    inter = len(np.intersect1d(approx.indices, cold.indices))
    rec = [pipe.index.measured_recall(s.name, truth[:, k])
           for k, s in enumerate(specs)]
    print(f"  indexed approx: {len(approx.indices)} rows, "
          f"{approx.stats.rows_evaluated} rows evaluated "
          f"(-{kept:.0f}%), {t_approx:.2f}s | recall vs cold: "
          f"{inter / max(len(cold.indices), 1):.2f} | measured "
          f"per-concept recall: "
          + ", ".join(f"{s.name}={r:.2f}" for s, r in zip(specs, rec)))

    # -------------------------------------------- index-seeded serving --
    from repro.serve.batcher import Request

    svc = build_cascade_service(frames,
                                {c.concept: c for c in plan.cascades},
                                shards=2, ingest_index=pipe.index)
    concept = plan.cascades[0].concept
    col = pipe.index.decided.column(plan.cascades[0].key)
    rows = np.where(col >= 0)[0][:64]
    for i, r in enumerate(rows):
        svc.submit(concept, Request(rid=i, payload=int(r)))
    s = svc.stats[concept]
    print(f"\n== serving seeded from the index ==")
    print(f"  {s.requests} requests for ingest-decided rows -> "
          f"{s.store_hits} answered at submit ({s.rows_evaluated} rows "
          f"evaluated, {s.batches} batches dispatched)")


if __name__ == "__main__":
    use_compile_cache()
    main()
