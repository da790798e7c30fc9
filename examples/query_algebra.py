"""Boolean expression-tree queries + a cross-camera temporal join
through the relational query algebra (engine/algebra.py, DESIGN.md §15):

  SELECT frames WHERE cam = 0
                  AND contains(a) AND (contains(b) OR NOT contains(c))

  SELECT pairs  FROM camA, camB
                WHERE camA contains(a) AND camB contains(a)
                  AND |t_A - t_B| <= delta

1. train one TAHOMA system per concept (as examples/query_engine.py);
2. plan: ``QuerySpec.where`` carries the expression tree into
   ``plan_query``, which normalizes it (De Morgan to NNF), annotates
   every node with cost/selectivity estimates, cost-orders children for
   short-circuiting (AND rank cost/(1-sel); OR uses the INVERTED rank
   cost/sel — a branch short-circuits on TRUE, so the rarely-true
   branch goes LAST), and prints the annotated plan TREE;
3. execute: positive-leaf runs lower onto single shared-pyramid engine
   calls, NOT leaves read decided-0 virtual columns, AND/OR thread
   survivor sets — compared for wall-clock AND bit-identical rows
   against (a) the same tree executed WITHOUT short-circuiting or
   ordering and (b) the per-row naive oracle;
4. join: the cheap side runs first (build side), surviving timestamps
   prune the probe side to rows inside some ±delta window (exact), and
   the pair set is checked against the nested-loop reference.

  PYTHONPATH=src python examples/query_algebra.py [--tiny] [--delta 2]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.configs.base import TahomaCNNConfig  # noqa: E402
from repro.core.pipeline import initialize_system  # noqa: E402
from repro.core.transforms import Representation  # noqa: E402
from repro.data.synthetic import (DEFAULT_PREDICATES, make_corpus,  # noqa: E402
                                  make_multi_corpus,
                                  make_two_camera_corpus,
                                  three_way_split)
from repro.engine import (And, Join, Not, Or, Pred, QuerySpec,  # noqa: E402
                          ScanEngine, execute_join, execute_tree,
                          naive_join_pairs, naive_tree_rows, plan_query)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test scale (CI)")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--min-accuracy", type=float, default=0.8)
    ap.add_argument("--delta", type=float, default=2.0,
                    help="temporal join window |t_A - t_B| <= delta")
    args = ap.parse_args()

    hw = 32
    if args.tiny:
        specs = DEFAULT_PREDICATES[:2]
        n_train, n_query, steps = 200, 160, 40
    else:
        specs = DEFAULT_PREDICATES[:3]
        n_train, n_query, steps = 360, 384, 100
    reps = [Representation(8, "gray"), Representation(16, "gray"),
            Representation(hw, "rgb")]
    archs = [TahomaCNNConfig(1, 8, 16)]

    names = [s.name for s in specs]
    print(f"== concepts: {', '.join(names)} ==")
    print("initializing one TAHOMA system per concept...")
    t0 = time.time()
    systems = {}
    for spec in specs:
        x, y = make_corpus(spec, n_train, hw=hw, seed=0)
        systems[spec.name] = initialize_system(
            *three_way_split(x, y, seed=1), archs, reps, steps=steps)
    print(f"  done in {time.time() - t0:.0f}s")

    # ---------------------------------------------- expression tree ----
    if args.tiny:       # XOR: positives of exactly one concept
        where = Or(And(Pred(names[0]), Not(Pred(names[1]))),
                   And(Pred(names[1]), Not(Pred(names[0]))))
    else:
        where = And(Pred(names[0]),
                    Or(Pred(names[1]), Not(Pred(names[2]))))
    qx, _ = make_multi_corpus(specs, n_query, hw=hw, seed=7,
                              positive_rate=0.4)
    metadata = {"cam": np.arange(n_query) % 2}
    spec_q = QuerySpec(metadata_eq={"cam": 0}, where=where)
    plan = plan_query(systems, spec_q, scenario="CAMERA",
                      metadata=metadata)
    print()
    print(plan.explain(n_rows=n_query))

    baseline = ScanEngine(qx, metadata, chunk=args.chunk)
    res_un = execute_tree(baseline, plan, optimize=False)
    engine = ScanEngine(qx, metadata, chunk=args.chunk)
    res = execute_tree(engine, plan)    # last: EXPLAIN shows its actuals
    t0 = time.perf_counter()
    ref = naive_tree_rows(qx, where, plan.cascade_map(), metadata,
                          plan.metadata_eq, chunk=args.chunk)
    t_naive = time.perf_counter() - t0
    print(f"\noptimized tree:   {len(res.indices)} rows in "
          f"{res.seconds:.2f}s ({res.engine_calls} engine calls, "
          f"{res.rows_evaluated} rows evaluated)")
    print(f"unoptimized tree: {len(res_un.indices)} rows in "
          f"{res_un.seconds:.2f}s ({res_un.engine_calls} engine calls, "
          f"{res_un.rows_evaluated} rows evaluated)")
    print(f"naive per-row oracle: {len(ref)} rows in {t_naive:.2f}s")
    same = (np.array_equal(res.indices, ref)
            and np.array_equal(res_un.indices, ref))
    print(f"identical rows across all three: {same}")
    print("\nannotated plan after execution (est vs actual):")
    print(plan.explain(n_rows=n_query))

    # ----------------------------------------- cross-camera join ----
    needle = names[0]
    print(f"\n== temporal join: {needle}@camA and {needle}@camB within "
          f"±{args.delta} ==")
    (xa, _, ta), (xb, _, tb) = make_two_camera_corpus(
        specs, n_query // 2, hw=hw, seed=11, corr=0.6,
        dt_max=int(args.delta))
    meta_a, meta_b = {"t": ta}, {"t": tb}
    jtree = Join(Pred(needle), Pred(needle), delta_t=args.delta)
    jplan = plan_query(systems, QuerySpec(where=jtree), scenario="CAMERA",
                       metadata=(meta_a, meta_b))
    print(jplan.explain(n_rows=(len(xa), len(xb))))
    eng_a = ScanEngine(xa, meta_a, chunk=args.chunk)
    eng_b = ScanEngine(xb, meta_b, chunk=args.chunk)
    jres = execute_join((eng_a, eng_b), jplan)
    print(f"\npushdown join: {len(jres.pairs)} pairs in "
          f"{jres.seconds:.2f}s (probe side pruned to "
          f"{jplan.window_kept}/{len(xb)} rows inside a window)")
    # baseline: both sides in full, then the same hash join
    jres_un = execute_join((ScanEngine(xa, meta_a, chunk=args.chunk),
                            ScanEngine(xb, meta_b, chunk=args.chunk)),
                           jplan, optimize=False)
    ref_pairs = naive_join_pairs(
        (jres_un.left.indices, ta), (jres_un.right.indices, tb),
        args.delta)
    same_pairs = (np.array_equal(jres.pairs, ref_pairs)
                  and np.array_equal(jres_un.pairs, ref_pairs))
    print(f"no-pushdown join: {len(jres_un.pairs)} pairs in "
          f"{jres_un.seconds:.2f}s")
    print(f"identical pairs (pushdown, baseline, nested loop): "
          f"{same_pairs}")


if __name__ == "__main__":
    use_compile_cache()
    main()
