"""End-to-end multi-predicate query through the query engine
(DESIGN.md §4, §11):

  SELECT frames WHERE cam = 0 AND contains(a) AND contains(b) AND
                       contains(c)

1. train one TAHOMA system (A x F grid -> thresholds -> cost profile ->
   evaluated cascade space) per concept;
2. plan: select the cascade SET under shared-representation costing
   (``--planner joint``, the default: per-predicate Pareto frontiers as
   candidate pools, shared pyramid levels priced once — DESIGN.md §11)
   or one cascade per predicate independently (``--planner
   independent``), order predicates by (marginal) cost/(1-selectivity),
   print the EXPLAIN-style physical plan;
3. execute: stream the corpus in chunks, ONE shared representation
   pyramid per chunk covering exactly the plan's level set, cascades
   only on rows surviving earlier predicates — and compare wall-clock +
   row set against naive per-predicate full scans;
4. re-run a re-planned query to show partial virtual-column reuse.

``--adaptive`` attaches the planner's OnlineReorderer: the engine feeds
observed per-flush selectivities back and re-orders surviving predicates
mid-scan when the eval-split estimates drift (row sets stay
bit-identical — DESIGN.md §11.3).

With ``--shards N`` the survivor set is partitioned across N shard
executors (DESIGN.md §9: pmap lockstep over the host's devices; set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to simulate a
multi-chip host on CPU) and EXPLAIN additionally prints the shard
layout. Row sets are bit-identical to the unsharded engine.

  PYTHONPATH=src python examples/query_engine.py [--scenario CAMERA]
                                                 [--planner joint]
                                                 [--shards N]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# simulate a multi-chip host on CPU for the sharded path; the flag must
# land before the first jax import (the repro imports below pull jax in)
from repro.launch.devsim import force_host_devices  # noqa: E402

force_host_devices(8, when_flag="--shards")

import numpy as np  # noqa: E402

from repro.configs.base import TahomaCNNConfig  # noqa: E402
from repro.core.pipeline import build_scan_engine, initialize_system  # noqa: E402
from repro.core.transforms import Representation  # noqa: E402
from repro.data.synthetic import (DEFAULT_PREDICATES, make_corpus,  # noqa: E402
                                  make_multi_corpus, three_way_split)
from repro.engine import (PredicateClause, QuerySpec,  # noqa: E402
                          naive_scan, plan_query)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402


EXPLAIN_HELP = """\
EXPLAIN output (PhysicalPlan.explain, DESIGN.md §4.1/§11.2):
  per predicate:  the chosen cascade, its estimated accuracy, standalone
    cost/row, selectivity, ordering rank cost/(1-sel), and the fraction
    of rows reaching it under the plan order.
  joint plans add per predicate:  'levels={...}' the pyramid levels the
    cascade touches; 'shared={...}' the levels inherited from EARLIER
    predicates (materialized once per chunk, free here); 'rep/row
    marginal X vs standalone Y' the representation cost actually charged
    under sharing vs the §VI standalone price; 'infer/row' the expected
    pure-inference cost.
  joint plans add a summary:  'shared-representation savings' = unshared
    minus joint est. cost/row, and the pyramid level set the engine will
    materialize once per chunk (== PhysicalPlan.level_set + raw base).
"""


def main():
    ap = argparse.ArgumentParser(
        epilog=EXPLAIN_HELP,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--scenario", default="CAMERA",
                    choices=["INFER_ONLY", "ARCHIVE", "ONGOING", "CAMERA"])
    ap.add_argument("--min-accuracy", type=float, default=0.8)
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--planner", default="joint",
                    choices=["joint", "independent"],
                    help="joint = select the cascade SET under shared-"
                         "representation costing (DESIGN.md §11); "
                         "independent = per-predicate Pareto selection")
    ap.add_argument("--adaptive", action="store_true",
                    help="refine selectivities online: re-order "
                         "surviving predicates mid-scan when observed "
                         "per-flush selectivity drifts from the "
                         "eval-split estimate (bit-identical rows)")
    ap.add_argument("--shards", type=int, default=0,
                    help="partition the scan across N shard executors "
                         "(0 = single-host engine)")
    ap.add_argument("--shard-strategy", default="range",
                    choices=["range", "hash"])
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test scale (CI)")
    args = ap.parse_args()

    hw = 32
    if args.tiny:
        specs = DEFAULT_PREDICATES[:2]
        n_train, n_query, steps = 200, 192, 40
        reps = [Representation(8, "gray"), Representation(16, "gray"),
                Representation(hw, "rgb")]
        archs = [TahomaCNNConfig(1, 8, 16)]
    else:
        specs = DEFAULT_PREDICATES[:3]
        n_train, n_query, steps = 360, 480, 100
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
    print(f"  {sum(len(s.bank.entries) for s in systems.values())} models "
          f"in {time.time() - t0:.0f}s")

    # the queried corpus carries all predicate signals independently
    qx, qlabels = make_multi_corpus(specs, n_query, hw=hw, seed=7,
                                    positive_rate=0.4)
    metadata = {"cam": np.arange(n_query) % 2}

    spec_q = QuerySpec(
        metadata_eq={"cam": 0},
        predicates=[PredicateClause(s.name, min_accuracy=args.min_accuracy)
                    for s in specs])
    plan = plan_query(systems, spec_q, scenario=args.scenario,
                      metadata=metadata, joint=args.planner == "joint")

    engine = build_scan_engine(qx, metadata, shards=args.shards,
                               chunk=args.chunk,
                               strategy=args.shard_strategy)
    shard_plan = (engine.plan_for(plan.cascades, plan.metadata_eq)
                  if args.shards else None)
    print()
    print(plan.explain(n_rows=n_query, shard_plan=shard_plan))

    monitor = None
    if args.adaptive:
        if args.shards:
            # re-ordering would desync the lockstep supersteps for zero
            # dispatch savings (engine/sharded.py docstring)
            print("note: --adaptive is a serial-engine feature and is "
                  "ignored with --shards")
        else:
            from repro.engine import OnlineReorderer
            monitor = OnlineReorderer.from_plan(plan,
                                                min_rows=args.chunk // 2)

    t0 = time.perf_counter()
    if shard_plan is not None:           # execute the layout EXPLAIN shows
        res = engine.execute(plan.cascades, plan.metadata_eq,
                             shard_plan=shard_plan)
    else:
        res = engine.execute(plan.cascades, plan.metadata_eq,
                             monitor=monitor)
    t_engine = time.perf_counter() - t0

    t0 = time.perf_counter()
    ref = naive_scan(qx, plan.cascades, metadata, plan.metadata_eq,
                     chunk=args.chunk)
    t_naive = time.perf_counter() - t0

    identical = np.array_equal(res.indices, ref)
    print(f"\nengine: {len(res.indices)} rows in {t_engine:.2f}s | naive "
          f"full scans: {len(ref)} rows in {t_naive:.2f}s "
          f"({t_naive / max(t_engine, 1e-9):.1f}x) | identical rows: "
          f"{identical}")
    for s in res.stats.stages:
        print(f"  {s.concept}: {s.rows_in} in -> {s.rows_evaluated} "
              f"evaluated ({s.batches} batches, {s.rows_cached} cached)")
    if monitor is not None:
        print(f"  adaptive: {res.stats.reorders} mid-scan re-orderings "
              f"(observed selectivities: "
              + ", ".join(f"{c.concept}={monitor.refined(c.key):.2f}"
                          for c in plan.cascades) + ")")
    if args.shards:
        st = res.stats
        print(f"  shards: {st.plan.describe()}  backend={st.backend} "
              f"devices={st.n_devices} supersteps={st.supersteps}")
        for i, sh in enumerate(st.shards):
            print(f"    shard {i}: {sh.rows_scanned} rows -> "
                  f"{sh.rows_evaluated} evaluated ({sh.chunks} chunks)")
    if len(res.indices):
        tp = qlabels[res.indices].all(axis=1).mean()
        print(f"  precision vs ground truth (all predicates): {tp:.2f}")

    # re-planned query (reversed order): partial virtual columns kick in
    res2 = engine.execute(plan.cascades[::-1], plan.metadata_eq)
    reused = sum(s.rows_cached for s in res2.stats.stages)
    print(f"\nre-planned (reversed) query: identical rows="
          f"{np.array_equal(res2.indices, res.indices)}, "
          f"{reused} row-labels reused from virtual columns, "
          f"{res2.stats.rows_evaluated} newly evaluated")


if __name__ == "__main__":
    use_compile_cache()
    main()
