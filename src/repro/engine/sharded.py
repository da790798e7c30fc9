"""Sharded multi-device scan engine (DESIGN.md §9).

Partitions the metadata-survivor row set across N shard executors
(`sharding/policy.plan_shards`: range or hash partitioning, skew-aware
when the planner's per-row cost estimates are available) and runs the
PR-2 chunk/stage pipeline per shard with shard-local pyramid
materialization. Two execution backends:

* **lockstep (default)** — shards advance through the scan in
  synchronized supersteps; each superstep stacks one bucketed
  index-slab per shard into a leading device axis and issues ONE
  ``jax.pmap`` dispatch over the shard devices
  (`launch/mesh.shard_devices`). Shard images are committed to their
  devices once per scan (one ``jax.device_put`` onto a 1-D shard
  mesh); each superstep gathers device-locally, materializes the pyramid shard-locally, and
  ships back only labels plus the small non-base levels — the base
  level is regathered on-device at flush time, so per-superstep host
  traffic is index slabs and labels, not image-sized tensors. On a
  multi-chip host every shard's pyramid/cascade computation runs on its
  own device concurrently. Python-thread-per-shard designs were
  measured and rejected: GIL-serialized dispatch makes threads *slower*
  than serial at 8 shards. Row routing between stages stays host-side
  numpy, exactly the serial engine's cache-aware walk.
* **serial fallback** (``parallel=False``) — one
  ``ScanEngine.scan_rows`` call per shard, the factored shard-invocable
  unit from engine/scan.py. Same row sets, no device concurrency; this
  is also the reference path the differential tests pit the lockstep
  against, and the per-shard unit BENCH_sharded_scan.json times in
  isolation for the critical-path throughput curve (on CPU CI the
  simulated devices share the physical cores, so lockstep wall-clock
  cannot scale there — see DESIGN.md §9.4).

Each shard scans against a shard-local `VirtualColumnStore` seeded from
the corpus-wide store, and the shard stores are merged back
(`VirtualColumnStore.merge_from`: union of computed entries, a computed
label is never overwritten) so re-planned queries reuse every partial
column regardless of which shard computed it.

Exactness: a row's labels depend only on its own pooled pyramid rows at
a fixed batch shape (per-row independence, DESIGN.md §4.2), and the
ShardPlan assigns every surviving row to exactly one shard — so the
merged row set is bit-identical to the single-shard `ScanEngine` and to
`naive_scan`, for any shard count, partitioning strategy, or backend
(tests/test_sharded_scan.py holds all three equal).

Ownership and invariants: each SHARD materializes its own pyramid —
shard-locally, on its own device, covering exactly the same union level
set the serial engine would build (``stage_needs``; ==
``PhysicalPlan.level_set`` + base for a planned query) — the corpus has
no global pyramid. This ENGINE (and only it) merges: shard-local stores
are seeded with their partition's rows before the scan and merged back
corpus-wide after (``VirtualColumnStore.merge_from``: union of computed
entries; a "decided" row — one whose column holds 0/1 — is never
overwritten, by any shard, in any merge order). The planner's
mid-scan re-order hook is a serial-engine feature; the lockstep backend
runs the plan's order unchanged (per-shard re-ordering would desync the
supersteps for zero dispatch savings).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Mapping, Sequence

import numpy as np

from repro.engine.scan import (CompiledCascade, ScanEngine, ScanStats,
                               StageStats, VirtualColumnStore,
                               level_schedule, stage_needs)
from repro.sharding.policy import ShardPlan, plan_shards


# ---------------------------------------------------------- slab builder --
SLAB_FLOOR = 16


def slab_width(n_valid: int, cap: int, floor: int = SLAB_FLOOR) -> int:
    """Bucketed slab width: smallest power-of-two >= ``n_valid``,
    floored at ``floor`` and capped at ``cap``. Keeps sparse batches
    (late-stage lockstep slabs, deadline-triggered partial serving
    flushes) from paying full-width padding compute while bounding the
    number of distinct compiled shapes to O(log cap). Labels are
    width-independent (per-row independence, DESIGN.md §4.2), so the
    bucket size is purely a perf knob. Shared by the lockstep supersteps
    here and the async service's batch assembler (serve/service.py)."""
    b = floor
    while b < n_valid:
        b *= 2
    return min(b, cap)


def pad_rows(ids: np.ndarray, width: int) -> np.ndarray:
    """Pad a valid id prefix to the slab width by repeating the last id
    (the lockstep/serving padding policy: stale duplicate rows are
    computed and discarded, never recorded). Requires 0 < len <= width."""
    ids = np.asarray(ids, np.int64)
    return np.concatenate([ids, np.full(width - len(ids), ids[-1],
                                        np.int64)])


@dataclass
class ShardedScanStats:
    plan: ShardPlan
    backend: str                       # 'lockstep' | 'serial'
    n_devices: int = 1
    supersteps: int = 0                # lockstep group dispatches issued
    shards: list = field(default_factory=list)   # ScanStats per shard
    # lockstep backend: shard index -> the device its staged image
    # block was committed to (empty on the eager and serial backends)
    staged_devices: dict = field(default_factory=dict)

    @property
    def rows_scanned(self) -> int:
        return sum(s.rows_scanned for s in self.shards)

    @property
    def rows_evaluated(self) -> int:
        return sum(s.rows_evaluated for s in self.shards)

    @property
    def level_rows(self) -> dict:
        """Per-level materialization counters summed across shards
        (same shape as ScanStats.level_rows)."""
        out: dict = {}
        for sh in self.shards:
            for r, n in sh.level_rows.items():
                out[r] = out.get(r, 0) + n
        return out

    @property
    def stages(self) -> list:
        """Per-predicate StageStats summed across shards (same shape the
        single-shard ScanStats exposes)."""
        if not self.shards or not self.shards[0].stages:
            return []
        out = []
        for i, st0 in enumerate(self.shards[0].stages):
            agg = StageStats(st0.concept)
            for sh in self.shards:
                st = sh.stages[i]
                agg.rows_in += st.rows_in
                agg.rows_cached += st.rows_cached
                agg.rows_evaluated += st.rows_evaluated
                agg.batches += st.batches
            out.append(agg)
        return out


@dataclass
class ShardedScanResult:
    indices: np.ndarray
    stats: ShardedScanStats


class _ObserveOnly:
    """Monitor wrapper for the serial-fallback shard loop: forwards
    observed labels (so re-plans see measured selectivities) but
    suppresses re-order proposals — a per-shard re-order would desync
    the shards' stage aggregation for zero dispatch savings."""

    def __init__(self, monitor):
        self._monitor = monitor

    def observe(self, key, labels, *, marginal: bool = False) -> None:
        self._monitor.observe(key, labels, marginal=marginal)

    def propose(self, cascades):
        return None


class ShardedScanEngine:
    """Corpus-wide scan over N shards with one merged virtual-column
    store. Wraps a single-host ScanEngine for the shared pieces
    (metadata masking, the serial shard unit, the corpus-wide store);
    owns the shard planning and the lockstep pmap execution."""

    def __init__(self, images, metadata: Mapping[str, np.ndarray]
                 | None = None, *, shards: int | None = None,
                 chunk: int = 64, jit: bool = True,
                 strategy: str = "range", devices: Sequence | None = None,
                 fused: bool = True, lazy: bool = True, int8: bool = False,
                 use_kernel: bool | None = None):
        from repro.launch.mesh import shard_devices

        self.local = ScanEngine(images, metadata, chunk=chunk, jit=jit,
                                fused=fused, lazy=lazy, int8=int8,
                                use_kernel=use_kernel)
        self.devices = list(devices) if devices is not None \
            else shard_devices(shards)
        self.n_shards = int(shards) if shards is not None \
            else len(self.devices)
        if self.n_shards < 1:
            raise ValueError("need at least one shard")
        self.chunk = int(chunk)
        self.jit = jit
        self.strategy = strategy
        self._fns: dict = {}

    # ------------------------------------------------------- delegation --
    @property
    def images(self) -> np.ndarray:
        return self.local.images

    @property
    def metadata(self) -> Mapping[str, np.ndarray]:
        """The corpus metadata columns (the algebra layer's temporal
        join reads its timestamp column engine-agnostically —
        engine/algebra.execute_join)."""
        return self.local.metadata

    @property
    def store(self) -> VirtualColumnStore:
        """The corpus-wide merged store (shared with the wrapped serial
        engine, so mixed sharded/unsharded sessions see one cache)."""
        return self.local.store

    def reset_cache(self) -> None:
        self.local.reset_cache()

    def metadata_mask(self, metadata_eq: Mapping | None) -> np.ndarray:
        return self.local.metadata_mask(metadata_eq)

    # ---------------------------------------------------- shard planning --
    def row_weights(self, cascades: Sequence[CompiledCascade],
                    ids: np.ndarray, *, monitor=None) -> np.ndarray:
        """Expected evaluation seconds per row under the planner's
        cost/selectivity estimates, refined by the store: a cached label
        costs nothing and collapses the row's survival to 0/1. This is
        the skew-aware signal range partitioning balances on — after a
        partial first query, the un-evaluated region of the corpus is
        more expensive and gets spread across more shards. ``monitor``
        (engine/planner.OnlineReorderer) swaps the static plan-time
        selectivities for the selectivities OBSERVED in earlier flushes
        (``monitor.refined``) — so a re-plan mid-corpus weighs the
        remaining rows by what the scan has actually measured, not by
        eval-split estimates that may have drifted."""
        ids = np.asarray(ids, np.int64)
        w = np.zeros(len(ids))
        alive = np.ones(len(ids))
        for casc in cascades:
            sel = (monitor.refined(casc.key) if monitor is not None
                   else casc.selectivity)
            cached = self.store.lookup(casc.key, ids)
            w += alive * np.where(cached < 0, max(casc.cost_s, 1e-12), 0.0)
            alive *= np.where(cached == 0, 0.0,
                              np.where(cached == 1, 1.0,
                                       np.clip(sel, 0.0, 1.0)))
        return w

    def plan_for(self, cascades: Sequence[CompiledCascade],
                 metadata_eq: Mapping | None = None, *,
                 ids: np.ndarray | None = None, monitor=None) -> ShardPlan:
        """The ShardPlan execute() would use: survivor ids partitioned
        under this engine's strategy with skew-aware weights (observed-
        selectivity-refined when a ``monitor`` is given)."""
        if ids is None:
            ids = np.where(self.metadata_mask(metadata_eq))[0]
        weights = (self.row_weights(cascades, ids, monitor=monitor)
                   if cascades else None)
        return plan_shards(ids, self.n_shards, strategy=self.strategy,
                           weights=weights)

    # --------------------------------------------------------- execution --
    def execute(self, cascades: Sequence[CompiledCascade],
                metadata_eq: Mapping | None = None, *,
                shard_plan: ShardPlan | None = None,
                parallel: bool = True,
                survivors: np.ndarray | None = None,
                monitor: object | None = None) -> ShardedScanResult:
        """SELECT row ids WHERE metadata_eq AND every cascade labels 1,
        sharded. ``shard_plan`` overrides the engine's own planning (it
        must partition exactly the metadata survivors). ``survivors``
        is an index-pruned survivor set (engine/ingest.CandidateIndex
        via PhysicalPlan.index_prefilter): only metadata survivors ALSO
        in it are partitioned and scanned — same semantics as the
        serial engine's ``execute``. ``monitor``
        (engine/planner.OnlineReorderer) is OBSERVE-ONLY here: every
        evaluation flush feeds it measured labels — so the NEXT
        ``plan_for`` partitions on observed selectivities — but the
        sharded backends never apply its re-order proposals mid-scan
        (per-shard re-ordering would desync the lockstep supersteps and
        the cross-shard stage aggregation)."""
        cascades = list(cascades)
        ids_all = np.where(self.metadata_mask(metadata_eq))[0]
        if survivors is not None:
            ids_all = np.intersect1d(ids_all,
                                     np.asarray(survivors, np.int64))
        if shard_plan is None:
            shard_plan = self.plan_for(cascades, ids=ids_all,
                                       monitor=monitor)
        else:
            shard_plan.validate(ids_all)

        backend = "lockstep" if parallel else "serial"
        stats = ShardedScanStats(
            shard_plan, backend,
            n_devices=min(self.n_shards, len(set(self.devices))),
            shards=[ScanStats(stages=[StageStats(c.concept)
                                      for c in cascades])
                    for _ in range(shard_plan.n_shards)])
        for st, part in zip(stats.shards, shard_plan.shards):
            st.rows_scanned = len(part)
        if not cascades:
            return ShardedScanResult(ids_all, stats)

        # shard-local stores seeded from the corpus-wide store (only the
        # shard's own partition rows — all it will ever look up)
        shard_stores = []
        for part in shard_plan.shards:
            st = VirtualColumnStore(len(self.images))
            st.seed_from(self.store, part)
            shard_stores.append(st)
        if parallel:
            accepted = self._lockstep(cascades, shard_plan, shard_stores,
                                      stats, monitor=monitor)
        else:
            proxy = _ObserveOnly(monitor) if monitor is not None else None
            accepted = []
            for si, part in enumerate(shard_plan.shards):
                if not len(part):
                    continue
                r = self.local.scan_rows(cascades, part,
                                         store=shard_stores[si],
                                         monitor=proxy)
                stats.shards[si] = r.stats
                accepted.append(r.indices)

        # merge: union of computed entries, no -1 overwrites
        for st in shard_stores:
            self.store.merge_from(st)

        nonempty = [a for a in accepted if len(a)]
        out = (np.sort(np.concatenate(nonempty)) if nonempty
               else np.empty(0, np.int64))
        return ShardedScanResult(out, stats)

    # ------------------------------------------------- lockstep backend --
    def _slab_runner(self, key: tuple, make_fn):
        """Compile cache for group slab functions: pmap over the shard
        devices when jitting, a per-shard python loop (same padding,
        same results) when not."""
        if key not in self._fns:
            fn = make_fn()
            width = key[-1]
            if self.jit:
                import jax
                devs = list(dict.fromkeys(self.devices))[:width]
                runner = jax.pmap(fn, devices=devs)
            else:
                def runner(*slabs, _fn=fn, _w=width):
                    import jax
                    outs = [_fn(*[jax.tree.map(lambda v: v[j], s)
                                  for s in slabs]) for j in range(_w)]
                    return jax.tree.map(lambda *xs: np.stack(xs), *outs)
            self._fns[key] = runner
        return self._fns[key]

    def _ingest_runner(self, casc: CompiledCascade, out_res: tuple,
                       width: int):
        """Fused ingest superstep: gather the slab's rows from the
        device-resident shard image block, then run the same fused
        pyramid + full-stage-0 program the serial engine builds
        (core/executor.make_fused_ingest — the Pallas pyramid+stage-0
        kernel on TPU with real CNN params, one jit composition
        elsewhere). Ships back ONLY the labels plus the small non-base
        levels later stages carry; the base level never round-trips (it
        is regathered from the block at flush time). Under lazy
        scheduling the program materializes just cascade 0's own levels
        plus ``out_res`` — later-stage-only levels wait for first touch
        at flush. One dispatch per superstep, minimal host bytes."""
        def make():
            import jax.numpy as jnp

            from repro.core.executor import make_fused_ingest
            # same chunk-clamped full-width capacities and int8/kernel
            # resolution as the serial engine's _ingest_fn (argsort
            # slicing clamps cap to the slab width b <= chunk)
            caps = [self.chunk] * (len(casc.model_fns) - 1)
            int8 = (self.local.int8 and casc.stage0 is not None
                    and casc.stage0.qparams is not None)
            use_kernel = (self.local.use_kernel
                          if casc.stage0 is not None else False)
            core = make_fused_ingest(
                casc.model_fns, casc.thresholds, casc.reps, caps,
                out_res, stage0=casc.stage0, use_kernel=use_kernel,
                int8=int8, jit=False)

            def fn(block, idx):
                return core(jnp.take(block, idx, axis=0))
            return fn
        return self._slab_runner(
            ("ingest", casc.key, out_res, width), make)

    def _flush_runner(self, casc: CompiledCascade, base_hw: int,
                      in_res: tuple, out_res: tuple, width: int):
        """Stage-s flush: cascade inputs are the host-carried small
        levels (``in_res`` minus base) plus, when the cascade reads the
        base resolution or must first-touch-derive a level, a
        device-side regather from the shard image block. Levels the
        cascade reads that are NOT in ``in_res`` are derived inside the
        program with exactly the serial engine's _cascade_fn policy
        (smallest provided/derived level that divides — bit-exact from
        base for dyadic pixels); ``out_res`` names the derived levels
        shipped back for downstream stages to carry."""
        with_base = base_hw in in_res

        def make():
            import jax.numpy as jnp

            from repro.core.executor import run_cascade_on_pyramid
            from repro.core.transforms import resize_area
            # full-width levels clamped by slab width, never
            # casc.capacities — see CompiledCascade
            caps = [self.chunk] * (len(casc.model_fns) - 1)
            steps: list[tuple[int, int]] = []
            avail = set(in_res)
            for r in sorted(set(casc.resolutions) - avail, reverse=True):
                steps.append((r, min(m for m in avail if m % r == 0)))
                avail.add(r)

            def fn(block, idx, small):
                pyr = dict(small)
                if with_base:
                    pyr[base_hw] = jnp.take(block, idx, axis=0)
                for r, src in steps:
                    pyr[r] = resize_area(pyr[src], r)
                labels = run_cascade_on_pyramid(
                    pyr, casc.model_fns, casc.thresholds, casc.reps,
                    caps)[0]
                return labels, {r: pyr[r] for r in out_res}
            return fn
        return self._slab_runner(
            ("flush", casc.key, tuple(in_res), tuple(out_res), width),
            make)

    def _slab_width(self, n_valid: int, cap: int | None = None) -> int:
        """Module-level ``slab_width`` bound to this engine's chunk."""
        return slab_width(n_valid, self.chunk if cap is None else cap)

    def _stage_blocks(self, lanes: list, width: int, base_hw: int):
        """Pad each lane's undetermined rows to a common chunk-multiple
        length and commit one image block per shard device
        (pmap-sharded, so every later superstep gathers device-locally
        with only tiny index slabs crossing the host boundary). Eager
        backend keeps the block host-side. NOTE: this stages the whole
        undetermined partition per shard — O(rows/shards) device memory,
        not the serial engine's O(chunk); corpora beyond device memory
        need windowed staging (ROADMAP: multi-host sharding)."""
        m = max((len(u) for u in lanes), default=1)
        L = max(self.chunk, -(-m // self.chunk) * self.chunk)
        block = np.zeros((width, L, base_hw, base_hw, 3), np.float32)
        for j, ids in enumerate(lanes):
            if len(ids):
                block[j, :len(ids)] = self.images[ids]
        if not self.jit:
            return block
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        devs = list(dict.fromkeys(self.devices))[:width]
        mesh = Mesh(np.array(devs), ("shard",))
        return jax.device_put(block,
                              NamedSharding(mesh, PartitionSpec("shard")))

    def _lockstep(self, cascades, plan: ShardPlan, stores, stats,
                  monitor=None):
        """Stage-synchronous shard execution: every superstep stacks one
        bucketed index-slab per shard and issues a single pmap dispatch
        over the shard devices. Images are staged device-side once per
        group; only labels and the small non-base pyramid levels cross
        the host boundary. Host-side routing walks cached labels between
        stages, exactly like the serial engine — including the lazy
        level schedule (level_schedule): later-stage-only levels are
        first-touch derived inside the stage's flush dispatch and
        shipped back only when a later stage carries them."""
        needed, union_res = stage_needs(cascades, self.images.shape[1])
        for sh in stats.shards:     # the STATIC union level set, same
            sh.pyramid_levels = union_res    # as the serial shard unit
        schedule = level_schedule(cascades, self.images.shape[1],
                                  self.local.lazy)
        width = min(plan.n_shards, max(len(set(self.devices)), 1))
        accepted: list[np.ndarray] = []

        for g0 in range(0, plan.n_shards, width):
            group = list(range(g0, min(g0 + width, plan.n_shards)))
            accepted += self._run_group(cascades, plan, group, width,
                                        stores, stats, needed, schedule,
                                        monitor)
        return accepted

    def _run_group(self, cascades, plan, group, width, stores, stats,
                   needed, schedule, monitor=None):
        import jax.numpy as jnp

        from repro.core.transforms import resize_area

        ingest_set, carry, derive = schedule

        k = len(cascades)
        chunk = self.chunk
        base_hw = self.images.shape[1]
        accepted: list[np.ndarray] = []

        # ---- presplit: rows whose outcome the seeded store already
        # determines (a cached 0, or cached 1s through every stage)
        # never enter the pipeline — a fully-cached re-run issues ZERO
        # dispatches and stages no images
        lanes = []
        for si in group:
            ids = plan.shards[si]
            walking = np.ones(len(ids), bool)   # on an all-cached-1 path
            unknown = np.zeros(len(ids), bool)  # hit a -1 while walking
            for casc in cascades:
                c = stores[si].lookup(casc.key, ids)
                unknown |= walking & (c < 0)
                walking &= c == 1
            if walking.any():
                accepted.append(ids[walking])
            lanes.append(ids[unknown])
            # cache-determined rows still count as stage traffic (all
            # served from the store), keeping stats comparable with the
            # serial backend, which walks them through route()
            at = ~unknown
            for s, casc in enumerate(cascades):
                if not at.any():
                    break
                st = stats.shards[si].stages[s]
                n = int(at.sum())
                st.rows_in += n
                st.rows_cached += n
                at &= stores[si].lookup(casc.key, ids) == 1
        if not any(len(u) for u in lanes):
            return accepted

        block = self._stage_blocks(lanes, width, base_hw)
        for piece in getattr(block, "addressable_shards", ()):
            j = piece.index[0].start or 0
            if j < len(group):
                stats.staged_devices[group[j]] = piece.device
        # worklists[s][j]: (ids, pos, rows) segments awaiting evaluation
        # at stage s; pos indexes the lane's staged image block so the
        # base level is regathered device-side instead of host-carried
        worklists: list[list[list]] = [[[] for _ in group]
                                       for _ in range(k)]

        def count_levels(si, res, n):
            lr = stats.shards[si].level_rows
            for r in res:
                lr[r] = lr.get(r, 0) + n

        def route(j, stage, ids, pos, rows):
            si = group[j]
            while len(ids):
                if stage == k:
                    accepted.append(ids)
                    return
                casc = cascades[stage]
                st = stats.shards[si].stages[stage]
                st.rows_in += len(ids)
                cached = stores[si].lookup(casc.key, ids)
                known = cached >= 0
                st.rows_cached += int(known.sum())
                unk = ~known
                if unk.any():
                    sub = {r: rows[r][unk] for r in carry[stage]
                           if r in rows}
                    missing = [r for r in carry[stage] if r not in rows]
                    if missing:
                        # cache-skip backfill, exactly the serial
                        # engine's feed(): rows that hopped over earlier
                        # stages on cached labels never saw those
                        # stages' flush-time derivation — pool their
                        # carry levels straight from base
                        imgs = jnp.asarray(self.images[ids[unk]])
                        for r in missing:
                            sub[r] = np.asarray(resize_area(imgs, r))
                        count_levels(si, missing, int(unk.sum()))
                    worklists[stage][j].append((ids[unk], pos[unk], sub))
                keep = known & (cached == 1)
                ids, pos = ids[keep], pos[keep]
                rows = {r: v[keep] for r, v in rows.items()}
                stage += 1

        # ---- ingest: fused pyramid + FULL cascade 0, lockstep ---------
        casc0 = cascades[0]
        out_res = tuple(carry[1]) if k > 1 else ()
        ingest = self._ingest_runner(casc0, out_res, width)
        n_steps = max(math.ceil(len(u) / chunk) for u in lanes if len(u))
        for t in range(n_steps):
            segs = [u[t * chunk:(t + 1) * chunk] for u in lanes]
            b = self._slab_width(max(len(s) for s in segs))
            idx = np.zeros((width, b), np.int32)
            for j, seg in enumerate(segs):
                idx[j, :len(seg)] = t * chunk + np.arange(len(seg))
            labels_all, levels = ingest(block, jnp.asarray(idx))
            labels_all = np.asarray(labels_all)
            levels = {r: np.asarray(v) for r, v in levels.items()}
            stats.supersteps += 1
            for j, si in enumerate(group):
                nv = len(segs[j])
                if not nv:
                    continue
                sh = stats.shards[si]
                sh.chunks += 1
                count_levels(si, ingest_set, nv)
                st = sh.stages[0]
                ids = segs[j]
                pos = t * chunk + np.arange(nv)
                st.rows_in += nv
                cached = stores[si].lookup(casc0.key, ids)
                known = cached >= 0
                st.rows_cached += int(known.sum())
                lab = labels_all[j, :nv]
                unk = ~known
                if unk.any():
                    # the fused kernel scored the whole slab; only the
                    # genuinely-unknown rows count as evaluations, and
                    # cached labels always win for routing
                    stores[si].record(casc0.key, ids[unk], lab[unk])
                    st.rows_evaluated += int(unk.sum())
                    st.batches += 1
                    if monitor is not None:
                        # stage-0 slabs see the unfiltered shard stream
                        monitor.observe(casc0.key, lab[unk],
                                        marginal=True)
                use = np.where(known, cached, lab)
                keep = use == 1
                route(j, 1, ids[keep], pos[keep],
                      {r: levels[r][j, :nv][keep] for r in out_res})

        # ---- stages 1..k-1: flush worklists in lockstep slabs ---------
        for s in range(1, k):
            casc = cascades[s]
            # host-carried small levels; the device program first-touch
            # derives derive[s] (and regathers base when the cascade or
            # a derivation reads it) — exactly the serial flush()
            need_base = (base_hw in casc.resolutions
                         or bool(derive[s]))
            in_res = tuple(carry[s]) + ((base_hw,) if need_base else ())
            down_carry = tuple(r for r in carry[s]
                               if s + 1 < k and r in needed[s + 1])
            out_dev = tuple(r for r in derive[s]
                            if s + 1 < k and r in needed[s + 1])
            flush = self._flush_runner(casc, base_hw, in_res, out_dev,
                                       width)
            pend = []
            for j in range(len(group)):
                segs = worklists[s][j]
                if segs:
                    ids = np.concatenate([a for a, _, _ in segs])
                    pos = np.concatenate([p for _, p, _ in segs])
                    rows = {r: np.concatenate([rw[r]
                                               for _, _, rw in segs])
                            for r in carry[s]}
                else:
                    ids = np.empty(0, np.int64)
                    pos = np.empty(0, np.int64)
                    rows = {}
                pend.append((ids, pos, rows))
            n_steps = max((math.ceil(len(p[0]) / chunk) for p in pend),
                          default=0)
            for t in range(n_steps):
                sl = slice(t * chunk, (t + 1) * chunk)
                segs = [(p[0][sl], p[1][sl]) for p in pend]
                b = self._slab_width(max(len(x) for x, _ in segs))
                idx = np.zeros((width, b), np.int32)
                small = {r: np.zeros((width, b, r, r, 3), np.float32)
                         for r in carry[s]}
                for j, (sids, spos) in enumerate(segs):
                    if not len(sids):
                        continue
                    idx[j, :len(sids)] = spos
                    for r in carry[s]:
                        small[r][j, :len(sids)] = pend[j][2][r][sl]
                labels_all, dev_levels = flush(
                    block, jnp.asarray(idx),
                    {r: jnp.asarray(v) for r, v in small.items()})
                labels_all = np.asarray(labels_all)
                dev_levels = {r: np.asarray(v)
                              for r, v in dev_levels.items()}
                stats.supersteps += 1
                for j, si in enumerate(group):
                    sids, spos = segs[j]
                    nv = len(sids)
                    if not nv:
                        continue
                    st = stats.shards[si].stages[s]
                    lab = labels_all[j, :nv]
                    stores[si].record(casc.key, sids, lab)
                    st.rows_evaluated += nv
                    st.batches += 1
                    count_levels(si, derive[s], nv)
                    if monitor is not None:
                        monitor.observe(casc.key, lab, marginal=False)
                    keep = lab == 1
                    down = {r: pend[j][2][r][sl][keep]
                            for r in down_carry}
                    for r in out_dev:
                        down[r] = dev_levels[r][j, :nv][keep]
                    route(j, s + 1, sids[keep], spos[keep], down)
        return accepted
