"""The general load generators that traffic files parameterise.

A traffic file names its ``kind``; each kind is a load class here with
four steps, which the harness calls in order:

* ``setup(seconds)``: frames, weights, thresholds and the program's objects,
  made from the seed, and every program shape warmed up;
* ``window(seconds) -> {end-to-end metric: value}``: the measured load;
* ``record() -> dict``: what the per-layer readers may read of the run,
  read before the program's state is freed by ``release()``;
* ``check(control=None) -> (attempted, failed, numbers)``: the answers the
  timed path produced against the plain reference (``bench/reference.py``)
  as the numbers the configuration's ``check_limits`` bound, and the rows
  whose answers pass those limits; with ``control`` naming a lower
  precision, the reference computed in it stands in the program's place.

``ingest_stream`` feeds the corpus's frames, in order and cycled, to one
``engine/ingest.IngestPipeline`` in blocks of ``feed_rows``, each block
as new rows of one stream, until the window has passed.
"""
from __future__ import annotations

import resource
import sys
import time
from contextlib import contextmanager

import numpy as np

from bench import data, reference

CLOCK = time.perf_counter


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Spans:
    """Host spans around the harness's calls into the program, kept in
    memory on the host clock as (name, start s, end s); each also opens a
    profiler annotation of the same name under ``bench.``."""

    def __init__(self):
        self.events: list = []

    @contextmanager
    def __call__(self, name: str):
        t0 = CLOCK()
        with annotate("bench." + name):
            yield
        self.events.append((name, t0, CLOCK()))


class IngestStream:
    """Frames arriving at the ingest-time index, scored by every
    predicate's first cascade level as they come."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.preds = config["predicates"]
        self.hw = int(config["frame_hw"])
        self.n = int(config["corpus_rows"])
        self.pipe = config["pipeline"]
        self.block = int(config.get("reference_block", 256))
        self.feed_rows = int(traffic["feed_rows"])
        if self.n % self.feed_rows:
            raise ValueError("corpus_rows must be a multiple of feed_rows")
        self.spans = Spans()

    # ------------------------------------------------------------ set-up --
    def _frames_of(self, seed: int):
        def get(lo, hi):
            rows = np.arange(lo, lo + self.block)
            rows[hi - lo:] = hi - 1
            return data.device_frames(seed, rows, self.hw)
        return get

    def reference(self, precision: str, seed: int | None = None,
                  n: int | None = None):
        """First-level scores (P, n) and detector signatures of frames
        0..n of ``seed`` (the run's by default)."""
        return reference.score_frames(
            self._frames_of(self.seed if seed is None else seed),
            self.n if n is None else n, self.block, self.preds, self.params,
            precision, int(self.pipe["skip_res"]))

    def setup(self, seconds: float) -> None:
        from repro.engine.ingest import IngestPipeline

        t = CLOCK()
        self.frames = data.make_frames(self.seed, self.n, self.hw)
        log(f"frames: {self.n} x {self.hw}px made in {CLOCK() - t:.2f} s")
        t = CLOCK()
        wseed = int(self.config["weights_seed"])
        self.params = data.make_weights(wseed, self.preds)
        fit = self.config["cuts"]
        calib, _ = self.reference("highest", wseed,
                                  int(fit["calibration_rows"]))
        self.cuts = [data.quantile_cuts(s, fit["low_quantile"],
                                        fit["high_quantile"])
                     for s in calib]
        self.cascades = [data.build_cascade(i, p, self.params[i], c)
                         for i, (p, c) in enumerate(zip(self.preds,
                                                        self.cuts))]
        log(f"weights and cuts: {CLOCK() - t:.2f} s")
        p = self.pipe
        self.capacity = int(p["capacity_rows"])
        self.pipeline = IngestPipeline(
            self.cascades, self.capacity, chunk=int(p["chunk"]),
            skip=bool(p["skip"]), skip_threshold=float(p["skip_threshold"]),
            skip_res=int(p["skip_res"]), top_k=p["top_k"],
            prune_margin=float(p["prune_margin"]), int8=False)
        self.fed = 0
        t = CLOCK()
        with self.spans("warmup"):
            self._feed()
        log(f"warm-up: {self.fed} frames in {CLOCK() - t:.2f} s")

    def _feed(self) -> None:
        if self.fed + self.feed_rows > self.capacity:
            raise RuntimeError(f"the stream passed the index's capacity of "
                               f"{self.capacity} rows")
        lo = self.fed % self.n
        self.pipeline.ingest(self.frames[lo:lo + self.feed_rows],
                             np.arange(self.fed, self.fed + self.feed_rows))
        self.fed += self.feed_rows

    # ------------------------------------------------------------ window --
    def window(self, seconds: float) -> dict:
        stats = self.pipeline.stats
        fed0, refs0 = self.fed, stats.refs
        laps = []
        faults0 = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        t0 = CLOCK()
        while True:
            t = CLOCK()
            with self.spans("ingest"):
                self._feed()
            laps.append(CLOCK() - t)
            self.window_s = CLOCK() - t0
            if self.window_s >= seconds:
                break
        faults = resource.getrusage(resource.RUSAGE_SELF).ru_minflt - faults0
        self.frames_in = self.fed - fed0
        self.refs_in = stats.refs - refs0
        per = max(1, len(laps) // 8)
        log(f"window: {self.frames_in} frames ({self.refs_in} scored, "
            f"{stats.skipped} aliased in all) in {self.window_s:.3f} s; "
            f"{faults} minor page faults; feeds of {self.feed_rows} frames, "
            f"mean seconds per group of {per}: " + ", ".join(
                f"{np.mean(laps[i:i + per]):.4f}"
                for i in range(0, len(laps), per)))
        return {"ingest_frames_per_s": self.frames_in / self.window_s}

    def record(self) -> dict:
        heads = {p["levels"][0]["resolution"] for p in self.preds[1:]}
        return {"window_s": self.window_s, "frames": self.frames_in,
                "refs": self.refs_in, "chunk": self.pipeline.chunk,
                "base": self.hw,
                # the first predicate's level runs in the fused kernel,
                # which emits the levels the other predicates' heads read
                "kernel_levels": sorted(heads - {self.hw}, reverse=True),
                "stage0": self.preds[0]["levels"][0]}

    def release(self) -> None:
        self.index = self.pipeline.index
        self.pipeline = None
        self.frames = None

    # ------------------------------------------------------------- check --
    def check(self, control: str | None = None):
        """Every frame the stream fed (warm-up and window) against the
        reference: its skip decision, and for each predicate its score,
        decided label and candidate flag. Returns (frames, frames whose
        widest gap passes ``gap_max``, {"gap_max": widest gap})."""
        ids = np.arange(self.fed)
        frame = ids % self.n
        scores, sigs = self.reference("highest")
        thr = float(self.pipe["skip_threshold"])
        margin = float(self.pipe["prune_margin"])
        diffs = reference.stream_diffs(sigs[frame])
        want = reference.index_answers(reference.aliases(diffs, thr),
                                       scores[:, frame], self.cuts, margin)
        if control is None:
            idx = self.index
            got = {"alias": idx.alias[ids],
                   "scores": np.stack([idx.scores[c.concept][ids]
                                       for c in self.cascades]),
                   "decided": np.stack([idx.decided.column(c.key)[ids]
                                        for c in self.cascades]),
                   "candidate": np.stack([idx.candidates[c.concept][ids]
                                          for c in self.cascades]),
                   "indexed": idx.indexed[ids]}
        else:
            ctrl, _ = self.reference(control)
            got = reference.index_answers(want["alias"], ctrl[:, frame],
                                          self.cuts, margin)
        gaps = reference.answer_gaps(got, want, diffs, thr, self.cuts,
                                     margin)
        limit = float(self.config["check_limits"]["gap_max"])
        return len(ids), int((gaps > limit).sum()), \
            {"gap_max": float(gaps.max())}


KINDS = {"ingest_stream": IngestStream}
