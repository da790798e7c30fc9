"""``vlm_ingest_stream``: distinct frames arriving at the ingest-time
index, each scored by the configuration's CNN first levels and by a
vision-language model's yes/no questions (``config["vlm"]``), one
predicate a question.

As ``ingest_stream``: the corpus's frames in order, cycled,
``feed_rows`` at a time, closed loop, into one ``engine/ingest.
IngestPipeline``. The model's weights, its questions and the yes and no
ids come from the configuration's ``weights_seed``
(``bench/reference_kimi_vl.init_params``, in the program's layout); each
question's cuts are the quartiles of its reference scores on that
seed's first ``calibration_rows`` frames.

The check compares every frame fed with the plain references
(``bench/reference.py`` for the CNN columns, ``bench/
reference_kimi_vl.py`` for the questions), through the same
``index_answers``: ``gap_max`` over the CNN columns, ``vlm_gap_max``
over the questions' (a score whose reference routing lies within
``route_tie`` of a tie between the 6th and 7th expert, at any token and
MoE layer of its sequence, is charged the distance to that tie;
``bench/vlm_route_gap.py`` reads how far the program's router values
stray from the reference's, the reading ``route_tie`` is set from).
"""
from __future__ import annotations

from functools import partial

import numpy as np

from bench import data, loads, reference
from bench import reference_kimi_vl as ref_vlm
from bench.loads import CLOCK, log


class Load(loads.IngestStream):
    """Frames scored by CNN first levels and by a VLM's questions."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        # a program without the VLM head fails here, before any work
        from repro.models import kimi_vl  # noqa: F401

        super().__init__(config, traffic, seed)
        self.vlm = config["vlm"]
        self.vlm_block = int(self.vlm["reference_block"])
        self.m = ref_vlm.model_of(config)

    # ------------------------------------------------------------ set-up --
    def vlm_reference(self, precision: str, seed: int | None = None,
                      n: int | None = None):
        """(P(yes) (K, n), routing tie distance (K, n)) of frames 0..n."""
        def get(lo, hi):
            rows = np.arange(lo, lo + self.vlm_block)
            rows[hi - lo:] = hi - 1
            return data.device_frames(self.seed if seed is None else seed,
                                      rows, self.hw)
        return ref_vlm.score_frames(get, self.n if n is None else n,
                                    self.vlm_block, self.vlm_params, self.m,
                                    self.questions, precision)

    def make_vlm(self, wseed: int) -> None:
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(wseed)
        vocab = self.m["vocab"]
        qs = rng.integers(0, vocab, (int(self.vlm["questions"]),
                                     int(self.vlm["question_tokens"])))
        yes, no = rng.choice(vocab, 2, replace=False)
        self.m = dict(self.m, yes=int(yes), no=int(no))
        self.question_ids = qs.astype(np.int32)
        self.questions = jnp.asarray(self.question_ids)
        key = jax.random.fold_in(data.weight_key(wseed), 0x4B56)
        self.vlm_params = jax.jit(partial(
            ref_vlm.init_params, m=self.m, w=self.vlm["weights"]))(key)
        jax.block_until_ready(self.vlm_params)

    def vlm_model(self):
        """The program's model over the weights ``make_vlm`` made."""
        from repro.configs import kimi_vl_a3b
        from repro.models.kimi_vl import KimiVL

        cfg = kimi_vl_a3b.from_hf(self.config, self.config["vision_config"],
                                  self.m["experts"])
        return KimiVL(cfg=cfg, params=self.vlm_params,
                      held=tuple(self.m["held"]), yes=self.m["yes"],
                      no=self.m["no"])

    def setup(self, seconds: float) -> None:
        from repro.core.transforms import Representation
        from repro.engine.ingest import IngestPipeline
        from repro.engine.scan import CompiledCascade
        from repro.models.kimi_vl import VLMQuestion

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
        log(f"CNN weights and cuts: {CLOCK() - t:.2f} s")
        t = CLOCK()
        self.make_vlm(wseed)
        vcal, _ = self.vlm_reference("highest", wseed,
                                     int(self.vlm["calibration_rows"]))
        self.vlm_cuts = [data.quantile_cuts(s, fit["low_quantile"],
                                            fit["high_quantile"])
                         for s in vcal]
        log(f"VLM weights and cuts: {CLOCK() - t:.2f} s; calibration "
            f"scores (min, quartiles, max) per question: " + "; ".join(
                " ".join(f"{v:.4f}" for v in np.quantile(
                    s, [0, 0.25, 0.5, 0.75, 1])) for s in vcal))
        model = self.vlm_model()
        self.cascades = [data.build_cascade(i, p, self.params[i], c)
                         for i, (p, c) in enumerate(zip(self.preds,
                                                        self.cuts))]
        base = len(self.preds)
        self.cascades += [
            CompiledCascade(concept=f"q{k}", cascade_id=(base + k,),
                            reps=[Representation(self.hw, "rgb")],
                            model_fns=[VLMQuestion(model, ids)],
                            thresholds=[(float(lo), float(hi))])
            for k, (ids, (lo, hi)) in enumerate(zip(self.question_ids,
                                                    self.vlm_cuts))]
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

    # ------------------------------------------------------------ window --
    def window(self, seconds: float) -> dict:
        stats = self.pipeline.stats
        tok0, rows0 = stats.vlm_tokens, stats.vlm_expert_rows
        out = super().window(seconds)
        self.vlm_tokens_in = stats.vlm_tokens - tok0
        self.vlm_rows_in = stats.vlm_expert_rows - rows0
        return out

    def record(self) -> dict:
        rec = super().record()
        rec.update(vlm_tokens=self.vlm_tokens_in,
                   vlm_expert_rows=self.vlm_rows_in,
                   questions=int(self.vlm["questions"]),
                   question_tokens=int(self.vlm["question_tokens"]))
        return rec

    # ------------------------------------------------------------- check --
    def _got(self, cascades, ids) -> dict:
        idx = self.index
        return {"alias": idx.alias[ids],
                "scores": np.stack([idx.scores[c.concept][ids]
                                    for c in cascades]),
                "decided": np.stack([idx.decided.column(c.key)[ids]
                                     for c in cascades]),
                "candidate": np.stack([idx.candidates[c.concept][ids]
                                       for c in cascades]),
                "indexed": idx.indexed[ids]}

    def check(self, control: str | None = None):
        """Every frame fed against the references. Returns (frames,
        frames whose gaps pass their limits, {"gap_max", "vlm_gap_max"})."""
        ids = np.arange(self.fed)
        frame = ids % self.n
        seen = min(self.fed, self.n)
        thr = float(self.pipe["skip_threshold"])
        margin = float(self.pipe["prune_margin"])
        scores, sigs = self.reference("highest")
        vscores, ties = self.vlm_reference("highest", n=seen)
        diffs = reference.stream_diffs(sigs[frame])
        alias = reference.aliases(diffs, thr)
        want = reference.index_answers(alias, scores[:, frame], self.cuts,
                                       margin)
        vwant = reference.index_answers(alias, vscores[:, frame],
                                        self.vlm_cuts, margin)
        p = len(self.preds)
        if control is None:
            got = self._got(self.cascades[:p], ids)
            vgot = self._got(self.cascades[p:], ids)
        else:
            ctrl, _ = self.reference(control)
            vctrl, _ = self.vlm_reference(control, n=seen)
            got = reference.index_answers(alias, ctrl[:, frame], self.cuts,
                                          margin)
            vgot = reference.index_answers(alias, vctrl[:, frame],
                                           self.vlm_cuts, margin)
        gaps = reference.answer_gaps(got, want, diffs, thr, self.cuts,
                                     margin)
        own = alias == np.arange(len(alias))
        tie_limit = float(self.vlm["route_tie"])
        vgaps = np.zeros(len(ids))
        tied = 0
        for k, cut in enumerate(self.vlm_cuts):
            pick = {key: (v[k:k + 1] if v.ndim == 2 else v)
                    for key, v in vgot.items()}
            wpick = {key: (v[k:k + 1] if v.ndim == 2 else v)
                     for key, v in vwant.items()}
            g = reference.answer_gaps(pick, wpick, diffs, thr, [cut], margin)
            tie = ties[k, frame]
            near = own & (tie <= tie_limit) & (g > tie)
            tied += int(near.sum())
            vgaps = np.maximum(vgaps, np.where(near, tie, g))
        limits = self.config["check_limits"]
        bad = (gaps > float(limits["gap_max"])) \
            | (vgaps > float(limits["vlm_gap_max"]))
        log(f"VLM check{'' if control is None else ' (' + control + ')'}: "
            f"{tied} answers charged their routing tie distance; widest "
            f"score gap before that "
            f"{np.nanmax(np.abs(vgot['scores'] - vwant['scores'])):.3e}")
        return len(ids), int(bad.sum()), \
            {"gap_max": float(gaps.max()), "vlm_gap_max": float(vgaps.max())}
