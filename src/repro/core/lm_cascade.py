"""The paper's technique as a first-class feature for the assigned LM
architectures (DESIGN.md §5): a *predicate cascade over language models*.

A contains-concept predicate over text/media is scored by asking a model
to choose between a YES token and a NO token; P(yes) is the probabilistic
output of Def. 7. A cheap model (small arch, truncated context — the
token-domain analogue of the paper's resolution scaling) answers first;
inputs whose score falls inside (p_low, p_high) fall through to the
trusted model. Thresholds are calibrated per model with the SAME
Algorithm 1 used for the CNN cascades — the core library is
classifier-agnostic, exactly as the paper claims (§VIII).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.thresholds import compute_thresholds
from repro.models.factory import Model


@dataclass
class LMLevel:
    model: Model
    params: object
    yes_token: int
    no_token: int
    max_context: int | None = None   # truncation = representation knob
    p_low: float | None = None
    p_high: float | None = None


def yes_share(pair):
    """(..., 2) yes and no logits -> P(yes) (...): their two-way softmax."""
    return jax.nn.softmax(pair.astype(jnp.float32), -1)[..., 0]


def lm_predicate_score(level: LMLevel, tokens: np.ndarray) -> np.ndarray:
    """tokens (B, S) -> P(yes) (B,). Uses the last-position logits."""
    t = tokens
    if level.max_context is not None and t.shape[1] > level.max_context:
        t = t[:, -level.max_context:]
    logits, _, _ = level.model.forward(
        level.params, {"tokens": jnp.asarray(t)}, remat_policy="none",
        logits_last_only=True)
    pair = logits[:, -1, jnp.asarray([level.yes_token, level.no_token])]
    return np.asarray(yes_share(pair))


def calibrate(levels: Sequence[LMLevel], tokens, truth,
              prec_target: float = 0.95) -> None:
    """Algorithm 1 per level (final level keeps None thresholds)."""
    for lvl in levels[:-1]:
        scores = lm_predicate_score(lvl, tokens)
        lvl.p_low, lvl.p_high = compute_thresholds(
            lambda _: scores, None, truth, prec_target)


def run_lm_cascade(levels: Sequence[LMLevel], tokens) -> tuple:
    """-> (labels (B,), level_used (B,)). Per-batch early exit with the
    same semantics as the CNN cascades."""
    b = tokens.shape[0]
    labels = np.zeros(b, np.int32)
    used = np.full(b, len(levels) - 1, np.int32)
    active = np.ones(b, bool)
    for li, lvl in enumerate(levels):
        if not active.any():
            break
        scores = lm_predicate_score(lvl, tokens)
        final = lvl.p_low is None
        if final:
            labels[active] = (scores >= 0.5)[active]
            used[active] = li
            active[:] = False
        else:
            certain = active & ((scores <= lvl.p_low)
                                | (scores >= lvl.p_high))
            labels[certain] = (scores >= lvl.p_high)[certain]
            used[certain] = li
            active &= ~certain
    return labels, used


def expected_cost(levels: Sequence[LMLevel], level_used,
                  infer_s: Sequence[float]) -> float:
    """Mean seconds/query given per-level inference costs: every input
    pays levels 0..used (the cascade cost model of §VI, inference-only)."""
    per = np.cumsum(np.asarray(infer_s))
    return float(per[np.asarray(level_used)].mean())
