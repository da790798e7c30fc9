"""The paper's technique on the ASSIGNED LM architectures: a predicate
cascade where a cheap truncated-context LM (token-domain analogue of the
paper's resolution scaling) answers contains-token(YES) queries and only
uncertain inputs fall through to the trusted LM. Thresholds come from the
same Algorithm 1 as the CNN cascades.

  PYTHONPATH=src python examples/lm_cascade_predicate.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import smoke_config  # noqa: E402
from repro.core.lm_cascade import (LMLevel, calibrate, expected_cost,  # noqa: E402
                                   lm_predicate_score, run_lm_cascade)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models.factory import build_model  # noqa: E402
from repro.train.optimizer import adamw  # noqa: E402

YES, NO = 7, 13


def make_task(vocab, n, seq, seed=0):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, vocab, (n, seq)).astype(np.int32)
    toks[toks == YES] = YES + 1
    labels = rng.integers(0, 2, n).astype(np.int32)
    for i in np.where(labels == 1)[0]:
        toks[i, rng.integers(0, seq - 1, size=3)] = YES
    return toks, labels


def train_level(arch, toks, labels, steps, seed=0):
    cfg = smoke_config(arch).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt = adamw(3e-3)
    state = opt.init(params)

    @jax.jit
    def step(params, state, tb, yb):
        def loss_fn(p):
            logits, _, _ = model.forward(p, {"tokens": tb},
                                         remat_policy="none",
                                         logits_last_only=True)
            pair = logits[:, -1, jnp.asarray([YES, NO])]
            logp = jax.nn.log_softmax(pair.astype(jnp.float32), -1)
            return -jnp.mean(jnp.where(yb == 1, logp[:, 0], logp[:, 1]))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, state, _ = opt.update(grads, state, params)
        return params, state, loss

    rng = np.random.default_rng(seed)
    for _ in range(steps):
        idx = rng.integers(0, len(toks), 16)
        params, state, _ = step(params, state, jnp.asarray(toks[idx]),
                                jnp.asarray(labels[idx]))
    return LMLevel(model=model, params=params, yes_token=YES, no_token=NO)


def main():
    vocab = smoke_config("deepseek-7b").vocab_size
    toks, labels = make_task(vocab, 360, 24)
    print("training cheap level (minitron smoke, 12-token context)...")
    small = train_level("minitron-4b", toks[:200, -12:], labels[:200], 150)
    small.max_context = 12
    print("training trusted level (deepseek-7b smoke, full context)...")
    trusted = train_level("deepseek-7b", toks[:200], labels[:200], 220,
                          seed=1)
    calibrate([small, trusted], toks[200:280], labels[200:280],
              prec_target=0.8)
    print(f"calibrated thresholds: p_low={small.p_low:.2f} "
          f"p_high={small.p_high:.2f}")

    ev_t, ev_y = toks[280:], labels[280:]
    preds, used = run_lm_cascade([small, trusted], ev_t)
    acc = (preds == ev_y).mean()
    acc_trusted = ((lm_predicate_score(trusted, ev_t) >= 0.5)
                   == ev_y).mean()
    cost = expected_cost([small, trusted], used, [1.0, 30.0])
    print(f"\ncascade accuracy {acc:.3f} (trusted-only {acc_trusted:.3f})")
    print(f"routed early: {(used == 0).mean():.0%}; expected cost "
          f"{cost:.1f} units vs trusted-only 31.0 "
          f"({31.0 / cost:.1f}x cheaper)")


if __name__ == "__main__":
    use_compile_cache()
    main()
