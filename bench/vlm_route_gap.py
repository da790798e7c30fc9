"""How far the program's router strays from the plain reference's: the
reading behind the Kimi-VL configuration's ``route_tie``.

    python3 bench/vlm_route_gap.py --seeds 11,12,13 [--frames 64]

For each seed it scores the cell's first ``--frames`` frames both ways
(the configuration's weights, from its ``weights_seed``) and takes out,
at every MoE layer, every token's router scores plus correction bias,
the values the top 6 are chosen by: the program's from
``models/ffn.router_scores``, the reference's from
``reference_kimi_vl._route``, each through a host callback that changes
nothing it computes. It logs per seed the widest difference, the tokens
whose chosen set differs and the reference's 6th-7th distance at them,
and prints as its last line one JSON object with the widest difference
over all seeds. Run from the root of a checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def tapped(fn, sink):
    """``fn`` returning (scores, biased), with ``biased`` also sent to
    ``sink``, layer after layer in the order the program runs them."""
    import jax

    def wrapped(*args):
        scores, biased = fn(*args)
        jax.debug.callback(lambda b: sink.append(np.asarray(b)), biased,
                           ordered=True)
        return scores, biased
    return wrapped


def by_layer(calls: list, layers: int) -> list:
    """Callbacks (calls x layers, each (..., E)) -> per layer (T, E), the
    calls' tokens in order."""
    return [np.concatenate([c.reshape(-1, c.shape[-1])
                            for c in calls[i::layers]])
            for i in range(layers)]


def compare(got: list, want: list, k: int) -> dict:
    """Per-token router values of the program and the reference, layer
    by layer: the widest difference, the tokens whose top-k set differs,
    and the reference's k-th to (k+1)-th distance (least over all
    tokens, widest over the differing ones)."""
    diff, flips, flip_tie, least_tie = 0.0, 0, 0.0, np.inf
    for g, w in zip(got, want):
        diff = max(diff, float(np.abs(g - w).max()))
        sw = -np.sort(-w, -1)
        tie = sw[:, k - 1] - sw[:, k]
        least_tie = min(least_tie, float(tie.min()))
        flip = np.any(np.sort(np.argsort(-g, -1)[:, :k], -1)
                      != np.sort(np.argsort(-w, -1)[:, :k], -1), -1)
        flips += int(flip.sum())
        if flip.any():
            flip_tie = max(flip_tie, float(tie[flip].max()))
    return {"max_diff": diff, "flips": flips, "flip_tie_max": flip_tie,
            "least_tie": least_tie}


def read(catalog, workload: str, seeds: list, n: int) -> dict:
    """The reading over ``seeds``, ``n`` frames each (a whole number of
    chunks): ``compare``'s numbers, widest (least for ``least_tie``)."""
    from bench import data
    from bench import reference_kimi_vl as ref
    from bench.loads import log
    from repro.models import ffn
    import jax.numpy as jnp

    cell = catalog.cell(workload)
    config = catalog.config(cell["config"])
    traffic = catalog.traffic(cell["traffic"])
    got, want = [], []
    saved = ffn.router_scores, ref._route, dict(ref._FNS)
    ffn.router_scores = tapped(ffn.router_scores, got)
    ref._route = tapped(ref._route, want)
    ref._FNS.clear()
    try:
        load = catalog.load_kind(traffic["kind"])(config, traffic, seeds[0])
        load.make_vlm(int(config["weights_seed"]))
        model = load.vlm_model()
        layers = load.m["layers"] - load.m["dense_layers"]
        chunk = int(load.pipe["chunk"])
        worst: dict = {}
        for seed in seeds:
            got.clear()
            want.clear()
            for lo in range(0, n, chunk):
                frames = data.device_frames(seed, np.arange(lo, lo + chunk),
                                            load.hw)
                scores, _ = model.scores(model.tower(frames),
                                         jnp.asarray(load.question_ids))
                np.asarray(scores)
            load.vlm_reference("highest", seed, n)
            found = compare(by_layer(got, layers), by_layer(want, layers),
                            load.m["top_k"])
            log(f"seed {seed}: {n} frames, {layers} MoE layers: {found}")
            for key, v in found.items():
                worst[key] = (min if key == "least_tie" else max)(
                    worst.get(key, v), v)
        return worst
    finally:
        ffn.router_scores, ref._route = saved[:2]
        ref._FNS.clear()
        ref._FNS.update(saved[2])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="ingest-vlm-distinct")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--frames", type=int, default=64)
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import harness

    harness.use_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    worst = read(harness.Catalog(ROOT), args.workload, seeds, args.frames)
    print(json.dumps(dict(worst, seeds=seeds, frames=args.frames)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
