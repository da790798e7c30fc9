"""A tiny copy of the benchmark's catalog, for driving the harness on the
CPU: the same cells, traffic kinds and readers at 32 px frames."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def level(layers, res, color):
    return {"conv_layers": layers, "conv_nodes": 16, "dense_nodes": 16,
            "resolution": res, "color": color}


PREDICATES = [{"name": "p0", "levels": [level(1, 8, "rgb")]},
              {"name": "p1", "levels": [level(2, 16, "gray")]},
              {"name": "p2", "levels": [level(1, 4, "r")]}]


def tiny_catalog(tmp: Path, *, rows: int = 256):
    """Write BENCHMARK.json and a bench directory under ``tmp`` that name
    the real cells over 32 px configurations; returns the root."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = tmp / "bench"
    shutil.copytree(BENCH / "traffic", bench / "traffic")
    shutil.copytree(BENCH / "metrics", bench / "metrics")
    (bench / "configs").mkdir()
    for entry in spec["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        cfg.update(frame_hw=32, corpus_rows=rows, reference_block=32,
                   predicates=PREDICATES)
        cfg["pipeline"] = dict(cfg["pipeline"], chunk=32, capacity_rows=16384)
        cfg["cuts"] = dict(cfg["cuts"], calibration_rows=128)
        (tmp / entry["file"]).write_text(json.dumps(cfg))
    for path in (bench / "traffic").glob("*.json"):
        t = json.loads(path.read_text())
        t["feed_rows"] = 64
        path.write_text(json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
