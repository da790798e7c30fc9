"""End-to-end driver: train a ~100M-class LM (reduced here to a few-M
smoke config so it runs on this 1-core container; pass --full on a real
fleet) for a few hundred steps with the fault-tolerant runtime —
checkpoints, failure injection + recovery, straggler detection, optional
gradient compression.

  PYTHONPATH=src python examples/train_lm.py --arch mamba2-130m \
      --steps 200 [--compress topk] [--inject-failure 50]
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.train import main  # noqa: E402

if __name__ == "__main__":
    if "--steps" not in " ".join(sys.argv):
        sys.argv += ["--steps", "200"]
    use_compile_cache()
    main()
