import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
for p in (ROOT, str(Path(ROOT) / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
