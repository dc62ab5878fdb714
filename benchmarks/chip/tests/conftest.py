"""Puts the checkout root and ``src`` on the path, so that the harness's
tests import ``benchmarks.chip`` and ``repro`` as ``run.py`` does."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
