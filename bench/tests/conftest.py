"""The benchmark's own tests: run with `python -m pytest bench/tests` from
the repository root (CPU only; nothing here loads a TPU library while
modules are imported)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
