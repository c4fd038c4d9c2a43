"""The benchmark's tests run on the CPU and import the harness from the
checkout root (``bench`` is a package there) and the program from
``src``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
