"""The benchmark's own CPU self-checks (``python -m pytest bench/tests``).
They run on the CPU at small widths; nothing here times anything."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]
