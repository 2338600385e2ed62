"""The benchmark's own tests run on the host CPU at small sizes:

    python -m pytest chip_bench/tests

The program's persistent compile cache and program store stay off, so a
test that breaks the program underneath gets a program of its own."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["REPRO_COMPILE_CACHE"] = "0"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
