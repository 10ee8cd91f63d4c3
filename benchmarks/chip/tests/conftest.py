"""The benchmark's own tests: run from the repository root with

  JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests

(the repository's tier-1 run collects only ``tests/``)."""

import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)
