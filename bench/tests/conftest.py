"""The benchmark's own tests, run on the CPU at small sizes:

  JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The repository's tier-1 run (``testpaths = ["tests"]``) does not collect
them."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
