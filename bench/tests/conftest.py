"""CPU checks of the benchmark's yardstick and rehearsals of its cells.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They run on the CPU at small sizes; the repository's own tests
(``testpaths = tests``) do not collect them.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "tools"))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
