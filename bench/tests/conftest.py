"""The benchmark's own tests, run by hand from the repository's root:

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They put ``bench/`` and the program's ``src/`` on the path the way
``bench/run.py`` does, and build tiny in-memory cells that are never listed
in ``BENCHMARK.json``.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
