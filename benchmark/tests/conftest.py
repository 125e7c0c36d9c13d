"""The benchmark's own tests: ``python -m pytest benchmark/tests -q`` from
the root of the checkout, on the CPU. They are not part of the repo's
tier-1 suite (which collects ``tests/`` only)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
