# The benchmark's modules (harness, reference, traffic/, ...) import one
# another by their plain names, as run.py's own directory on sys.path
# lets them; the tests see them the same way.
import os
import sys

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks",
                     "fpm_bench")
sys.path.insert(0, os.path.abspath(BENCH))
