import os
import sys

# The benchmark's tests run on the CPU; they import the harness as the
# benchmark's command does (its own directory, then the program's src).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(_BENCH, "..", "..", "src"), _BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
