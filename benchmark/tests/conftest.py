"""The benchmark's own tests: CPU only, one command,
`JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q`. Not part of tier-1."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]
