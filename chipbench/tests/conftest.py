"""The benchmark's own checks, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
