"""The benchmark's own tests run on the CPU, by hand:

    python -m pytest chipbench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[1] / "src")]
