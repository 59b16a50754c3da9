"""Tests of the chip benchmark, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They put the benchmark's directory and the program's ``src`` on the path,
as ``run.py`` does.
"""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))
