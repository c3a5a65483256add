"""The benchmark's own tests: run with `python -m pytest bench/tests`.

They import the benchmark as the package `bench` and the program from
`src/`, as `bench/run.py` does, and run on the CPU.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
