"""Puts the benchmark's own modules and the system under test on the path,
as ``run.py`` does."""
import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]
