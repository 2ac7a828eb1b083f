"""The benchmark's own tests: run on the CPU from the checkout's root with
``python -m pytest bench/tests``."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
