"""Test-suite bootstrap: puts ``src/`` on sys.path so ``PYTHONPATH=src``
is not required."""
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)          # for ``import benchmarks.analytic``
