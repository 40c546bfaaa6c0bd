"""Puts the repository root on sys.path so `import benchmarks` works from
the tests, whatever directory pytest was started in."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
