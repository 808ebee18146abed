"""Run by hand: `python -m pytest benchmarks/tests -q` (not part of tests/)."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
