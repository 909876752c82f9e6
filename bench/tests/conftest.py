"""Tests of the benchmark harness, run with ``python -m pytest bench/tests``
(outside the repository's ``testpaths``).  They run on the CPU, at small
sizes."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
