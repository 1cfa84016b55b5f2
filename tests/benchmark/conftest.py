"""Tests of the benchmark's harness (``benchmark/``).  The repo root goes
on ``sys.path`` so ``benchmark`` imports as a package; platform and
virtual devices come from ``tests/conftest.py``."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
