"""Tests of the benchmark's own code: ``python -m pytest benchmark/tests``.

They are not part of the repository's tier-1 run (that walks ``tests/``); the
ones that start a server take about a minute each on the sandbox CPU.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
