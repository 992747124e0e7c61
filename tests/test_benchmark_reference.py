"""Tier-1's collector of `benchmark/tests/test_reference.py` (the reference
evaluator and the comparison that decides `correct`, held to PR 33's frozen
evaluator): `pytest tests/` does not look under `benchmark/`, so without this
line no command the driver runs holds them (PERF.md 7.6, ROADMAP D15)."""

from benchmark.tests.test_reference import *  # noqa: F401,F403
