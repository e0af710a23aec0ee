"""The benchmark (BENCHMARK.json at the repo root is its table of cells).

Only ``perfbench/adapters/*`` import the program under test; everything
else here is the yardstick and stays independent of it.
"""
