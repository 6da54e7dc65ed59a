"""Reader: benchmark/nemotronh_scopes.py (the arithmetic is in its docstring)."""

from benchmark.nemotronh_scopes import held_expert_matmul_roofline_pct as read  # noqa: F401
