"""Reader: benchmark/olmoe_scopes.py (the arithmetic is in its docstring)."""

from benchmark.olmoe_scopes import expert_matmul_roofline_pct as read  # noqa: F401
