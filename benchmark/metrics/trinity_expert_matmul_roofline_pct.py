"""Reader: benchmark/trinity_scopes.py."""

from benchmark.trinity_scopes import expert_matmul_roofline_pct as read  # noqa: F401
