"""Reader: benchmark/zaya_scopes.py."""

from benchmark.zaya_scopes import expert_matmul_roofline_pct as read  # noqa: F401
