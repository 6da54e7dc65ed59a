"""Reader: benchmark/moonlight_scopes.py."""

from benchmark.moonlight_scopes import expert_matmul_roofline_pct as read  # noqa: F401
