"""Reader: benchmark/trinity_scopes.py."""

from benchmark.trinity_scopes import core_roofline_pct as read  # noqa: F401
