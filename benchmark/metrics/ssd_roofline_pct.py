"""Reader: benchmark/nemotronh_scopes.py (the arithmetic is in its docstring)."""

from benchmark.nemotronh_scopes import ssd_roofline_pct as read  # noqa: F401
