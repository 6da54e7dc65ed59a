"""Reader: benchmark/zaya_scopes.py."""

from benchmark.zaya_scopes import cca_mix_roofline_pct as read  # noqa: F401
