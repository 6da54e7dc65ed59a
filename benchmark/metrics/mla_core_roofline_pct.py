"""Reader: benchmark/moonlight_scopes.py."""

from benchmark.moonlight_scopes import mla_core_roofline_pct as read  # noqa: F401
