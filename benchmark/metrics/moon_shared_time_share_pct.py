"""Reader: benchmark/moonlight_scopes.py."""

from benchmark.moonlight_scopes import share_pct as read  # noqa: F401
