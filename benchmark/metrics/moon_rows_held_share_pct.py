"""Reader: benchmark/moonlight_scopes.py."""

from benchmark.moonlight_scopes import rows_held_share_pct as read  # noqa: F401
