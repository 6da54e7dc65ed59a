"""Reader: benchmark/trinity_scopes.py."""

from benchmark.trinity_scopes import rows_held_share_pct as read  # noqa: F401
