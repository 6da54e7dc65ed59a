"""Reader: benchmark/zaya_scopes.py."""

from benchmark.zaya_scopes import rows_share_pct as read  # noqa: F401
