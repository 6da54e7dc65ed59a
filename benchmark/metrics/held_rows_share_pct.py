"""Reader: benchmark/nemotronh_scopes.py."""

from benchmark.nemotronh_scopes import held_rows_share_pct as read  # noqa: F401
