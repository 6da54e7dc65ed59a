"""Reader: benchmark/trinity_scopes.py."""

from benchmark.trinity_scopes import share_pct as read  # noqa: F401
