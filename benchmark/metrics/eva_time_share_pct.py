"""Reader: benchmark/evabyte_scopes.py."""

from benchmark.evabyte_scopes import share_pct as read  # noqa: F401
