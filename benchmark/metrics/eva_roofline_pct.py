"""Reader: benchmark/evabyte_scopes.py."""

from benchmark.evabyte_scopes import eva_roofline_pct as read  # noqa: F401
