"""Reader: benchmark/evabyte_scopes.py."""

from benchmark.evabyte_scopes import eval_rows_run_share_pct as read  # noqa: F401
