"""Reader of a number on the round driver's round_log span: benchmark/olmoe_scopes.py."""

from benchmark.olmoe_scopes import span_arg_median as read  # noqa: F401
