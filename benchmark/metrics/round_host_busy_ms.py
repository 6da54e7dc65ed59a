"""Reader: benchmark/round_spans.py."""

from benchmark.round_spans import round_host_busy_ms as read  # noqa: F401
