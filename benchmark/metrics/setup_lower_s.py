"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import setup_lower_s as read  # noqa: F401
