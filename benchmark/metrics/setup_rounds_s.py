"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import setup_rounds_s as read  # noqa: F401
