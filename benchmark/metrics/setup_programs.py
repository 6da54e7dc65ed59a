"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import setup_programs as read  # noqa: F401
