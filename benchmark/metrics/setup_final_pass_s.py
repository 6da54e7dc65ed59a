"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import setup_final_pass_s as read  # noqa: F401
