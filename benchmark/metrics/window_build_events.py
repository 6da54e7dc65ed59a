"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import window_build_events as read  # noqa: F401
