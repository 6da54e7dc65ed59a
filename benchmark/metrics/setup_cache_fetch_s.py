"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import setup_cache_fetch_s as read  # noqa: F401
