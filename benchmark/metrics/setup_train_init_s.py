"""Reader: benchmark/setup_spans.py."""

from benchmark.setup_spans import setup_train_init_s as read  # noqa: F401
