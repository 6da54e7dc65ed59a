"""Reader: benchmark/round_spans.py."""

from benchmark.round_spans import padded_step_share_counted_pct as read  # noqa: F401
