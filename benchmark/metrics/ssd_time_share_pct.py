"""Reader of a share of device time by the hybrid trunk's scope classes: benchmark/nemotronh_scopes.py."""

from benchmark.nemotronh_scopes import share_pct as read  # noqa: F401
