"""Reader of a share of device time by scope class: benchmark/scopes.py."""

from benchmark.scopes import read  # noqa: F401
