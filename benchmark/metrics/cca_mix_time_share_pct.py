"""Reader of a share of device time by ZAYA1's layer's scope classes: benchmark/zaya_scopes.py."""

from benchmark.zaya_scopes import share_pct as read  # noqa: F401
