"""Reader of a share of device time by the block's scope classes: benchmark/olmoe_scopes.py."""

from benchmark.olmoe_scopes import share_pct as read  # noqa: F401
