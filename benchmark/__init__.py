"""The repository's benchmark: one federated-round cell per process.

Everything the yardstick needs lives in this directory (``BENCHMARK.json``
at the repo root lists it under ``paths``): the cohort generator, the
analytic FLOPs, the table of peaks, the device-trace reduction, each
configuration's plain reference forward pass and the comparison that
decides ``correct``. See ``README.md``.
"""
