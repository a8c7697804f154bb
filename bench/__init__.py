"""Chip benchmark of the TDR query server, driven by ``BENCHMARK.json``.

``bench/cell.py`` runs one cell; every configuration, traffic mix and
per-layer metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``, found by the name the manifest
gives it.  Nothing here imports the program except ``bench/cell.py`` (the
system under test) and ``bench/controls.py`` (the control runs).
"""
