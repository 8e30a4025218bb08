"""Chip benchmark of the serving path: cells, traffic, metrics, reference.

Everything a cell needs is found by name: ``BENCHMARK.json`` names the
cell, ``cells/<cell>.json`` its engine sizes and rate, ``configs/<config>.json``
the model, whose ``family`` (``families/<family>.py``) knows its shapes,
program config, served weights and FLOPs and whose ``reference``
(``reference/<name>.py``) computes it plainly, ``traffic/<mix>.json`` the
arrivals and lengths, and ``metrics/<metric>.py`` the reader of each
per-layer metric.  See ``run.py`` for the entry point.
"""
