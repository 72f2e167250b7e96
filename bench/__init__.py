"""On-chip benchmark of the MCT-filtered serving path (see ``run.py``).

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:
``configs/<config>.json`` (+ ``configs/<config>.py``, its plain reference),
``traffic/<mix>.json`` and ``metrics/<metric>.py``.
"""
