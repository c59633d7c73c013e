"""Chip benchmark of the paged serving path.

One run serves one cell of ``BENCHMARK.json`` (a model configuration under
a traffic mix) on one TPU and prints the cell's metrics; see ``run.py``.
Everything a cell is made of is a file found by name: the configuration
under ``configs/``, its architecture's plug-in under ``archs/`` (by the
configuration's ``model_type``), the traffic mix under ``traffic/``, and
each per-layer metric's reader under ``metrics/``.
"""
