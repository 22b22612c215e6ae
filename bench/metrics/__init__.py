"""Metric readers, one file each, named as the metric: ``read(ctx)`` returns
the metric's value, or None where the run has nothing to read it from.

``ctx`` holds ``cell`` (the run's ``harness.Cell``), ``records`` (one dict
per answered request of the window), ``setup_s``, ``setup`` (what set-up
measured), ``trace`` (the reduced profiler trace, trace runs only),
``peaks`` (the device's row of ``peaks.json``), ``config`` and
``compiles``. Shared helpers live in ``common.py``.
"""
