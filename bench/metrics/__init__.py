"""One reader per per-layer metric, found by the metric's name.

Each module defines ``read(ctx) -> float | None``. ``ctx`` carries the
traced window's ``trace`` (``bench/trace.py`` ``TraceSummary``), the
driver's ``counters``, the configuration's ``tm`` fields, the device's
``peaks`` and the ``work`` functions. A reader that finds nothing to read
returns None, and the metric is left out of the result line.
"""
