"""The chip benchmark of cilium-tpu (``python -m benchmark.run``).

Everything here is the yardstick: traffic generation (``worlds/``,
``traffic/``), the drivers of the timed window (``kinds/``), the
readers of per-layer metrics (``metrics/``), the trace reduction
(``trace.py``), the peaks table (``peaks.json``), the plain reference
(``reference/``) and the comparison that decides ``correct``
(``compare.py``). From the program it takes only the system under test
(``program.py`` is the one module that imports ``cilium_tpu``) and its
counters.
"""
