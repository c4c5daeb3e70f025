"""The on-chip benchmark of babble-tpu (see BENCHMARK.json and PERF.md).

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<mix>.json``, ``drivers/<driver>.py`` and
``metrics/<metric>.py``.  The yardstick (traffic generation, trace
reduction, the plain reference and the comparison behind ``correct``)
lives here and imports nothing of the program.
"""
