"""The benchmark: harness, traffic, yardsticks and plain reference.

Everything here is measured *against* the program (``eksml_tpu``); only
``harness.py`` and the task modules (``tasks/``) import it.  See ``PERF.md`` and ``BENCHMARK.json``.
"""
