"""The benchmark: one harness driven by the data files beside it.

``BENCHMARK.json`` names the cells.  Each configuration is a JSON file
under ``configs/``, each traffic mix a JSON file under ``traffic/``, each
entry point of the system a module under ``entries/``, each plain
reference a module under ``references/`` and each metric a reader under
``metrics/``.  The harness finds all of them by name.
"""
