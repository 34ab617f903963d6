"""The chip benchmark: cells of BENCHMARK.json, one configuration under one
traffic mix each, run by ``python3 bench/run.py --workload <cell> ...``.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json`` and one reader per per-layer metric in
``metrics/<metric>.py``, or ``metrics/<stem>.py`` for the metrics of one
stem that differ only in their cell-family suffix (``step_ms.chat``).
"""
