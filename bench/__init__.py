"""Chip benchmark of the reservoir serving engine, driven by data.

One run of one cell::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``bench/workloads/<cell>.json``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix.  The metrics a cell
reports are the entries of ``BENCHMARK.json``; each is read by its own module
``bench/metrics/<metric>.py``.  Adding a configuration, a cell or a metric
adds files and entries; no module here changes.
"""
