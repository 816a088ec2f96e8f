"""Chip benchmark of the serving engine.

One command runs one cell once::

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own that the harness finds by name:
``configs/<config>.json``, ``traffic/<mix>.json``, ``cells/<cell>.json``
(the engine settings of a cell) and ``metrics/<metric>.py``.
"""
