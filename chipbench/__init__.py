"""Chip benchmark of the sharded Shortcut-EH index.

Driven by ``BENCHMARK.json`` at the repository root: a cell names a
configuration file (``configs/``), a traffic mix (``traffic/<name>.json``)
and its metrics (``metrics/<name>.py``), and the harness finds each by
that name.  ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell on the chip.
"""
