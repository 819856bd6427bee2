"""Chip benchmark: one cell of ``BENCHMARK.json`` run once on a TPU.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: a configuration in ``configs/<name>.json``,
a traffic mix in ``traffic/<name>.json`` driven by ``traffic/<kind>.py``,
a metric in ``metrics/<name>.py`` and a served system in
``entries/<entry>.py``. The yardstick (generators, the reduction of the
trace, the peak table, the FLOP count, the plain reference and the
comparison that decides ``correct``) lives here and nowhere in the
program under test.
"""
