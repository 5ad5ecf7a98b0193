"""The benchmark harness: one cell of ``BENCHMARK.json`` per process.

``python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell on the chips of the machine it starts on
and prints one JSON result line last.  Everything a cell needs is found
by name: its configuration under ``configs/``, its traffic mix under
``traffic/``, the system adapter its configuration names under
``systems/`` and each per-layer metric's reader under ``metrics/``.
"""
