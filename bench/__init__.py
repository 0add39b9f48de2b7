"""Chip benchmark of the vector-search service, driven by BENCHMARK.json.

Entry point: ``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``. A cell names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``); every metric is a reader in
``metrics/<name>.py``. The yardstick (data, traffic, reference, comparison,
trace reduction, peaks) lives here and imports nothing of the program.
"""
