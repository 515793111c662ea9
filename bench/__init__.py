"""Chip benchmark of the Recoil decode service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on.  A cell
names a configuration (``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and a chip count; its metrics are read
by one small reader per metric (``bench/end_to_end/<name>.py``,
``bench/layer_metrics/<name>.py``).  A new cell, configuration, mix or
per-layer metric is new files plus an entry in ``BENCHMARK.json``.
"""
