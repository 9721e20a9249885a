"""On-chip benchmark of the repository's training and serving paths.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once and prints one JSON line.  Cells, configurations and
per-layer metrics are data: ``workloads/<cell>.json``,
``configs/<config>.json`` and ``metrics/<metric>.py``.
"""
