"""The on-chip benchmark of the SU3 stack: one cell per (configuration, traffic).

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the TPU it finds and prints one JSON result line.  Cells,
configurations, traffic drivers, references and per-layer metrics are files
found by name (see ``bench/harness.py``).
"""
