"""Chip benchmark of the edge tier's real-decode path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything a cell needs is found by name: ``configs/<config>.json``
(model sizes), ``families/<model_type>.py`` (the block the configuration's
``model_type`` names: its program configuration, weights' layout, float32
forward and counts), ``traffic/<traffic>.json`` (the request mix),
``cells/<cell>.json`` (the limits of the correctness check) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
