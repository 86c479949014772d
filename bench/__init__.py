"""On-chip benchmark of the NeuroAda repo, driven by ``BENCHMARK.json``.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the chip it is started on. Everything that belongs to one
configuration, traffic mix or per-layer metric sits in a file of its own
under ``bench/`` and is found by the name ``BENCHMARK.json`` gives it.
"""
