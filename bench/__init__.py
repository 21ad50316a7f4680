"""End-to-end benchmark of RF-to-image serving on the chip.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once; see run.py.
"""
