"""Chip benchmark: one cell (configuration x traffic mix) per run.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Cells, metrics and bounds are declared in ``BENCHMARK.json`` at the
checkout root; everything a cell needs is found by name under this
directory (``configs/``, ``traffic/``, ``metrics/``, ``references/``).
"""
