"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

Run it as ``python bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  Everything that belongs to
one configuration, traffic mix or per-layer metric lives in a file of its
own (``configs/``, ``traffic/``, ``metrics/``), found by the name that
``BENCHMARK.json`` gives it.
"""
