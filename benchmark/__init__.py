"""The benchmark of photon_tpu: harness, yardstick and plain reference.

Run as ``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout. ``BENCHMARK.json`` names the
cells; every configuration, traffic mix, per-layer metric and set of limits
is a file of its own under this directory, found by name; so is each
traffic kind (``kinds/``).
"""
