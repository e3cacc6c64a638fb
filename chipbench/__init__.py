"""Chip benchmark of the MoR trainer and quantized server.

Run one cell with ``python3 -m chipbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root; ``README.md``
beside this file says how cells, configurations, traffic mixes and
metrics are added as files.
"""
