"""On-chip benchmark of VP serving and the paper's uplink equalizer.

`python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json`.  Everything a cell uses is found by
name: its configuration under `configs/`, its traffic mix under
`traffic/`, its driver under `drivers/` and each per-layer metric's
reader under `metrics/`.
"""
