"""On-chip benchmark of the SwarmSGD system: swarm training and serving.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once and prints one JSON result line.
Everything a cell needs is data found by name: its configuration
(`bench/configs/<config>.json`), its traffic (`bench/traffic/<traffic>.json`,
which names the job), its correctness limits (`bench/limits/<cell>.json`),
the job driver (`bench/jobs/<job>.py`) and one reader per per-layer metric
(`bench/metrics/<metric>.py`).
"""
