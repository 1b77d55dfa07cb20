"""tpu-bft's benchmark: `python3 perfbench/run.py --workload <cell> ...`.
See perfbench/README.md; the contract is BENCHMARK.json at the repo root."""
