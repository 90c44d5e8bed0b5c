"""``python3 -m bench --workload <cell> ...``: the same as ``bench/run.py``."""

from bench.run import main

main()
