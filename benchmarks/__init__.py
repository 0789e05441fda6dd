"""The training benchmark: `python -m benchmarks.run --workload <cell> ...`.

Everything that decides a number lives here, where a PR that claims a gain
cannot change it: traffic generation, the step recorder, the reduction from
trace to metrics, the table of peaks, the FLOP and byte counts, the plain
references and the comparison that decides `correct`.  See README.md.
"""
