"""On-chip benchmark of the ASH serving path.

Run one cell with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the checkout root
names every configuration, traffic mix, cell and metric, and this
package finds each one's file by that name.
"""
