"""The chip benchmark: one command, driven by `BENCHMARK.json` and the data
files beside this package.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives it:

    bench/configs/<config>.json      sizes as run, source, cuts, driver
    bench/traffic/<traffic>.json     parameters for one generator
    bench/generators/<generator>.py  turns a traffic file and a seed into inputs
    bench/workloads/<cell>.json      the limits that decide `correct`
    bench/drivers/<driver>.py        set-up, measured window, comparison
    bench/metrics/<metric>.py        reads one per-layer metric

The yardstick (peaks, FLOP and byte counts, trace reduction, the plain
references) lives here too, so that the program under test cannot move it.
"""
