"""One runner an entry point, chosen by the configuration's `entry`.
`run(cell, seed, seconds, trace, devices, t_start)` drives one cell once
and returns a `harness.RunResult`. A runner holds the loop, the clock, the
sample and the comparison; the serving runner finds the program it drives
by the configuration's `program` (`benchmark/programs/`)."""
