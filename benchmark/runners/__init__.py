"""One runner an entry point, chosen by the configuration's `entry`.
`run(cell, seed, seconds, trace, devices, t_start)` drives one cell once
and returns a `harness.RunResult`."""
