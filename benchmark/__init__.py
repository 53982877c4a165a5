"""The chip benchmark: harness, data and yardstick (see BENCHMARK.json, PERF.md).

Everything a later PR may not change lives here: traffic generation, the
reduction from traces to metrics, the table of peaks, the functions that
count a kernel's operations and bytes, the plain references and the
comparison that decides `correct`. From the program it takes only the two
entry points under test (`HybridTrainer`, `ServingEngine`). What differs
from model to model is a file found by a name that the configuration
gives: its program's builder (`program`), its plain reference
(`reference`), its whole step's operations (`step_work`) and its published
sizes (`published_as`).
"""
