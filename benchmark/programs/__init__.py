"""The program under test, built as a deployment builds it: one file a
program, chosen by the configuration's `program`.
`build_engine(config, seed)` returns `(model, engine, shapes)`: the engine
the serving runner drives, the model it serves (held while the engine
lives, freed with it) and the `{name: (shape, dtype)}` of the weights it
was given, from which the runner makes them again for the plain reference.
What a program cannot run as the configuration states it, it refuses here
(`BenchmarkError`); the runner knows no model's sizes."""
