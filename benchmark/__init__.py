"""The benchmark: cells of BENCHMARK.json run through `benchmark/run.py`.

Everything that decides a number lives here, where a PR that claims a
gain cannot edit it: traffic generation, metric arithmetic, the trace
reduction, the peak table, FLOPs from shapes, the plain reference and
the comparison behind `correct`. From the program the benchmark takes
the system under test (`ServingEngine.run`, `cli.lm.main` +
`Trainer.train_epoch`) and its spans and counters, nothing else.
"""
