"""Set-up time: from the start of the process to the start of the measured
window — imports, building the engine, weights from the seed, the
reference check, compilation or loading from the compile cache, and the
warm-up drain or epoch. Host clock, taken by the benchmark.
"""

def compute(record):
    return record["setup_s"]
