"""A numeric column of standard-normal float32 values."""


def make(spec, rows, rng):
    import numpy as np
    return rng.standard_normal(rows, dtype=np.float32)


def add(table, name, values):
    table.add_numeric(name, values)
