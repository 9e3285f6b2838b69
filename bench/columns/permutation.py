"""A numeric column of the row ids in random order, as a data set's own
order is: exact in float32 below 2**24 rows."""


def make(spec, rows, rng):
    import numpy as np
    if rows > 1 << 24:
        raise ValueError("a permutation column needs rows <= 2**24")
    return rng.permutation(rows).astype(np.float32)


def add(table, name, values):
    table.add_numeric(name, values)
