"""A numeric column uniform on ``[lo, hi)``, float32."""


def make(spec, rows, rng):
    import numpy as np
    return rng.uniform(spec["lo"], spec["hi"], rows).astype(np.float32)


def add(table, name, values):
    table.add_numeric(name, values)
