"""N.R: a numeric range ``lo <= column <= hi``, exact.

Bound from ``{"column", "pass_fraction"}``: ``hi`` is the column's
largest value and ``lo`` lets ``round(pass_fraction * rows)`` rows pass
(exactly, for distinct values), once per table."""


def filter_bounds(column, pass_fraction):
    """``(lo, hi)`` such that ``lo <= column <= hi`` holds for
    ``round(pass_fraction * rows)`` rows (exactly, for distinct values)."""
    import numpy as np
    m = max(1, int(round(pass_fraction * len(column))))
    srt = np.sort(column)
    return float(srt[len(srt) - m]), float(srt[-1])


def bind(body, ctx):
    name, frac = body["column"], float(body["pass_fraction"])
    key = ("nr", name, frac)
    if key not in ctx.cache:
        ctx.cache[key] = filter_bounds(ctx.table.numeric[name], frac)
    lo, hi = ctx.cache[key]
    return {"column": name, "lo": lo, "hi": hi}


def build(params, ctx):
    from repro.core import query as Q
    return Q.NR(params["column"], params["lo"], params["hi"])


def mask(params, view, band, sub):
    c = view.column(params["column"])
    return ((c[None, :] >= params["lo"][:, None])
            & (c[None, :] <= params["hi"][:, None]))
