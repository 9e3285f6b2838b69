"""V.R: the rows of a vector column within radius ``r`` (L2) of a
held-out pool vector of the request.

Bound from ``{"column", "around", "pass_fraction"}``: the centre is the
request's pool vector named ``around``; ``r`` is fixed per table, the
median over the first 64 pool vectors of the distance to the
``ceil(pass_fraction * rows)``-th nearest row (float64, rounded to
float32 so that the program and the reference test the same radius).

Which side of ``r`` a row lies on is decided by rounding for rows close
to it: the mask takes ``d <= r * (1 + band)``.
"""
PROBES = 64          # pool vectors the radius is the median over
_ROWS = 8            # probes per distance pass
_WINDOW = 1024       # float32 ranks around the target, rechecked in float64


def radius(x, probes, pass_fraction):
    import math
    import numpy as np
    n = len(x)
    m = min(n, max(1, math.ceil(round(pass_fraction * n, 6))))
    xx = np.einsum("ij,ij->i", x, x)
    out = []
    for p0 in range(0, len(probes), _ROWS):
        q = probes[p0:p0 + _ROWS]
        d2 = xx[None, :] + np.einsum("ij,ij->i", q, q)[:, None] \
            - 2.0 * (q @ x.T)
        lo, hi = max(0, m - 1 - _WINDOW), min(n, m + _WINDOW)
        for qi, row in zip(q, d2):
            part = np.argpartition(row, (lo, hi - 1))[lo:hi]
            diff = x[part].astype(np.float64) - qi.astype(np.float64)
            d64 = np.sort(np.einsum("ij,ij->i", diff, diff))
            out.append(math.sqrt(d64[m - 1 - lo]))
    return float(np.float32(np.median(out)))


def bind(body, ctx):
    name, frac = body["column"], float(body["pass_fraction"])
    key = ("vr", name, frac)
    if key not in ctx.cache:
        t = ctx.table
        ctx.cache[key] = radius(t.vectors, t.pool[:PROBES], frac)
    return {"column": name, "around": int(ctx.vectors[body["around"]]),
            "radius": ctx.cache[key]}


def build(params, ctx):
    from repro.core import query as Q
    return Q.VR.of(params["column"], ctx.pool[params["around"]],
                   params["radius"])


def mask(params, view, band, sub):
    d2 = view.sq_dist(params["column"], params["around"])
    r = params["radius"] * (1.0 + band)
    return d2 <= (r * r)[:, None]
