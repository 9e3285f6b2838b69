"""Or: the rows that pass any child (``{"or": [node, ...]}``)."""


def bind(body, ctx):
    return tuple(ctx.bind(d) for d in body)


def build(params, ctx):
    from repro.core import query as Q
    return Q.Or.of(*[ctx.build(c) for c in params])


def mask(params, view, band, sub):
    out = sub(params[0])
    for c in params[1:]:
        out = out | sub(c)
    return out
