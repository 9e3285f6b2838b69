"""Requests per executed micro-batch over the window: the server's
``stats()`` served divided by batches (serving layer, ``serve/engine.py``)."""


def read(rec):
    st = rec.window.stats
    if not st.get("batches"):
        return None
    return st["served"] / st["batches"]
