"""prep_ms.{open,closed} — query prep and prep cache
(``serving/engine.py`` ``_prep_for``, ``core/scoring.py``
``prepare_queries``), the program's span ``ash.prep``.

Median duration of one fused call's query prep (row hashing, cache
lookup, ``prepare`` on the device, the cache fill), over the calls whose
``ash.prep`` lies wholly inside the traced window."""
from bench import spans


def read(ctx):
    return spans.prep_ms(ctx.trace)
