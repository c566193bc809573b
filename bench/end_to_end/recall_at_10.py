"""Share of the exact scan's top 10 that the served answers hold, over
every answer the window gave for a seeded sample of its distinct query
columns (``check.judge``)."""
UNIT = "ratio"


def read(run):
    return run.verdict["recall"]
