"""Seconds from the process's start to the opening of the window: imports,
the model (trained once per checkout, then read from its cache), the lake
made from the seed, ingest, engine open with AOT warmup of the mix's
buckets, and warm-up traffic."""
UNIT = "s"


def read(run):
    return run.setup_s
