"""Device time per batch of the scorer: the self time of the ops the
pipelines trace under the ``score`` stage scope (``exec/stages.py``:
``score_columns``, ``score_streamed``), read from each op's ``tf_op``
metadata in the traced window (``program_spans``). Batches are counted as
pipeline modules, as ``program_device_ms`` counts them."""
LAYER = "scorer (exec/stages.py)"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "p50_ms"


def read(run):
    from bench import program_spans

    trace = program_spans.of(run)
    if trace is None:
        return None
    n = program_spans.batches(trace)
    s = program_spans.scope_self_seconds(trace, "score")
    return s / n * 1e3 if n and s is not None else None
