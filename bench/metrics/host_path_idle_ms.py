"""Device-idle time per batch during which the serving host path holds
the device: idle time of the traced window while a ``freyja.engine.*`` or
``freyja.executor.*`` span is open (``program_spans.idle_split``). The
rest of ``device_idle_share`` is the worker waiting for arrivals
(``freyja.scheduler.wait`` / ``coalesce``), delivery and the collector.
Batches are counted as pipeline modules, as ``program_device_ms`` counts
them."""
LAYER = "engine + executor host path (service/engine.py, exec/executor.py)"
SOURCE = "program_span"
UNIT = "ms"
MOVES = "p50_ms"


def read(run):
    from bench import program_spans

    trace = program_spans.of(run)
    if trace is None or not program_spans.spans(trace):
        return None
    n = program_spans.batches(trace)
    _, host_path = program_spans.idle_split(trace)
    return host_path / n * 1e3 if n else None
