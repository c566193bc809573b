"""Device time per batch of the tiered plan's full-lake coarse pass: the
self time of the ops the pipeline traces under the ``coarse`` stage scope
(nested in ``candidates``: the proxy fill over the dequantized sidecar,
the digest probe, the survivor-block expansion and the survivor top-k;
``exec/stages.py``, ``exec/executor.py``), read from each op's ``tf_op``
metadata in the traced window (``program_spans``). Batches are counted as
pipeline modules, as ``program_device_ms`` counts them.

Two kinds of op are read with care:

* XLA's TopK splitter rewrites the survivor top-k over (Q, C) into a
  reshape and two sorts that carry no metadata. An op with no label
  counts with the last op before it in the same module that has a scope
  path.
* The compiler labels an argument's relayout copy with the bare name of
  the argument, and ``_local_tiered``'s digest keys are called
  ``coarse``. Such a label is no scope path and never counts: only
  ``coarse`` directly inside ``candidates`` is the scope, and a program
  without the scope reads None."""
import bisect
import dataclasses

LAYER = "tiered coarse pass (exec/stages.py, exec/executor.py)"
SOURCE = "device_trace"
UNIT = "ms"
MOVES = "p50_ms"

SCOPE = "coarse"
PARENT = "candidates"


def scoped(tf_op: str) -> bool:
    """Whether an op's ``tf_op`` path holds ``coarse`` inside
    ``candidates``."""
    path = tf_op.rstrip(":").split("/")
    return any(a == PARENT and b == SCOPE for a, b in zip(path, path[1:]))


def relabelled(trace):
    """``trace`` with each device op labelled ``coarse`` where it counts
    (by its own scope path, or, for an op with no label, by the last scope
    path before it in its module) and unlabelled elsewhere."""
    from bench import tracing

    device = [e for e in trace.device if e.line != tracing.OPS_LINE]
    for plane in {e.plane for e in trace.device}:
        starts = sorted(e.start for e in trace.device if e.plane == plane
                        and e.line == tracing.MODULES_LINE)
        ops = sorted((e for e in trace.device if e.plane == plane
                      and e.line == tracing.OPS_LINE),
                     key=lambda e: (e.start, -e.dur))
        module, label = -1, ""
        for e in ops:
            m = bisect.bisect_right(starts, e.start)
            if m != module:
                module, label = m, ""
            own = e.stats.get("tf_op", "")
            if "/" in own:
                label = own
            counts = scoped(label) and (not own or own == label)
            device.append(dataclasses.replace(
                e, stats={"tf_op": SCOPE} if counts else {}))
    return tracing.Trace(device=device, host=trace.host)


def read(run):
    from bench import program_spans

    trace = program_spans.of(run)
    if trace is None:
        return None
    n = program_spans.batches(trace)
    s = program_spans.scope_self_seconds(relabelled(trace), SCOPE)
    return s / n * 1e3 if n and s is not None else None
