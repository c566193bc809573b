"""CPU tests of the readers of the program's own spans and stage scopes
(``bench/program_spans.py``, ``metrics/host_path_idle_ms.py``,
``metrics/score_device_ms.py``): exact values on a synthetic event list,
the protobuf decoding of op metadata, and recorded chip traces."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, program_spans, tracing  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e3                                   # ns per microsecond
DEV, HOST = "/device:TPU:0", "/host:CPU"


class _Run:
    def __init__(self, trace):
        self.trace = trace
        self.program_trace = trace


def _read(name, trace):
    return harness.Layout().reader("metrics", name).read(_Run(trace))


def _dev(line, name, a, b, tf_op=None):
    stats = {} if tf_op is None else {"tf_op": tf_op}
    return tracing.Event(DEV, line, name, a * US, (b - a) * US, stats)


def _span(name, a, b, **stats):
    return tracing.Event(HOST, "python", name, a * US, (b - a) * US, stats)


@pytest.fixture
def synthetic():
    """Two pipeline batches in a 1,000 us window. Device idle: [0, 100],
    [300, 500], [700, 710], [720, 1000]. The worker waits, runs batch 1
    (pin, execute with put / dispatch / fetch, finalize, deliver with a
    collection inside), then nothing is open over [480, 500] and
    [700, 715], and it waits again from 715."""
    mod = "jit__local_pruned(42)"
    device = [
        _dev(tracing.MODULES_LINE, mod, 100, 300),
        _dev(tracing.MODULES_LINE, mod, 500, 700),
        _dev(tracing.OPS_LINE, "%while", 100, 300,
             "jit(_local_pruned)/score/while:"),
        _dev(tracing.OPS_LINE, "%fusion.41", 120, 200,
             "jit(_local_pruned)/score/while/body/closed_call/gather:"),
        _dev(tracing.OPS_LINE, "%sort", 200, 260,
             "jit(_local_pruned)/candidates/top_k:"),
        _dev(tracing.OPS_LINE, "%sort.1", 500, 700,
             "jit(_local_pruned)/merge/top_k:"),
        _dev(tracing.OPS_LINE, "%copy", 710, 720),
    ]
    host = [
        _span(tracing.WINDOW_SPAN, 0, 1000),
        _span("bench.executor.execute", 0, 1000),   # not the program's
        _span("freyja.scheduler.wait", 0, 50),
        _span("freyja.scheduler.batch", 50, 480, batch=1, n=8, bucket=8),
        _span("freyja.engine.pin", 50, 60),
        _span("freyja.engine.execute", 60, 320, batch=1),
        _span("freyja.executor.put", 60, 80),
        _span("freyja.executor.dispatch", 80, 98, program="_local_pruned",
              aot=1),
        _span("freyja.executor.fetch", 98, 310),
        _span("freyja.engine.finalize", 320, 400),
        _span("freyja.scheduler.deliver", 400, 470, n=8),
        _span("freyja.gc", 420, 440, generation=0),
        _span("freyja.scheduler.wait", 715, 1000),
    ]
    return tracing.Trace(device=device, host=host)


def test_idle_time_by_innermost_span_is_exact(synthetic):
    by_span, host_path = program_spans.idle_split(synthetic)
    want_us = {"freyja.scheduler.wait": 50 + 280, "freyja.engine.pin": 10,
               "freyja.executor.put": 20, "freyja.executor.dispatch": 18,
               "freyja.executor.fetch": 2 + 10, "freyja.engine.execute": 10,
               "freyja.engine.finalize": 80, "freyja.scheduler.deliver": 50,
               "freyja.gc": 20, "freyja.scheduler.batch": 10,
               program_spans.NO_SPAN: 20 + 10}
    assert by_span.keys() == want_us.keys()
    for name, us in want_us.items():
        assert by_span[name] == pytest.approx(us * 1e-6, abs=1e-12), name
    idle = synthetic.window_s() - synthetic.busy_s()
    assert sum(by_span.values()) == pytest.approx(idle, abs=1e-12)
    # engine / executor spans open over [50, 100] and [300, 400]
    assert host_path == pytest.approx(150e-6, abs=1e-12)


def test_host_path_idle_ms_is_exact(synthetic):
    assert program_spans.batches(synthetic) == 2
    assert _read("host_path_idle_ms", synthetic) == \
        pytest.approx(150e-3 / 2, abs=1e-9)


def test_score_device_ms_counts_self_time_under_the_scope(synthetic):
    # the while op's own 200 us less its body ops (80 + 60), plus the
    # gather inside it (80); the candidates sort inside it is not the
    # scorer's, nor is the merge
    assert program_spans.scope_self_seconds(synthetic, "score") == \
        pytest.approx(140e-6, abs=1e-12)
    assert program_spans.scope_self_seconds(synthetic, "merge") == \
        pytest.approx(200e-6, abs=1e-12)
    assert _read("score_device_ms", synthetic) == \
        pytest.approx(140e-3 / 2, abs=1e-9)


def test_readers_are_silent_without_the_programs_spans():
    """A program that opens no ``freyja.*`` span and traces no stage
    scope (the trace recorded before they existed) gives both readers
    nothing to read: they return None and raise nothing."""
    old = tracing.load_json(os.path.join(DATA, "trace-canada-65k.json"))
    assert program_spans.spans(old) == []
    assert _read("host_path_idle_ms", old) is None
    assert _read("score_device_ms", old) is None
    assert _read("score_device_ms", None) is None     # untraced run


# ---------------------------------------------------------------------------
# decoding of op metadata from the wire format
# ---------------------------------------------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name, stat_names, events):
    out = _field(2, name)
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    for eid, (ename, stats) in events.items():
        body = _field(1, eid) + _field(2, ename)
        for sid, kind, v in stats:
            body += _field(5, _field(1, sid) + _field(kind, v))
        out += _field(4, _field(1, eid) + _field(2, body))
    out += _field(3, _field(2, "XLA Ops"))            # a line: skipped
    return out


def test_op_metadata_is_decoded_from_the_wire_format(tmp_path):
    stats = {1: "tf_op", 2: "program_id", 3: "jit(f)/merge/top_k:",
             4: "flops"}
    dev = _plane(DEV, stats, {
        7: ("%fusion.1 = f32[8] fusion()",
            [(1, 5, "jit(f)/score/mul:"), (2, 3, 42), (4, 3, 9)]),
        8: ("%sort.2", [(2, 3, 42), (1, 7, 3)]),     # tf_op by reference
        9: ("%copy.1", [(2, 3, 42)]),                # no tf_op
    })
    host = _plane(HOST, {1: "tf_op"}, {1: ("freyja.x", [(1, 5, "a/b:")])})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, dev) + _field(1, host) + _field(4, "h"))
    assert program_spans.op_metadata(str(path)) == {
        (DEV, "42", "%fusion.1 = f32[8] fusion()"): "jit(f)/score/mul:",
        (DEV, "42", "%sort.2"): "jit(f)/merge/top_k:"}


def test_ops_are_labelled_within_their_program():
    meta = {(DEV, "1", "%fusion"): "jit(a)/score/mul:",
            (DEV, "2", "%fusion"): "jit(b)/merge/add:"}
    device = [_dev(tracing.MODULES_LINE, "jit_a(1)", 0, 10),
              _dev(tracing.MODULES_LINE, "jit_b(2)", 20, 30),
              _dev(tracing.OPS_LINE, "%fusion", 2, 4),
              _dev(tracing.OPS_LINE, "%fusion", 22, 24),
              _dev(tracing.OPS_LINE, "%other", 25, 26)]
    program_spans._label_ops(device, meta)
    assert [e.stats.get("tf_op") for e in device[2:]] == \
        ["jit(a)/score/mul:", "jit(b)/merge/add:", None]


# ---------------------------------------------------------------------------
# a recorded chip trace (TPU v5 lite, canada-65k.steady, 0.3 s)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip_spans():
    return tracing.load_json(os.path.join(DATA, "spans-canada-65k.json"))


def _grid(trace, t0, t1):
    """Per 1-us cell of the window: the label of the innermost device op
    (painted longest first, so nested ops overwrite their parents)."""
    import numpy as np

    n = int((t1 - t0) / US) + 1
    label = np.full(n, "", dtype=object)
    for e in sorted(trace.ops(), key=lambda e: -e.dur):
        a = int(max(e.start - t0, 0) / US)
        b = int(min(e.end - t0, t1 - t0) / US)
        label[a:b] = e.stats.get("tf_op", "-")
    return label


def test_both_readers_on_a_recorded_chip_trace(chip_spans):
    import numpy as np

    t0, t1 = chip_spans.window()
    n = program_spans.batches(chip_spans)
    assert n >= 3
    names = {e.name for e in program_spans.spans(chip_spans)}
    assert {"freyja.scheduler.batch", "freyja.engine.execute",
            "freyja.executor.fetch"} <= names
    label = _grid(chip_spans, t0, t1)
    # the scorer's self time again, on a 1 us grid
    score_us = sum(1 for x in label if "score" in x.split("/"))
    got = _read("score_device_ms", chip_spans)
    assert got == pytest.approx(score_us / 1e3 / n, rel=0.01)
    assert 30 < got < 70
    # idle while the engine or executor host path is open, on the grid
    open_ = np.zeros(label.size, bool)
    for e in program_spans.spans(chip_spans):
        if e.name.startswith(program_spans.HOST_PATH):
            a = int(max(e.start - t0, 0) / US)
            open_[a:int(min(e.end - t0, t1 - t0) / US)] = True
    idle_open_us = int(np.sum((label == "") & open_))
    got = _read("host_path_idle_ms", chip_spans)
    assert got == pytest.approx(idle_open_us / 1e3 / n, rel=0.02, abs=0.01)
    # nearly all idle time of the window lies under some program span
    by_span, _ = program_spans.idle_split(chip_spans)
    assert by_span.get(program_spans.NO_SPAN, 0.0) <= \
        0.05 * sum(by_span.values())
