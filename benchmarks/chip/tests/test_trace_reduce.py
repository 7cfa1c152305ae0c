"""The trace reduction on a synthesised profiler trace with known answers."""
import pytest
from jax.profiler import ProfileData

from harness import trace as tr

MS = 10**9  # picoseconds per millisecond


def _plane(pid, name, lines, names):
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                   for i, n in names.items())
    body = ""
    for lid, (lname, events) in enumerate(lines, 1):
        evs = "".join(f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
                      f"duration_ps: {int(d * MS)} }}\n" for m, s, d in events)
        body += f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0\n{evs}}}\n'
    return f'planes {{ id: {pid} name: "{name}"\n{body}{meta}}}\n'


@pytest.fixture(scope="module")
def trace():
    # device: ops at [10,20) and [15,30) (overlapping: 20 ms busy), and
    # [60,70); the step program spans [10,30) and [60,70).  Host: the
    # window [0,100), "dispatch" over [0,40), "sketch-fold" over [40,80).
    dev = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [(1, 10, 10), (2, 15, 15), (1, 60, 10)]),
        ("XLA Modules", [(3, 10, 20), (3, 60, 10)]),
        ("Steps", [(4, 10, 60)]),
    ], {1: "%fusion.1 = f32[8] fusion(...)", 2: "%custom-call.2 = f32[8] custom-call(), "
        'custom_call_target=\\"tpu_custom_call\\"', 3: "jit_train_step(1)", 4: "0"})
    host = _plane(2, "/host:CPU", [
        ("python3", [(1, 0, 100), (2, 0, 40), (3, 40, 40)]),
    ], {1: tr.WINDOW_SPAN, 2: "dispatch", 3: "sketch-fold"})
    return tr.load(ProfileData.from_text_proto(dev + host))


def test_busy_union_and_idle_share(trace):
    red = tr.reduce(trace, tr.SPAN_NAMES)
    assert red.window_s == pytest.approx(0.100)
    assert red.busy_s == pytest.approx(0.030)  # [10,30) + [60,70)
    assert red.idle_share == pytest.approx(0.70)
    assert red.n_devices == 1


def test_op_and_program_times(trace):
    red = tr.reduce(trace, tr.SPAN_NAMES)
    assert red.ops_matching("tpu_custom_call") == pytest.approx(0.015)
    assert red.ops_matching("%fusion.1") == pytest.approx(0.020)
    assert red.module_s == {"jit_train_step(1)": pytest.approx(0.030)}
    assert red.module_n == {"jit_train_step(1)": 2}


def test_idle_gaps_go_to_the_open_host_span(trace):
    red = tr.reduce(trace, tr.SPAN_NAMES)
    # gaps [0,10) and [30,40) under dispatch, [40,60) under sketch-fold,
    # [70,80) under sketch-fold, [80,100) under no span
    assert red.idle_gaps["dispatch"] == pytest.approx(0.020)
    assert red.idle_gaps["sketch-fold"] == pytest.approx(0.030)
    assert red.idle_gaps[tr.NO_SPAN] == pytest.approx(0.020)
    b = red.breakdown()
    assert b["idle_gaps"][0] == ["sketch-fold", pytest.approx(0.030)]
    assert b["device_ops"][0][0].startswith("%fusion.1")


def test_no_window_or_no_device_reads_nothing(trace):
    assert tr.reduce(tr.Trace(trace.ops, trace.modules, []), tr.SPAN_NAMES) is None
    assert tr.reduce(tr.Trace({}, {}, trace.host), tr.SPAN_NAMES) is None


def test_innermost_span_wins():
    spans = [tr.Event("dispatch", 0, 100), tr.Event("sketch-fold", 20, 10)]
    starts = [0, 20]
    assert tr._span_at(spans, starts, 25) == "sketch-fold"
    assert tr._span_at(spans, starts, 50) == "dispatch"
    assert tr._span_at(spans, starts, 150) == tr.NO_SPAN
