"""The reduction from a profiler trace to busy time, operation
categories and named idle gaps: exact on a trace built by hand, and
sane on a small trace recorded on a TPU v5e (``data/tiny_v5e.xplane.pb``:
five calls of a jitted sort + gather of 2**20 floats under a
``bench.window`` annotation)."""

import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))

# times in microseconds; the window is [0, 100)
HOST = [("bench.window", 0, 100), ("plan.fetch", 30, 20),
        ("np.asarray(jax.Array)", 35, 10), ("bench.q1", 60, 40)]
DEVICE = {
    "/device:TPU:0": [
        ("%sort.3 = (s32[8]{0}, f32[8]{0:T(1024)S(1)}) sort(s32[8]{0} %p)",
         0, 30),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kCustom", 50, 10),
        ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %b), kind=kLoop", 60, 20),
        # runs past the window's end: only its first 10 us count
        ("%all-to-all.1 = u32[8]{0} all-to-all(u32[8]{0} %w)", 90, 20),
    ],
    "/device:TPU:1": [
        ("%sort.1 = s32[8]{0} sort(s32[8]{0} %p)", 0, 50),
        # overlaps the sort: busy counts the union
        ("%copy-start.4 = (f32[8]{0}) copy-start(f32[8]{0} %c)", 40, 20),
    ],
}


def _plane(pid, name, line, events):
    metas = "".join(
        f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
        f'name: "{n}" }} }}\n' for i, (n, _, _) in enumerate(events))
    evs = "".join(
        f"events {{ metadata_id: {i + 1} offset_ps: {s * 1_000_000} "
        f"duration_ps: {d * 1_000_000} }}\n"
        for i, (_, s, d) in enumerate(events))
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
            f"{metas}}}\n")


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    text = _plane(1, "/host:CPU", "main", HOST)
    for i, (name, events) in enumerate(DEVICE.items()):
        text += _plane(i + 2, name, "XLA Ops", events)
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(text),
                                       span_names={"plan.fetch"})


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(100e-6)
    assert summary.devices == 2
    # TPU:0 busy 30 + 10 + 20 + 10 = 70 us; TPU:1 the union [0, 60) = 60
    assert summary.busy_s == pytest.approx(65e-6)


def test_categories_are_the_traces_own(summary):
    ops = summary.ops
    assert ops["sort"] == pytest.approx((30 + 50) / 2 * 1e-6)
    assert ops["fusion:kCustom"] == pytest.approx(5e-6)
    assert ops["fusion:kLoop"] == pytest.approx(10e-6)
    assert ops["all-to-all"] == pytest.approx(5e-6)
    assert ops["copy-start"] == pytest.approx(10e-6)
    assert summary.op_seconds("^sort") == pytest.approx(40e-6)


def test_idle_gaps_named_by_host_span(summary):
    # TPU:0 idles over [30, 50) inside plan.fetch (innermost: the
    # transfer at its middle) and [80, 90) inside bench.q1; TPU:1 over
    # [60, 100), whose middle (80) lies in bench.q1
    idle = summary.idle
    assert idle["plan.fetch: np.asarray(jax.Array)"] == pytest.approx(10e-6)
    assert idle["bench.q1"] == pytest.approx((10 + 40) / 2 * 1e-6)
    assert sum(idle.values()) == pytest.approx(100e-6 - summary.busy_s)
    b = summary.breakdown()
    assert b["idle_gaps"][0][0] == "bench.q1"
    assert b["device_ops"][0] == ["sort", pytest.approx(40e-6)]


def test_missing_window_or_device_is_an_error():
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", "main", HOST[1:])
    dev = _plane(2, "/device:TPU:0", "XLA Ops", DEVICE["/device:TPU:0"])
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(host + dev))
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_profile(ProfileData.from_text_proto(
            _plane(1, "/host:CPU", "main", HOST)))


def test_recorded_v5e_trace():
    s = trace_reduce.reduce_file(
        os.path.join(HERE, "data", "tiny_v5e.xplane.pb"))
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s < 0.1
    assert sum(s.ops.values()) == pytest.approx(s.busy_s, rel=1e-6)
    # five sorts of 2**20 floats and five gathers (kCustom fusions)
    assert s.ops["sort"] > 0 and s.ops["fusion:kCustom"] > s.ops["sort"]
    assert sum(s.idle.values()) == pytest.approx(s.window_s - s.busy_s)
