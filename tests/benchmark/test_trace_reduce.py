"""The reduction from a profiler trace to device numbers, on hand-built
traces in the profiler's own format."""

import pytest

from benchmark import trace_reduce


def _profile(planes: str):
    import jax

    pd = jax.profiler.ProfileData
    return pd.from_serialized_xspace(pd.text_proto_to_serialized_xspace(planes))


def _line(name: str, events) -> str:
    """events: (metadata id, start us, duration us)."""
    body = "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * 1e6)} "
        f"duration_ps: {int(d * 1e6)} }}\n"
        for m, s, d in events
    )
    return f'lines {{ id: 1 name: "{name}" timestamp_ns: 0\n{body}}}\n'


_NAMES = {1: "while.1", 2: "fusion.3", 3: "copy.7", 4: "host.span"}
_META = "".join(
    f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }}\n'
    for k, v in _NAMES.items()
)


def _plane(name: str, *lines: str) -> str:
    return f'planes {{ id: 1 name: "{name}"\n{"".join(lines)}{_META}}}\n'


# while.1 [0,50) holds fusion.3 [10,30) and copy.7 [30,40); fusion.3
# again [40,45) inside, and copy.7 alone [100,140): busy 50 + 40 us.
_OPS = [(1, 0, 50), (2, 10, 20), (3, 30, 10), (2, 40, 5), (3, 100, 40)]


def test_overlapping_intervals_merge_and_self_times_sum_to_busy():
    got = trace_reduce.reduce_profile(
        _profile(
            _plane("/device:TPU:0", _line("XLA Ops", _OPS))
            + _plane("/host:CPU", _line("python", [(4, 45, 60)]))
        ),
        window_s=200e-6,
    )
    assert got.chips == 1
    assert got.busy_s == pytest.approx(90e-6, abs=1e-12)
    assert 1.0 - got.busy_s / got.window_s == pytest.approx(0.55)
    assert got.op_self_s == pytest.approx(
        {"while.1": 15e-6, "fusion.3": 25e-6, "copy.7": 50e-6}, abs=1e-12
    )
    assert sum(got.op_self_s.values()) == pytest.approx(got.busy_s)
    # The one gap, [50,100), has its middle inside host.span [45,105).
    assert got.idle_gap_s == pytest.approx({"host.span": 50e-6}, abs=1e-12)
    assert got.breakdown()["device_ops"][0] == ["copy.7", pytest.approx(50e-6)]


def test_window_defaults_to_the_span_of_the_device_events():
    got = trace_reduce.reduce_profile(
        _profile(_plane("/device:TPU:0", _line("XLA Ops", _OPS)))
    )
    assert got.window_s == pytest.approx(140e-6)
    assert got.idle_gap_s == pytest.approx({"(no host span)": 50e-6})


def test_busy_is_the_mean_over_chips():
    got = trace_reduce.reduce_profile(
        _profile(
            _plane("/device:TPU:0", _line("XLA Ops", [(2, 0, 10)]))
            + _plane("/device:TPU:1", _line("XLA Ops", [(2, 0, 30)]))
        ),
        window_s=100e-6,
    )
    assert got.chips == 2
    assert got.busy_s == pytest.approx(20e-6)


@pytest.mark.parametrize(
    "planes, what",
    [
        (_plane("/host:CPU", _line("python", [(4, 0, 10)])), "no device plane"),
        (_plane("/device:TPU:0", _line("Steps", [(2, 0, 10)])), "XLA Ops"),
        (_plane("/device:TPU:0", _line("XLA Ops", [])), "no operation ran"),
    ],
)
def test_a_trace_without_device_operations_is_an_error_not_idle(planes, what):
    with pytest.raises(trace_reduce.TraceError, match=what):
        trace_reduce.reduce_profile(_profile(planes), window_s=1.0)
