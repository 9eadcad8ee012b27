"""The trace reduction on small synthesised traces with hand-worked answers.

Run: ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""

import pytest
from jax.profiler import ProfileData

from bench import trace

MS = 1_000_000  # ns


def _plane(pid, name, lines):
    """lines: {line name: [(event name, start_ms, duration_ms)]}"""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, evs) in enumerate(lines.items(), 1):
        out.append(f'  lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for n, s, d in evs:
            out.append(f"    events {{ metadata_id: {meta[n]} offset_ps: {int(s * 1e9)} "
                       f"duration_ps: {int(d * 1e9)} }}")
        out.append("  }")
    for n, i in meta.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


def _space(devices, host):
    parts = [_plane(i + 1, f"/device:TPU:{i}", {"XLA Ops": evs}) for i, evs in enumerate(devices)]
    parts.append(_plane(99, "/host:CPU", {"python3": host}))
    return "\n".join(parts)


HOST = [("bench.window", 0, 10), ("bench.dispatch", 0, 1.2), ("bench.fetch_tokens", 3.5, 6.5),
        ("jit_other", 0, 10)]


def test_union_subtract_and_gaps():
    assert trace.union([(5, 7), (1, 3), (2, 4), (4, 4)]) == [(1, 4), (5, 7)]
    assert trace.subtract([(0, 10)], [(1, 4), (5, 7)]) == [(0, 1), (4, 5), (7, 10)]
    assert trace.subtract([(2, 6), (8, 9)], [(1, 3), (5, 10)]) == [(3, 5)]
    assert trace.gaps([(1, 4)], 0, 5) == [(0, 1), (4, 5)]
    assert trace.total([(1, 4), (5, 7)]) == 5


def test_busy_idle_ops_and_labelled_gaps():
    dev = [("fusion.1", 1, 2), ("fusion.1", 3, 1), ("all-reduce.3", 5, 2), ("copy.2", 6, 0.5),
           ("fusion.9", 11, 1)]  # outside the window: ignored
    red = trace.reduce(ProfileData.from_text_proto(_space([dev], HOST)))
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.005)  # [1,4] and [5,7] ms
    assert red["idle_share"] == pytest.approx(0.5)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.003)]
    # gaps [0,1] (dispatch), [4,5] and [7,10] (fetch); the window span is no label
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"bench.dispatch": 0.001, "bench.fetch_tokens": 0.004})
    # the all-reduce [5,7] runs beside copy.2 for 0.5 ms of it
    assert red["exposed_collective_s"] == pytest.approx(0.0015)


def test_exposed_collective_is_the_median_over_devices_per_step():
    devs = [
        [("all-reduce.1", 1, 2)],  # 2 ms exposed
        [("all-reduce.1", 1, 2), ("fusion.1", 1, 2)],  # hidden: 0
        [("all-gather.4", 1, 4), ("fusion.1", 2, 1)],  # 3 ms exposed
    ]
    red = trace.reduce(ProfileData.from_text_proto(_space(devs, HOST)), steps=2)
    assert red["devices"] == 3
    assert red["exposed_collective_s"] == pytest.approx(0.002 / 2)


def test_self_time_leaves_out_nested_events():
    ops = [(0, 10, "while.1"), (1, 3, "fusion.1"), (4, 6, "fusion.2"), (4, 5, "copy.1"),
           (12, 13, "fusion.1")]
    assert trace.self_times(ops) == pytest.approx(
        {"while.1": 6e-9, "fusion.1": 3e-9, "fusion.2": 1e-9, "copy.1": 1e-9})


def test_op_names_drop_the_instruction_text():
    assert trace.op_name("%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %a), kind=kLoop") \
        == "fusion.12"
    assert trace.op_name("all-reduce.3") == "all-reduce.3"


def test_file_round_trip(tmp_path):
    raw = ProfileData.text_proto_to_serialized_xspace(_space([[("fusion.1", 1, 2)]], HOST))
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    red = trace.reduce(trace.load(trace.find_xplane(str(tmp_path))))
    assert red["busy_s"] == pytest.approx(0.002)


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(ProfileData.from_text_proto(_space([[("f", 0, 1)]], [("x", 0, 1)])))
    host_only = _plane(99, "/host:CPU", {"python3": HOST})
    with pytest.raises(ValueError, match="device plane"):
        trace.reduce(ProfileData.from_text_proto(host_only))
