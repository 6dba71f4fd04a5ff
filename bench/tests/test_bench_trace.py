"""The trace reduction, on a small synthetic ``.xplane.pb``: busy union,
idle share, per-name device time, programs per call, the window span."""
import pytest

from bench import trace_reduce


def _event(meta, start_ns, dur_ns, base_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {(start_ns - base_ns) * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _line(lid, name, base_ns, events):
    body = "\n".join(_event(m, s, d, base_ns) for m, s, d in events)
    return (f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: {base_ns}\n"
            f"{body}\n}}")


def _plane(pid, name, lines, metas):
    md = "\n".join(f"event_metadata {{ key: {k} value {{ id: {k} name: \"{v}\" }} }}"
                   for k, v in metas.items())
    return f"planes {{ id: {pid} name: \"{name}\"\n{''.join(lines)}\n{md}\n}}"


META = {1: "fusion.12", 2: "paged_decode_attention", 3: "fusion.7",
        4: "jit__step_paged_impl(3)", 5: "jit__lambda_(9)"}


def space():
    """Window 1000..2000 ns (host span).  Device 0: ops [900,1100) fusion.12
    (clipped to 1000..1100), [1050,1300) paged_decode_attention
    (overlapping), [1500,1600) fusion.7, [1950,2100) fusion.12 (clipped);
    modules: step [1000,1400), lambda [1450,1650).  Device 1: one op
    [1200,1700)."""
    base = 500
    d0 = _plane(1, "/device:TPU:0", [
        _line(1, "XLA Ops", base, [(1, 900, 200), (2, 1050, 250),
                                   (3, 1500, 100), (1, 1950, 150)]),
        _line(2, "XLA Modules", base, [(4, 1000, 400), (5, 1450, 200)]),
    ], META)
    d1 = _plane(2, "/device:TPU:1", [
        _line(1, "XLA Ops", base, [(3, 1200, 500)]),
    ], META)
    host = _plane(3, "/host:CPU", [
        _line(1, "python3", base, [(6, 1000, 1000)]),
    ], {6: trace_reduce.WINDOW_SPAN})
    return "\n".join([d0, d1, host])


@pytest.fixture
def xplane(tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(space())
    path = tmp_path / "plugins" / "profile" / "run" / "host.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    return str(path)


def test_reduce_synthetic_xplane(xplane):
    devices, window = trace_reduce.read(xplane)
    assert set(devices) == {"/device:TPU:0", "/device:TPU:1"}
    assert window[1:] == (1000.0, 2000.0)
    red = trace_reduce.reduce(devices, window)
    assert red.devices == 2
    assert red.window_s == pytest.approx(1000e-9)
    # device 0 busy: [1000,1300) + [1500,1600) + [1950,2000) = 450 ns;
    # device 1: [1200,1700) = 500 ns; mean 475 ns
    assert red.busy_s == pytest.approx(475e-9)
    assert red.idle_share == pytest.approx(1 - 0.475)
    # per-name device time, averaged over the two devices
    assert red.op_s["paged_decode_attention"] == pytest.approx(250e-9 / 2)
    assert red.op_s["fusion.12"] == pytest.approx((100 + 50) * 1e-9 / 2)
    assert red.op_s["fusion.7"] == pytest.approx((100 + 500) * 1e-9 / 2)
    assert red.seconds_of(r"paged_decode_attention") == pytest.approx(125e-9)
    secs, calls = red.module_seconds_calls(r"_step(_paged)?_impl")
    assert secs == pytest.approx(400e-9 / 2) and calls == pytest.approx(0.5)
    # idle gaps of device 0: [1300,1500), [1600,1950); device 1:
    # [1000,1200), [1700,2000)
    assert [round(g[1] * 1e9) for g in red.gaps] == [350, 300, 200, 200]
    bd = trace_reduce.breakdown(red)
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] == "fusion" and "paged_decode_attention" in names
    assert bd["idle_gaps"][0] == ["unattributed", pytest.approx(350e-9)]


def test_reduce_without_window_span_uses_the_device_span():
    devices = {"/device:TPU:0": trace_reduce.DeviceEvents(
        ops=[("a", 0.0, 100.0), ("b", 300.0, 400.0)])}
    red = trace_reduce.reduce(devices)
    assert red.window_s == pytest.approx(400e-9)
    assert red.busy_s == pytest.approx(200e-9)
    assert red.idle_share == pytest.approx(0.5)


def test_reduce_refuses_a_trace_with_no_device_events():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"/device:TPU:0": trace_reduce.DeviceEvents()})
