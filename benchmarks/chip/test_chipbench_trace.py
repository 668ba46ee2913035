"""The trace reduction against numbers worked out by hand: on planes built
here, and on a trace recorded on a TPU v5e (``testdata/``)."""

import pathlib
import types

import pytest

from chipbench import tracereduce as tr


def ev(name, start, end, **stats):
    return types.SimpleNamespace(name=name, start_ns=start,
                                 duration_ns=end - start,
                                 stats=list(stats.items()))


def plane(name, **lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=k.replace("_", " "), events=v)
        for k, v in lines.items()])


HOST = plane("/host:CPU", python=[
    ev("bench.window", 1000, 11000), ev("bench.step", 1000, 6000),
    ev("bench.decode_step", 2000, 5000), ev("bench.wait", 6000, 11000),
    ev("not_ours", 0, 20000)])
DEVICE = plane("/device:TPU:0", XLA_Ops=[
    ev("fusion.1", 0, 900),                    # before the window
    ev("fusion.2", 1500, 2500), ev("copy.3", 2200, 3000),
    ev("custom-call.4", 4000, 4500),
    ev("fusion.5", 10500, 12000, hlo_module="jit_bar")],
    XLA_Modules=[ev("jit_early(0)", 0, 950), ev("jit_foo(1)", 1400, 3100),
                 ev("jit_paged_attention(2)", 3900, 4600)])


def test_reduction_by_hand():
    r = tr.reduce_planes([HOST, DEVICE])
    assert r.window_s == 10000e-9
    # busy: [1500, 3000] + [4000, 4500] + [10500, 11000] (clipped)
    assert r.busy_s == pytest.approx(2500e-9)
    assert r.devices == 1
    # module time sums op durations (overlaps count in each op), clipped
    assert r.module_s == pytest.approx({"jit_foo": 1800e-9,
                                        "jit_paged_attention": 500e-9,
                                        "jit_bar": 500e-9})
    # idle gaps go to the innermost span at their midpoints:
    # [1000, 1500] step; [3000, 4000] decode_step; [4500, 10500] wait
    assert r.idle_by_span == pytest.approx({"bench.step": 500e-9,
                                            "bench.decode_step": 1000e-9,
                                            "bench.wait": 6000e-9})
    assert r.top(r.idle_by_span, 2) == [["bench.wait", 6000e-9],
                                        ["bench.decode_step", 1000e-9]]


def test_gap_outside_every_span_and_missing_window():
    host = plane("/host:CPU", python=[ev("bench.window", 0, 100)])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("x", 40, 60,
                                             hlo_module="jit_x(7)")])
    r = tr.reduce_planes([host, dev])
    assert r.idle_by_span == pytest.approx({tr.NO_SPAN: 80e-9})
    assert r.module_s == pytest.approx({"jit_x": 20e-9})
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_planes([dev])
    with pytest.raises(ValueError, match="no device"):
        tr.reduce_planes([host])


def test_module_name():
    assert tr.module_name("jit_paged_attention(1234)") == \
        "jit_paged_attention"
    assert tr.module_name("jit_paged_attention") == "jit_paged_attention"


def test_recorded_v5e_trace_by_hand():
    """Three steps of two small jitted programs, each followed by a 20 ms
    sleep, traced on one TPU v5e chip. By hand from its events: the window
    span lasts 67,281,293 ns. The first step's two programs ran before it
    opened (the device clock reads about 1 ms behind the host's there), so
    two steps remain: program f as three ops of 13 + 11,418 + 90,088 and
    13 + 11,685 + 90,087 ns, program g as one op of 106,258 and 106,041 ns;
    415,603 ns busy. Every gap's midpoint falls in a ``bench.wait`` span."""
    path = pathlib.Path(__file__).parent / "testdata" / "v5e_small.xplane.pb"
    r = tr.reduce_file(str(path))
    assert r.window_s == pytest.approx(67_281_293e-9)
    assert r.busy_s == pytest.approx(415_603e-9)
    assert r.module_s == pytest.approx({"jit__lambda": 415_603e-9})
    assert r.idle_by_span == pytest.approx(
        {"bench.wait": (67_281_293 - 415_603) * 1e-9})
