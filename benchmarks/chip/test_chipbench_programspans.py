"""The reduction of the program's own spans against numbers worked out by
hand, and against ``tracereduce`` on traces that carry none."""

import pathlib

import pytest

from chipbench import programspans as ps
from chipbench import tracereduce as tr
from test_chipbench_trace import DEVICE, HOST, ev, plane

# One engine step inside the harness's spans, as the program nests its
# own: serve.step > serve.decode > bench.decode_step >
# serve.decode.dispatch > two layers' children, one attention launch
# holding the harness's bench.paged_attention. A serve.step that starts
# before the window counts nowhere.
STEP_HOST = plane("/host:CPU", python=[
    ev("bench.window", 1000, 21000),
    ev("serve.step", 500, 1500),
    ev("bench.step", 2000, 12000),
    ev("serve.step", 2100, 11900),
    ev("serve.schedule", 2200, 2600),
    ev("serve.pages", 2700, 2900),
    ev("serve.decode", 3000, 11000),
    ev("bench.decode_step", 3500, 10500),
    ev("serve.decode.dispatch", 3600, 9000),
    ev("serve.decode.layer_params", 3700, 4200),
    ev("serve.decode.kv_write", 4500, 5500),
    ev("serve.decode.attention", 6000, 7000),
    ev("bench.paged_attention", 6100, 6900),
    ev("serve.decode.layer_params", 7100, 7600),
    ev("serve.decode.kv_write", 7800, 8500),
    ev("serve.decode.attention", 8600, 8900),
    ev("serve.decode.sync", 10600, 10900),
    ev("serve.account", 11000, 11800),
    ev("bench.wait", 12000, 21000)])
# busy [1200, 2300] [2500, 3100] [4000, 4600] [6200, 6800] [7500, 7900]
# [9500, 11050] [11500, 13000] [20000, 21000]: 7,350 ns of 20,000
STEP_DEVICE = plane("/device:TPU:0", XLA_Ops=[
    ev(f"fusion.{i}", s, e, hlo_module="jit_step")
    for i, (s, e) in enumerate([
        (1200, 2300), (2500, 3100), (4000, 4600), (6200, 6800),
        (7500, 7900), (9500, 11050), (11500, 13000), (20000, 22000)])])


def test_program_spans_by_hand():
    r = ps.reduce_planes([STEP_HOST, STEP_DEVICE])
    assert r.window_s == 20000e-9
    assert r.busy_s == pytest.approx(7350e-9)
    # gaps, midpoints, innermost span of either prefix:
    # [1000, 1200] none; [2300, 2500] schedule; [3100, 4000] at 3550
    # bench.decode_step (dispatch opens at 3600); [4600, 6200] at 5400
    # kv_write; [6800, 7500] at 7150 layer_params; [7900, 9500] at 8700
    # attention; [11050, 11500] account; [13000, 20000] bench.wait
    assert r.idle_by_span == pytest.approx({
        tr.NO_SPAN: 200e-9, "serve.schedule": 200e-9,
        "bench.decode_step": 900e-9, "serve.decode.kv_write": 1600e-9,
        "serve.decode.layer_params": 700e-9,
        "serve.decode.attention": 1600e-9, "serve.account": 450e-9,
        "bench.wait": 7000e-9})
    assert r.span_s == pytest.approx({
        "serve.step": 9800e-9, "serve.schedule": 400e-9,
        "serve.pages": 200e-9, "serve.decode": 8000e-9,
        "serve.decode.dispatch": 5400e-9,
        "serve.decode.layer_params": 1000e-9,
        "serve.decode.kv_write": 1700e-9,
        "serve.decode.attention": 1300e-9, "serve.decode.sync": 300e-9,
        "serve.account": 800e-9})
    assert r.span_calls == {
        "serve.step": 1, "serve.schedule": 1, "serve.pages": 1,
        "serve.decode": 1, "serve.decode.dispatch": 1,
        "serve.decode.layer_params": 2, "serve.decode.kv_write": 2,
        "serve.decode.attention": 2, "serve.decode.sync": 1,
        "serve.account": 1}
    # by containment: the step holds every gap from 2300 to 11500
    # (without the pre-window step's [1000, 1200]); dispatch holds
    # [3600, 4000] + 1600 + 700 + [7900, 9000]
    assert r.idle_in_span == pytest.approx({
        "serve.step": 5450e-9, "serve.schedule": 200e-9,
        "serve.pages": 0.0, "serve.decode": 4800e-9,
        "serve.decode.dispatch": 3800e-9,
        "serve.decode.layer_params": (300 + 400) * 1e-9,
        "serve.decode.kv_write": (900 + 600) * 1e-9,
        "serve.decode.attention": (200 + 200 + 300) * 1e-9,
        "serve.decode.sync": 0.0, "serve.account": 450e-9})
    # the harness's own reduction of the same trace sees only bench.*
    base = tr.reduce_planes([STEP_HOST, STEP_DEVICE])
    assert base.idle_by_span == pytest.approx({
        tr.NO_SPAN: 200e-9, "bench.step": 650e-9,
        "bench.decode_step": 4800e-9, "bench.wait": 7000e-9})
    assert (r.window_s, r.busy_s, r.module_s) == \
        (base.window_s, base.busy_s, base.module_s)


def test_spans_of_one_name_merge_before_idle_is_counted():
    host = plane("/host:CPU", python=[
        ev("bench.window", 0, 1000), ev("serve.x", 100, 400),
        ev("serve.x", 200, 300)])
    dev = plane("/device:TPU:0", XLA_Ops=[ev("x", 500, 1000,
                                             hlo_module="jit_x")])
    r = ps.reduce_planes([host, dev])
    assert r.span_s == pytest.approx({"serve.x": 400e-9})
    assert r.span_calls == {"serve.x": 2}
    assert r.idle_in_span == pytest.approx({"serve.x": 300e-9})
    # [0, 500] at 250: the inner serve.x
    assert r.idle_by_span == pytest.approx({"serve.x": 500e-9})


@pytest.mark.parametrize("planes", [
    [HOST, DEVICE],
    [plane("/host:CPU", python=[ev("bench.window", 0, 100)]),
     plane("/device:TPU:0", XLA_Ops=[ev("x", 40, 60,
                                        hlo_module="jit_x(7)")])],
    "v5e_small.xplane.pb"])
def test_without_program_spans_numbers_are_tracereduce_s(planes):
    if isinstance(planes, str):
        path = pathlib.Path(__file__).parent / "testdata" / planes
        r, base = ps.reduce_file(str(path)), tr.reduce_file(str(path))
    else:
        r, base = ps.reduce_planes(planes), tr.reduce_planes(planes)
    assert (r.window_s, r.busy_s, r.devices, r.module_s, r.idle_by_span) \
        == (base.window_s, base.busy_s, base.devices, base.module_s,
            base.idle_by_span)
    assert r.span_s == r.span_calls == r.idle_in_span == {}
    assert ps.layer_metrics(r) == {}


def test_layer_metrics():
    r = ps.reduce_planes([STEP_HOST, STEP_DEVICE])
    assert ps.layer_metrics(r) == pytest.approx({
        "schedule_ms_per_step": 400e-6,
        "fabric_ms_per_step": (200 + 800) * 1e-6,
        "decode_dispatch_ms_per_call": 5400e-6,
        "dispatch_idle_share": 100.0 * 3800 / 20000})
    # a reduction without program spans, or none at all
    assert ps.layer_metrics(tr.reduce_planes([STEP_HOST, STEP_DEVICE])) \
        == {}
    assert ps.layer_metrics(None) == {}
