"""The trace reduction on a small synthetic trace."""
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as tr


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k, events=v) for k, v in lines.items()])


def trace():
    ops0 = [ev("fusion.1", 500, 1500),            # clipped to [1000, 2000)
            ev("all-reduce.3", 2000, 1000),
            ev("%resize_convert_images.1 = f32[96,224,224]{2,1,0:T(8,128)} "
               "custom-call(u8[96,256,256]{2,1,0} %x)",
               2500, 1000),                      # overlaps: union
            ev("fusion.1", 8000, 1000)]
    ops1 = [ev("fusion.1", 1000, 2000), ev("all-reduce.3", 3000, 1000)]
    mods = [ev("jit_train_step(123)", 1000, 3000),
            ev("jit_resize_convert_images(9)", 2500, 1000),
            ev("jit_train_step(123)", 8000, 1000)]
    return NS(planes=[
        plane("/device:TPU:0", **{"XLA Ops": ops0, "XLA Modules": mods}),
        plane("/device:TPU:1", **{"XLA Ops": ops1, "XLA Modules": mods}),
        plane("/device:TPU_NON_CORE:0", **{"XLA Ops": [ev("x", 1000, 9000)]}),
    ])


# the host's records, in seconds from the trace's start, which lag the
# trace's clock by 400 ns: the first step's loss is on the host as its
# program ends, the second's 200 ns after
HOST = [("train_step", 600e-9, 3600e-9), ("next_batch", 5600e-9, 10_600e-9)]
DONE = [3600e-9, 8800e-9]
WINDOW = (600e-9, 10_600e-9)


def test_busy_idle_collectives_and_gaps():
    r = tr.reduce(trace(), *WINDOW, host=HOST, done_s=DONE)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(10e-6)
    # device 0 busy [1000, 3500) + [8000, 9000) = 3500 ns; device 1 3000 ns
    assert r["busy_s"] == pytest.approx(3250e-9)
    assert r["collective_s"] == pytest.approx(1000e-9)
    assert r["op_s"]["fusion.1"] == pytest.approx((2000 + 2000) / 2 * 1e-9)
    assert r["op_n"]["resize_convert_images.1 f32[96,224,224]"] == 0.5
    assert tr.matching(r["module_s"], "train_step") == pytest.approx(4000e-9)
    # the longest gap: device 1, [4000, 11000), its middle under next_batch
    assert r["idle_gaps"][0] == ["next_batch", pytest.approx(7000e-9)]
    assert r["device_ops"][0][0] == "fusion.1"
    assert tr.matching(r["op_s"], "^resize_convert_images") == \
        pytest.approx(500e-9)


def test_the_host_clock_is_put_on_the_trace_by_the_steps():
    assert tr.offset_ns(trace(), DONE) == pytest.approx(400)
    # steps and programs that do not pair up leave the clock as it is
    assert tr.offset_ns(trace(), DONE[:1]) == 0.0


def test_a_gap_outside_every_host_call_says_so():
    r = tr.reduce(trace(), *WINDOW, host=(), done_s=DONE)
    assert r["idle_gaps"][0][0] == tr.OUTSIDE


def test_a_trace_with_no_device_work_is_an_error():
    t = trace()
    t.planes = t.planes[2:]
    with pytest.raises(ValueError, match="no device ran"):
        tr.reduce(t, 0.0, 1e-5)


def test_union_and_gaps():
    assert tr.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert tr.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
