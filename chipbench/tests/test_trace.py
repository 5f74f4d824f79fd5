"""The trace reduction, on hand-made event lists."""
import pytest

from chipbench import trace as t

E = t.Event


def hand_trace():
    # window [100, 200] ns; two chips
    host = [E("window", 100, 100),
            E("insert", 100, 30),          # 100..130
            E("lookup_batched", 130, 50),  # 130..180
            E("fetch", 180, 20)]           # 180..200
    modules = {"/device:TPU:0": [
        E("jit_convert_element_type(1)", 90, 20),  # 90..110 (clipped)
        E("jit_eh_insert_many(7)", 120, 10),       # 120..130
        E("jit_sharded_eh_lookup(3)", 125, 15),    # overlaps: ..140
        E("jit__refresh_slice(2)", 190, 5)],       # 190..195
        "/device:TPU:1": [E("jit_sharded_eh_lookup(3)", 150, 40)]}
    return t.Trace(modules=modules, host=host)


def test_busy_union_per_chip_and_mean():
    r = t.reduce(hand_trace())
    assert r.window_s == pytest.approx(100e-9)
    # chip 0: [100,110] + [120,140] + [190,195] = 35; chip 1: 40
    assert r.busy_s == pytest.approx(37.5e-9)
    assert r.idle_share == pytest.approx(0.625)


def test_device_time_by_jit_name():
    r = t.reduce(hand_trace())
    assert r.by_jit["jit(eh_insert_many)"] == pytest.approx(10e-9)
    assert r.by_jit["jit(sharded_eh_lookup)"] == pytest.approx(55e-9)
    # a program is charged only its part inside the window
    assert r.by_jit["jit(convert_element_type)"] == pytest.approx(10e-9)
    assert t.top(r.by_jit)[0][0] == "jit(sharded_eh_lookup)"


def test_idle_gaps_charged_to_the_covering_host_span():
    r = t.reduce(hand_trace())
    # chip 0 gaps: [110,120] insert, [140,190] lookup_batched,
    # [195,200] fetch; chip 1: [100,150] insert 30 + lookup 20 -> insert
    # covers 30 of it, so insert; [190,200] fetch.  Mean over 2 chips.
    assert r.idle_by_span["insert"] == pytest.approx((10 + 50) / 2 * 1e-9)
    assert r.idle_by_span["lookup_batched"] == pytest.approx(25e-9)
    assert r.idle_by_span["fetch"] == pytest.approx((5 + 10) / 2 * 1e-9)
    total = sum(r.idle_by_span.values())
    assert total == pytest.approx(r.window_s - r.busy_s)


def test_jit_names_and_errors():
    assert t.jit_name("jit_sharded_eh_lookup(12)") == "jit(sharded_eh_lookup)"
    assert t.jit_name("jit_eh_insert_many") == "jit(eh_insert_many)"
    assert t.jit_name("jit(eh_insert_many)") == "jit(eh_insert_many)"
    with pytest.raises(ValueError):
        t.reduce(t.Trace(modules={"/device:TPU:0": []}, host=[]))
    with pytest.raises(ValueError):
        t.reduce(t.Trace(modules={}, host=[E("window", 0, 10)]))
