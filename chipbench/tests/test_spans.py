"""The program's spans: self times and the idle gaps charged down to the
deepest client-thread span, on hand-made spans; and every span the
program documents, read back from a real profiler trace."""
import numpy as np
import pytest

from chipbench import spans as sp
from chipbench import trace as t

CLIENT = ("/host:CPU", 1)
MAPPER = ("/host:CPU", 0)


def S(name, start, end, line=CLIENT, **args):
    return sp.Span(name, start, end - start, line, tuple(args.items()))


def hand_spans():
    # one request in the window [0, 1000] ns; the mapper's thread
    # covers all of it
    return [
        S("window", 0, 1000),
        S("insert", -100, -50), S("insert.scan", -90, -60),  # before it
        S("insert", 50, 300),
        S("insert.scan", 60, 250),
        S("insert.lock", 60, 70), S("insert.publish", 210, 240),
        S("insert.touched", 250, 270), S("insert.submit", 270, 290,
                                         version=7),
        S("lookup_batched", 300, 900),
        S("lookup.bucketize", 300, 400), S("lookup.gate", 400, 420),
        S("lookup.operands", 420, 430), S("lookup.dispatch", 430, 450),
        S("lookup.wait", 450, 700), S("lookup.scatter", 700, 900),
        S("fetch", 900, 1000),
        S("mapper.replay", 0, 1000, MAPPER, version=7),
        S("mapper.remap", 100, 200, MAPPER),
    ]


def hand_trace(busy):
    spans = hand_spans()
    host = [t.Event(s.name, s.start_ns, s.dur_ns) for s in spans
            if s.line == CLIENT and s.name in sp.HARNESS_SPANS]
    modules = {"/device:TPU:0": [t.Event("jit_x(1)", a, b - a)
                                 for a, b in busy]}
    return t.Trace(modules=modules, host=host), spans


BUSY = [(30, 200), (440, 690), (920, 960)]
# gaps: [0, 30] [200, 440] [690, 920] [960, 1000]


def test_gap_inside_a_program_span_is_charged_to_it():
    tr, spans = hand_trace([(0, 750), (850, 1000)])
    # [750, 850] lies inside lookup_batched and lookup.scatter
    assert sp.idle_by_span(tr, spans) == {
        "lookup.scatter": pytest.approx(100e-9)}


def test_gap_across_spans_is_split_among_them():
    tr, spans = hand_trace(BUSY)
    idle = sp.idle_by_span(tr, spans)
    # [690, 920]: 10 in lookup.wait, 200 in lookup.scatter, 20 in fetch
    assert idle["lookup.wait"] == pytest.approx(10e-9)
    assert idle["lookup.scatter"] == pytest.approx(200e-9)
    # [200, 440]: insert.scan's own 20 around insert.publish's 30, then
    # insert.touched, insert.submit, the bare insert, and lookup.*
    assert idle["insert.publish"] == pytest.approx(30e-9)
    assert idle["insert.scan"] == pytest.approx(20e-9)
    assert idle["insert"] == pytest.approx(10e-9)
    assert idle["lookup.bucketize"] == pytest.approx(100e-9)
    assert idle["lookup.dispatch"] == pytest.approx(10e-9)
    assert "lookup_batched" not in idle


def test_gap_under_the_harness_span_alone_keeps_its_name():
    tr, spans = hand_trace(BUSY)
    # [900, 920] and [960, 1000] lie in fetch, which holds no span
    assert sp.idle_by_span(tr, spans)["fetch"] == pytest.approx(60e-9)


def test_mapper_spans_never_label_a_client_gap():
    tr, spans = hand_trace(BUSY)
    idle = sp.idle_by_span(tr, spans)
    # [0, 30] lies under mapper.replay and no client span
    assert idle[t.NO_SPAN] == pytest.approx(30e-9)
    assert not any(name.startswith("mapper.") for name in idle)


def test_descent_goes_as_deep_as_the_spans_nest():
    tr, spans = hand_trace([(0, 215), (235, 1000)])
    # [215, 235]: insert -> insert.scan -> insert.publish
    assert sp.idle_by_span(tr, spans) == {
        "insert.publish": pytest.approx(20e-9)}


@pytest.mark.parametrize("busy", [BUSY, [(0, 215), (235, 1000)], []])
def test_idle_sums_to_window_less_busy_within_each_self_time(busy):
    tr, spans = hand_trace(busy)
    r = t.reduce(tr)
    idle = sp.idle_by_span(tr, spans)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    own = sp.self_times(spans)
    own[t.NO_SPAN] = own.pop(t.WINDOW)
    for name, v in idle.items():
        assert v <= own[name] + 1e-18, name
    if not busy:
        client = {k: v for k, v in own.items()
                  if v and not k.startswith("mapper.")}
        assert idle == pytest.approx(client)


def test_self_time_subtracts_children_inside_the_window():
    st = sp.self_times(hand_spans())
    assert st["insert.scan"] == pytest.approx((190 - 10 - 30) * 1e-9)
    assert st["insert"] == pytest.approx((250 - 190 - 20 - 20) * 1e-9)
    assert st["lookup_batched"] == pytest.approx(0.0)
    assert st["lookup.wait"] == pytest.approx(250e-9)
    assert st["window"] == pytest.approx((1000 - 250 - 600 - 100) * 1e-9)
    # other threads are summed too, each against its own nesting
    assert st["mapper.replay"] == pytest.approx(900e-9)
    assert st["mapper.remap"] == pytest.approx(100e-9)
    # the whole client thread adds up to the window
    assert sum(v for k, v in st.items()
               if not k.startswith("mapper.")) == pytest.approx(1000e-9)


def test_self_time_needs_one_window():
    with pytest.raises(ValueError):
        sp.self_times([S("lookup.wait", 0, 10)])
    assert sp.client_line(hand_spans()) == CLIENT


def test_every_documented_span_is_in_a_real_trace(tmp_path):
    """A tiny index under the profiler writes every span the program
    documents, the mapper's on their own thread, and each mapper batch
    carries the version of an insert it publishes."""
    import jax

    from repro.core.sharded_eh import ShardedShortcutEH

    rng = np.random.default_rng(5)
    keys = (rng.choice(2**31 - 1, 2048, replace=False) + 1).astype(
        np.uint32)
    idx = ShardedShortcutEH(9, 64, 128, async_mapper=True,
                            poll_interval=0.001)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(t.WINDOW):
            idx.insert(keys, keys)                  # doubles: a create
            assert idx.wait_in_sync(120)
            idx.shards[0].fan_in_threshold = 0.0    # builds eh_trad
            idx.lookup_batched(keys[:256])
            idx.shards[0].fan_in_threshold = 8.0
            for i in range(2):                      # updates: touched
                idx.insert(keys[:64], keys[:64] + i + 1)
                assert idx.wait_in_sync(120)
            got = np.asarray(idx.lookup_batched(keys[:256]))
    finally:
        jax.profiler.stop_trace()
        idx.close()
    np.testing.assert_array_equal(got[:64], keys[:64] + 2)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = sp.load(str(path))
    client = sp.client_line(spans)
    lines = {}
    for s in spans:
        lines.setdefault(s.name, set()).add(s.line)
    assert set(sp.CLIENT_SPANS + sp.MAPPER_SPANS) <= set(lines)
    assert all(lines[n] == {client} for n in sp.CLIENT_SPANS)
    assert all(client not in lines[n] for n in sp.MAPPER_SPANS)
    submitted = {dict(s.args)["version"] for s in spans
                 if s.name == "insert.submit"}
    replayed = {dict(s.args)["version"] for s in spans
                if s.name == "mapper.replay"}
    assert replayed and replayed <= submitted
    st = sp.self_times(spans)
    assert all(st[n] >= 0 for n in sp.CLIENT_SPANS)
