"""CPU tests of the readers of the program's own spans and scopes: idle
time by innermost program span, device time by (module, scope) with
nested operations counted once, the xplane reading on a synthetic trace,
and the four per-layer readers on records with and without what they
read.  The breakdown the run prints stays labelled by ``bench.*`` spans."""
from __future__ import annotations

import pytest

import tinycell  # noqa: F401  (puts the checkout on sys.path)
from bench import program_trace as P
from bench import registry, trace_reduce

DEV = "/device:TPU:0"
DECODE = "jit_engine_decode"


def _reader(name):
    return registry.load_reader(name, tinycell.REPO).read


# ---------------------------------------------------------------- trace


def test_idle_goes_to_the_innermost_program_span():
    # device busy 0-2 and 6-10; idle 2-6
    ops = {DEV: [((DECODE, "ffn"), 0.0, 2.0), ((DECODE, "head"), 6.0, 10.0)]}
    spans = [("decode.block", 1.0, 9.0), ("decode.fetch", 2.5, 3.5),
             ("decode.guard", 3.5, 4.0), ("engine.admit", 5.0, 5.5),
             ("decode.guard", 4.5, 4.5)]           # no duration: no time
    idle = P.idle_by_span(ops, spans, (0.0, 10.0))
    assert idle == pytest.approx({"decode.block": 0.5 + 1.0 + 0.5,
                                  "decode.fetch": 1.0, "decode.guard": 0.5,
                                  "engine.admit": 0.5})
    # outside every span the idle time is charged to NO_SPAN, and the
    # charged time adds up to the device's idle time
    idle = P.idle_by_span(ops, [("decode.fetch", 3.0, 4.0)], (0.0, 10.0))
    assert idle == pytest.approx({"decode.fetch": 1.0, P.NO_SPAN: 3.0})
    assert sum(idle.values()) == pytest.approx(4.0)


def test_while_and_its_body_are_charged_once_to_the_leaf_scope():
    while_op = ((DECODE, P.UNSCOPED), 0.0, 10.0)
    body = [((DECODE, "attention/core"), 1.0, 4.0),
            ((DECODE, "cache_io"), 4.0, 5.0),
            ((DECODE, "attention/core"), 6.0, 7.0)]
    ops = {DEV: [while_op] + body + [(("jit_engine_prefill", "ffn"),
                                      12.0, 13.0),
                                     ((DECODE, "head"), 8.0, 8.0)]}
    by = P.device_by_scope(ops, (0.0, 20.0))
    assert by == pytest.approx({(DECODE, "attention/core"): 4.0,
                                (DECODE, "cache_io"): 1.0,
                                (DECODE, P.UNSCOPED): 5.0,
                                ("jit_engine_prefill", "ffn"): 1.0})
    # summed over scopes: the device's busy time, nested time once
    busy = trace_reduce.busy_intervals(
        [(None, s, e) for _, s, e in ops[DEV]], 0.0, 20.0)
    assert sum(by.values()) == pytest.approx((busy[:, 1] - busy[:, 0]).sum())
    t = {"device_by_scope": by}
    assert P.module_scope_seconds(t, DECODE, "attention") == 4.0
    assert P.module_scope_seconds(t, DECODE, "moe") is None


def test_scope_of_an_op_name():
    assert P.scope_of("jit(engine_decode)/while/body/closed_call/while/body/"
                      "closed_call/attention/core/dot_general:") == \
        "attention/core"
    assert P.scope_of("jit(engine_decode)/while/body/closed_call/head/"
                      "dot_general:") == "head"
    assert P.scope_of("jit(engine_decode)/while/body/add:") == P.UNSCOPED
    assert P.scope_of("") == P.UNSCOPED


@pytest.mark.parametrize("scope", ["ssm", "recurrent"])
def test_recurrent_and_ssm_ops_are_charged_to_their_scope(scope):
    name = (f"jit(engine_decode)/while/body/closed_call/{scope}/"
            "dot_general:")
    assert P.scope_of(name) == scope
    ops = {DEV: [((DECODE, P.scope_of(name)), 0.0, 2.0),
                 ((DECODE, P.scope_of("jit(engine_decode)/add:")), 2.0,
                  3.0)]}
    by = P.device_by_scope(ops, (0.0, 10.0))
    assert by == pytest.approx({(DECODE, scope): 2.0,
                                (DECODE, P.UNSCOPED): 1.0})
    assert P.module_scope_seconds({"device_by_scope": by}, DECODE,
                                  scope) == 2.0


def _xspace(device_ops, modules, host):
    """A serialized XSpace: one TPU plane with "XLA Modules" and "XLA Ops"
    lines (ops carry ``program_id`` and ``tf_op`` in their metadata), and
    one host plane.  Times in ns."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="t_xplane.proto",
                                           package="t", syntax="proto3")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, typ, rep, tname in fields:
            m.field.add(name=fname, number=num, type=typ, type_name=tname,
                        label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL)

    msg("Stat", ("metadata_id", 1, F.TYPE_INT64, 0, None),
        ("uint64_value", 3, F.TYPE_UINT64, 0, None),
        ("str_value", 5, F.TYPE_STRING, 0, None))
    msg("Event", ("metadata_id", 1, F.TYPE_INT64, 0, None),
        ("offset_ps", 2, F.TYPE_INT64, 0, None),
        ("duration_ps", 3, F.TYPE_INT64, 0, None))
    msg("Line", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None),
        ("timestamp_ns", 3, F.TYPE_INT64, 0, None),
        ("events", 4, F.TYPE_MESSAGE, 1, ".t.Event"))
    msg("EventMetadata", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None),
        ("stats", 5, F.TYPE_MESSAGE, 1, ".t.Stat"))
    msg("StatMetadata", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None))
    msg("EMEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, ".t.EventMetadata"))
    msg("SMEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, ".t.StatMetadata"))
    msg("Plane", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None),
        ("lines", 3, F.TYPE_MESSAGE, 1, ".t.Line"),
        ("event_metadata", 4, F.TYPE_MESSAGE, 1, ".t.EMEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, 1, ".t.SMEntry"))
    msg("Space", ("planes", 1, F.TYPE_MESSAGE, 1, ".t.Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    space = message_factory.GetMessageClass(
        pool.FindMessageTypeByName("t.Space"))()

    def plane(pid, name, lines):
        """lines: [(name, [(event name, t0, t1, (program id, tf_op) or
        None)])]; one event metadata per distinct (name, stats)."""
        p = space.planes.add(id=pid, name=name)
        for i, sname in ((1, "program_id"), (2, "tf_op")):
            e = p.stat_metadata.add(key=i)
            e.value.id, e.value.name = i, sname
        ids = {}
        for lid, (lname, evs) in enumerate(lines, 1):
            line = p.lines.add(id=lid, name=lname, timestamp_ns=0)
            for ev_name, t0, t1, meta in evs:
                if (ev_name, meta) not in ids:
                    mid = ids[(ev_name, meta)] = len(ids) + 1
                    e = p.event_metadata.add(key=mid)
                    e.value.id, e.value.name = mid, ev_name
                    if meta:
                        e.value.stats.add(metadata_id=1,
                                          uint64_value=meta[0])
                        e.value.stats.add(metadata_id=2, str_value=meta[1])
                line.events.add(metadata_id=ids[(ev_name, meta)],
                                offset_ps=t0 * 1000,
                                duration_ps=(t1 - t0) * 1000)

    plane(1, DEV, [("XLA Modules", [(*m, None) for m in modules]),
                   ("XLA Ops", [(n, a, b, (pid, op))
                                for n, a, b, pid, op in device_ops])])
    plane(2, "/host:CPU", [("python", [(*h, None) for h in host])])
    return space.SerializeToString()


def test_read_xplane_finds_module_scope_and_program_spans(tmp_path):
    decode_id, prefill_id = 111, 222
    body = "jit(engine_decode)/while/body/closed_call/"
    ops = [
        ("%while.1 = (...) while(...)", 1000, 9000, decode_id,
         "jit(engine_decode)/while:"),
        ("%fusion.7 = bf16[...] fusion(...)", 2000, 5000, decode_id,
         body + "attention/core/gather:"),
        ("%fusion.9 = bf16[...] fusion(...)", 5000, 6000, decode_id,
         body + "cache_io/dynamic_update_slice:"),
        # the same op text in another program, with another op_name
        ("%fusion.7 = bf16[...] fusion(...)", 12000, 13000, prefill_id,
         "jit(engine_prefill)/while/body/closed_call/ffn/dot_general:"),
    ]
    modules = [(f"jit_engine_decode({decode_id})", 1000, 9000),
               (f"jit_engine_prefill({prefill_id})", 12000, 13000)]
    host = [("bench.window", 0, 20000), ("bench.decode", 500, 10000),
            ("repro.decode.block", 600, 9800),
            ("repro.decode.fetch", 9100, 9700), ("other", 0, 100)]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops, modules, host))

    dev, spans, window = P.read_xplane(str(path))
    assert window == pytest.approx((0.0, 20e-6))
    assert [n for n, _, _ in sorted(spans)] == ["decode.block",
                                                "decode.fetch"]
    assert [t for _, a, b in sorted(spans) for t in (a, b)] == \
        pytest.approx([0.6e-6, 9.8e-6, 9.1e-6, 9.7e-6])
    assert [k for k, _, _ in dev[DEV]] == [
        (DECODE, P.UNSCOPED), (DECODE, "attention/core"),
        (DECODE, "cache_io"), ("jit_engine_prefill", "ffn")]
    t = P.reduce(dev, spans, window)
    # idle 0-1, 9-12 and 13-20 us; decode.block holds 0.6-9.8, its fetch
    # 9.1-9.7
    assert t["idle_by_span"] == pytest.approx(
        {P.NO_SPAN: 0.6e-6 + 2.2e-6 + 7e-6,
         "decode.block": 0.4e-6 + 0.1e-6 + 0.1e-6, "decode.fetch": 0.6e-6})
    assert sum(t["idle_by_span"].values()) == pytest.approx(11e-6)
    assert P.module_scope_seconds(t, DECODE, "attention") == \
        pytest.approx(3e-6)
    # the harness's own reading keeps only the benchmark's spans
    _, bench_spans = trace_reduce.read_xplane(str(path))
    assert sorted(n for n, _, _ in bench_spans) == ["bench.decode",
                                                    "bench.window"]


def test_load_matches_the_run_by_its_window(tmp_path):
    host = [("bench.window", 0, 20000), ("repro.decode.guard", 10, 20)]
    ops = [("%f = f32[] fusion()", 100, 200, 1, "jit(f)/head/add:")]
    (tmp_path / "a").mkdir()
    (tmp_path / "a" / "x.xplane.pb").write_bytes(
        _xspace(ops, [("jit_f(1)", 100, 200)], host))
    rec = {"trace": {"window_s": 20e-6, "busy_s": 0.1e-6}}
    t = P.load(rec, tmp_path)
    assert t["n_spans"] == 1 and t["window_s"] == pytest.approx(20e-6)
    assert P.load({"trace": {"window_s": 5.0}}, tmp_path) is None
    assert P.load({"trace": {}}, tmp_path) is None


# ---------------------------------------------------------------- readers

def _rec(spans, steps=4, trace=None):
    return {"window": (100.0, 110.0), "span_clock0": 100.0,
            "spans": spans, "counters": {"decode_steps": steps},
            "trace": trace or {}}


def _x(name, ts_s, dur_s, tid=998, args=None):
    e = {"name": name, "ph": "X", "tid": tid, "ts": ts_s * 1e6,
         "dur": dur_s * 1e6, "q": 0}
    if args:
        e["args"] = args
    return e


def test_decode_host_ms_sums_fetch_and_guard_in_the_window():
    spans = [_x("decode.block", 1.0, 0.5), _x("decode.launch", 1.0, 0.3),
             _x("decode.fetch", 1.3, 0.02), _x("decode.guard", 1.32, 0.01),
             _x("decode.fetch", 2.0, 0.03),
             _x("decode.fetch", 10.5, 0.2)]       # after the window
    assert _reader("decode_host_ms")(_rec(spans)) == pytest.approx(
        1e3 * 0.06 / 4)
    # a program without the spans, or a run that kept none: nothing read
    assert _reader("decode_host_ms")(_rec([_x("decode.block", 1, 1)])) \
        is None
    assert _reader("decode_host_ms")(_rec(None)) is None


def test_admit_host_ms_is_the_self_time_per_admission():
    spans = [
        _x("prefill.chunk", 1.0, 0.3, tid=2),
        _x("prefill.chunk", 1.35, 0.3, tid=2),
        _x("admit.first_token", 1.7, 0.05, tid=2),
        _x("engine.admit", 0.9, 0.9, tid=2, args={"rid": 1}),   # self 0.25
        _x("decode.block", 1.0, 0.5),                           # other track
        _x("swap.gather", 3.05, 0.02, tid=3),
        _x("prefill.chunk", 3.1, 0.2, tid=3),
        _x("engine.admit", 3.0, 0.35, tid=3, args={"rid": 2}),  # self 0.13
    ]
    assert _reader("admit_host_ms")(_rec(spans)) == pytest.approx(
        1e3 * (0.25 + 0.13) / 2)
    assert P.self_seconds(_rec(spans), "engine.admit") == pytest.approx(
        [0.25, 0.13])
    assert _reader("admit_host_ms")(_rec([_x("prefill.chunk", 1, 1)])) \
        is None


@pytest.mark.parametrize("name", ["decode_host_idle_share",
                                  "decode_attention_ms"])
def test_device_trace_readers_read_nothing_without_a_trace(name):
    assert _reader(name)(_rec([])) is None


def test_device_trace_readers_on_a_reduced_trace(monkeypatch):
    t = {"window_s": 10.0, "n_spans": 3,
         "idle_by_span": {"decode.fetch": 0.4, "decode.guard": 0.1,
                          P.NO_SPAN: 0.5},
         "device_by_scope": {(DECODE, "attention/core"): 0.6,
                             (DECODE, "attention/qkv"): 0.2,
                             (DECODE, "ffn"): 0.3,
                             ("jit_engine_prefill", "attention/core"): 1.0}}
    monkeypatch.setattr(P, "load", lambda rec: t)
    rec = _rec([], steps=8, trace={"window_s": 10.0, "busy_s": 9.0})
    assert _reader("decode_host_idle_share")(rec) == pytest.approx(5.0)
    assert _reader("decode_attention_ms")(rec) == pytest.approx(
        1e3 * 0.8 / 8)
    # a program without spans or scopes (the trace of an older program)
    t.update(n_spans=0, idle_by_span={P.NO_SPAN: 1.0},
             device_by_scope={("jit__lambda", P.UNSCOPED): 9.0})
    assert _reader("decode_host_idle_share")(rec) is None
    assert _reader("decode_attention_ms")(rec) is None
