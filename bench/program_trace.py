"""What the program was doing, read from its own spans and from the trace.

The program's ``TraceRecorder`` keeps every span in the run's record
(``rec["spans"]``, host clock) and, under the profiler, also opens a host
annotation named ``repro.<span>`` on the profiler's clock.  Its model code
runs under ``jax.named_scope``s (``embed``, ``attention`` with ``qkv``,
``kv_write``, ``core`` and ``out``, ``ffn`` or ``moe``, ``head``,
``cache_io``, and in RG-LRU and Mamba-2 blocks ``recurrent`` and ``ssm``),
which XLA keeps in each operation's metadata, and its two
engine programs are named (``jit_engine_prefill``, ``jit_engine_decode``).

From those this module gives:

* ``span_seconds`` / ``self_seconds``: span durations inside the window,
  from the run's record (no trace needed);
* ``idle_by_span``: each idle stretch of the device charged to the
  innermost ``repro.*`` annotation open then, or to ``NO_SPAN``;
* ``device_by_scope``: device seconds by (HLO module, scope path), each
  instant charged to the innermost operation on the "XLA Ops" line, so a
  ``while`` and the body it holds are counted once.

``load`` finds the profile of the run whose trace the record holds (the
newest under ``.bench_profile/`` whose ``bench.window`` is as long as the
record's) and reduces it once.  Every reader returns None where the
program has none of what it reads, as a program without these spans and
scopes has not.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

from bench import trace_reduce as T

ROOT = Path(__file__).resolve().parent.parent
PROFILE_DIR = ROOT / ".bench_profile"
PROGRAM_PREFIX = "repro."
NO_SPAN = "no program span"
UNSCOPED = "unscoped"
SCOPES = frozenset({"embed", "attention", "qkv", "kv_write", "core", "out",
                    "ffn", "moe", "head", "cache_io", "recurrent", "ssm"})
WINDOW = T.HOST_PREFIX + "window"
MODULES_LINE = "XLA Modules"
CACHE_KEY = "program_trace"     # where ``load`` keeps its reduction


# -- the run's record --------------------------------------------------------

def window_spans(rec: dict, names) -> list | None:
    """The recorder's complete spans named in ``names`` that lie inside the
    measured window, as event dicts; None when the run kept no spans."""
    if rec.get("spans") is None:
        return None
    t0, t1 = rec["window"]
    c0 = rec["span_clock0"]
    out = []
    for e in rec["spans"]:
        if e.get("ph") != "X" or e["name"] not in names:
            continue
        s = c0 + e["ts"] * 1e-6
        if t0 <= s and s + e["dur"] * 1e-6 <= t1:
            out.append(e)
    return out


def span_seconds(rec: dict, names) -> float | None:
    """Summed duration of the window's spans named in ``names``; None when
    there is none (a program without those spans)."""
    spans = window_spans(rec, names)
    if not spans:
        return None
    return 1e-6 * sum(e["dur"] for e in spans)


def self_seconds(rec: dict, name: str) -> list | None:
    """Self time of each window span called ``name``: its duration less
    that of its direct children on the same track."""
    if rec.get("spans") is None:
        return None
    parents = window_spans(rec, {name})
    if not parents:
        return None
    by_tid = defaultdict(list)
    for e in rec["spans"]:
        if e.get("ph") == "X":
            by_tid[e["tid"]].append(e)
    out = []
    for p in parents:
        a, b = p["ts"], p["ts"] + p["dur"]
        kids = sorted((e for e in by_tid[p["tid"]] if e is not p
                       and a <= e["ts"] and e["ts"] + e["dur"] <= b),
                      key=lambda e: (e["ts"], -e["dur"]))
        # spans on one track nest, so a child that starts before the
        # previous direct child ends lies inside it
        child, reach = 0.0, a
        for k in kids:
            if k["ts"] >= reach:
                child += k["dur"]
                reach = k["ts"] + k["dur"]
        out.append(1e-6 * (p["dur"] - child))
    return out


# -- the profiler trace ------------------------------------------------------

def scope_of(op_name: str) -> str:
    """The model scope path in an op_name ("jit(f)/while/body/attention/
    core/dot_general:" -> "attention/core"), or ``UNSCOPED``."""
    parts = [p for p in op_name.split("/") if p in SCOPES]
    return "/".join(parts) if parts else UNSCOPED


def _xplane_schema():
    """Message classes for the part of ``xplane.proto`` read here: each
    plane's name and its event and stat metadata (the op_name of an
    operation is a stat of its event metadata, ``tf_op``, which
    ``jax.profiler.ProfileData`` does not expose).  The lines, the bulk of
    the file, are left unparsed."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, typ, rep, tname in fields:
            m.field.add(name=fname, number=num, type=typ,
                        label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL,
                        type_name=tname)

    msg("Stat", ("metadata_id", 1, F.TYPE_INT64, 0, None),
        ("uint64_value", 3, F.TYPE_UINT64, 0, None),
        ("int64_value", 4, F.TYPE_INT64, 0, None),
        ("str_value", 5, F.TYPE_STRING, 0, None),
        ("ref_value", 7, F.TYPE_UINT64, 0, None))
    msg("EventMetadata", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None),
        ("stats", 5, F.TYPE_MESSAGE, 1, ".bench_xplane.Stat"))
    msg("StatMetadata", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None))
    # map<int64, V> fields, read as the repeated entries they are encoded as
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, ".bench_xplane.EventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, ".bench_xplane.StatMetadata"))
    msg("Plane", ("name", 2, F.TYPE_STRING, 0, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, 1,
         ".bench_xplane.EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, 1,
         ".bench_xplane.StatMetadataEntry"))
    msg("Space", ("planes", 1, F.TYPE_MESSAGE, 1, ".bench_xplane.Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.Space"))


def op_names(data: bytes) -> dict:
    """{device plane: {(program id, op event name): op_name}} from the
    raw ``.xplane.pb`` bytes."""
    space = _xplane_schema()()
    space.ParseFromString(data)
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(T.DEVICE_PLANE):
            continue
        stat = {e.key: e.value.name for e in plane.stat_metadata}
        names = {}
        for e in plane.event_metadata:
            pid, op = None, None
            for st in e.value.stats:
                k = stat.get(st.metadata_id)
                if k == "program_id":      # as the module's name has it
                    pid = (st.uint64_value or st.int64_value) % 2**64
                elif k == "tf_op":
                    op = stat.get(st.ref_value) if st.ref_value \
                        else st.str_value
            if op is not None:
                names[(pid, e.value.name)] = op
        out[plane.name] = names
    return out


def _module(name: str) -> tuple[str, int | None]:
    """"jit_engine_decode(1516...)" -> ("jit_engine_decode", 1516...)."""
    head, _, tail = name.partition("(")
    pid = tail.rstrip(")")
    return head, int(pid) if pid.isdigit() else None


def read_xplane(path: str):
    """(ops per device {plane: [((module, scope), start_s, end_s)]},
    program spans [(name, start_s, end_s)], the bench window or None).
    An operation's module is the "XLA Modules" event it runs inside; its
    scope comes from its op_name."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    names = op_names(data)
    pd = ProfileData.from_serialized_xspace(data)
    ops, spans, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith(T.DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           _module(e.name))
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            opn = names.get(plane.name, {})
            evs = sorted((e.start_ns, e.duration_ns, e.name)
                         for e in (lines[T.OPS_LINE].events
                                   if T.OPS_LINE in lines else ()))
            rows, m = [], 0
            for s, d, name in evs:
                while m < len(mods) and mods[m][1] < s:
                    m += 1
                mod, pid = mods[m][2] if m < len(mods) \
                    and mods[m][0] <= s else ("", None)
                key = (mod, scope_of(opn.get((pid, name), "")))
                rows.append((key, s * 1e-9, (s + d) * 1e-9))
            ops[plane.name] = rows
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        spans.append((e.name[len(PROGRAM_PREFIX):],
                                      e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
                    elif e.name == WINDOW:
                        window = (e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
    return ops, spans, window


def _inside(events, t0: float, t1: float) -> list:
    """The (label, start, end) events that overlap [t0, t1] and last: an
    event of no duration holds no time, and ``innermost_segments`` would
    meet its end before its start."""
    return [x for x in events if x[2] > x[1] and x[2] > t0 and x[1] < t1]


def idle_by_span(ops_by_device: dict, spans, window) -> dict:
    """{label: idle seconds} of the first device inside ``window``, each
    idle stretch charged to the innermost program span open then, or to
    ``NO_SPAN``."""
    t0, t1 = window
    if not ops_by_device or t1 <= t0:
        return {}
    first = sorted(ops_by_device)[0]
    busy = T.busy_intervals(ops_by_device[first], t0, t1)
    segs = T.innermost_segments(_inside(spans, t0, t1), t0, t1)
    idle = defaultdict(float)
    j = 0
    for g0, g1 in T.gaps(busy, t0, t1):
        while j < len(segs) and segs[j][1] <= g0:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < g1:
            a, b, label = segs[k]
            idle[NO_SPAN if label == T.NO_SPAN else label] += \
                min(b, g1) - max(a, g0)
            k += 1
    return dict(idle)


def device_by_scope(ops_by_device: dict, window) -> dict:
    """{(module, scope): device seconds} inside ``window``, mean over the
    devices; each instant goes to the innermost operation running then."""
    t0, t1 = window
    if not ops_by_device or t1 <= t0:
        return {}
    out = defaultdict(float)
    for ops in ops_by_device.values():
        for a, b, key in T.innermost_segments(_inside(ops, t0, t1), t0, t1):
            if key != T.NO_SPAN:
                out[key] += b - a
    n = len(ops_by_device)
    return {k: v / n for k, v in out.items()}


def reduce(ops_by_device: dict, spans, window) -> dict:
    return {"window_s": window[1] - window[0], "n_spans": len(spans),
            "idle_by_span": idle_by_span(ops_by_device, spans, window),
            "device_by_scope": device_by_scope(ops_by_device, window)}


def _profile_files(profile_dir: Path) -> list:
    files = Path(profile_dir).glob("**/*.xplane.pb")
    return sorted(files, key=lambda p: p.stat().st_mtime, reverse=True)


def load(rec: dict, profile_dir: Path = PROFILE_DIR) -> dict | None:
    """The reduction of the profile this run's trace came from, or None
    when the run has no trace.  Kept in the record, so the readers of one
    run parse the profile once."""
    t = rec.get("trace") or {}
    if not t:
        return None
    if CACHE_KEY not in rec:
        rec[CACHE_KEY] = None
        for path in _profile_files(profile_dir):
            ops, spans, window = read_xplane(str(path))
            if window and abs(window[1] - window[0] - t["window_s"]) < 1e-9:
                rec[CACHE_KEY] = reduce(ops, spans, window)
                break
    return rec[CACHE_KEY]


def module_scope_seconds(t: dict, module: str, scope: str) -> float | None:
    """Device seconds of ``module``'s operations under ``scope`` (and its
    sub-scopes); None when no operation of the module carries it."""
    secs = [v for (mod, sc), v in t["device_by_scope"].items()
            if mod.startswith(module)
            and (sc == scope or sc.startswith(scope + "/"))]
    return sum(secs) if secs else None
