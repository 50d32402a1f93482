"""Reduce a profiler trace to the partitioner's own spans and device scopes.

``reduce_trace(log_dir, span)`` reads the same ``.xplane.pb`` as
``bench/trace.py`` and returns, over the window of host span ``span``:

* ``scopes``: device seconds of the op events that run inside a
  ``jit_uncoarsen_level`` module execution, keyed by the innermost
  ``jet.*``, ``uncoarsen.*`` or ``initial`` component of the op's metadata
  path (``jax.named_scope`` in ``core/partition.py`` and ``core/refine.py``);
  ops with none go to ``other``.  An op's seconds are its self time, its
  duration less the ops nested in it (a ``while`` holds its body's ops), so
  the table, ``other`` included, sums to the device's busy time inside those
  modules (``uncoarsen_s``);
* ``initial_s``: the device seconds of the initial partition, the ops that
  run inside a ``jit__initial_batch`` or ``jit__initial_fleet`` execution,
  and ``initial_scopes`` the same seconds by scope (the ``initial`` scope
  of ``core/initial.py``, and ``other`` for ops outside it);
* ``levels``: per ``partition`` span, the device seconds of each
  ``jit_uncoarsen_level`` execution that starts in it, matched in order to
  the ``uncoarsen.level`` spans that start in it, as ``[level, seconds]``
  (coarsest first); None for a partition whose two counts differ;
* ``partitions``: how many ``partition`` (or ``partition_fleet``) spans
  the window holds;
* ``idle_by_span``: the device's idle seconds in the window, keyed by the
  innermost program span open at the middle of each gap;
* ``host_self``: host self time per program span name: its duration less
  what the program spans nested in it cover.

A program span is a host event named ``partition``, ``partition_fleet`` or
``partition.*``, ``coarsen.*``, ``uncoarsen.*``, ``serve.*``
(``core/spans.py``); the profiler's own events and Python frames are not.
A trace of a program without them gives empty tables, no levels and no
partitions, and never an error.

Where the metadata path lives (checked by hand on a v5e trace): an ``XLA
Ops`` event of a TPU device plane is named by its HLO instruction's text
(``%fusion.163 = s32[4096]... fusion(...)``), and the stats that
``jax.profiler.ProfileData`` gives for it are only ``device_offset_ps``,
``device_duration_ps`` and ``Time Scale Multiplier``.  The op's
``metadata.op_name`` (the stat ``tf_op`` of the event's metadata, which
``ProfileData`` does not expose) is read instead from the HLO proto that
the profiler stores for each program in its ``/host:metadata`` plane,
keyed by the module's name, ``jit_uncoarsen_level(<program id>)``, as on
the ``XLA Modules`` event around the op.  :func:`hlo_op_names` reads those
protos from the file's bytes with a small protobuf reader.  A path looks
like ``jit(uncoarsen_level)/vmap(jit(_refine_loop))/while/body/jet.rs/...``;
a scope set inside ``vmap`` shows as ``vmap(uncoarsen.project)``, so a
component is read with its transform wrappers stripped.

The pure functions below take plain tuples, so the arithmetic is tested
without a trace.

Run as a script, it measures one cell as ``bench/run.py --trace 1`` does
and prints this reduction of the traced window as one JSON line, beside
the harness's own (``busy_s``, ``idle_share``, ``device_ops``) and the
driver's ``info``:

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

``bench/run.py`` does not call it: its result line and per-layer metrics
are the accepted benchmark's.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

if __name__ == "__main__":
    # as in bench/run.py: bench/ itself must not be on the path
    sys.path[0] = str(Path(__file__).resolve().parents[1])
    sys.path.insert(1, str(Path(sys.path[0]) / "src"))

from bench import trace  # noqa: E402

METADATA_PLANE = "/host:metadata"
UNCOARSEN_MODULE = "jit_uncoarsen_level"
INITIAL_MODULES = ("jit__initial_batch", "jit__initial_fleet")
PARTITION_SPANS = ("partition", "partition_fleet")
LEVEL_SPAN = "uncoarsen.level"
NO_SPAN = "(no program span)"
_SPAN_PREFIXES = ("partition.", "coarsen.", "uncoarsen.", "serve.")
_WRAPPER = re.compile(r"^(?:[\w.-]*\()+|\)+$")
_INSTRUCTION = re.compile(r"^%?([^\s=]+)")


def is_program_span(name: str) -> bool:
    return name in PARTITION_SPANS or name.startswith(_SPAN_PREFIXES)


def scope_of(path: str) -> str:
    """The innermost scope component of an op's metadata path."""
    for part in reversed(path.split("/")):
        part = _WRAPPER.sub("", part)
        if part == "initial" or part.startswith(("jet.", "uncoarsen.")):
            return part
    return "other"


def self_times(events) -> list[float]:
    """Self time of each ``(start, end, ...)`` event, in the order given:
    its duration less the events nested in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    out = [e[1] - e[0] for e in events]
    stack: list[int] = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            out[p] -= min(e, events[p][1]) - s
        stack.append(i)
    return out


def _module_index(modules):
    """Sorted module executions and their starts, for lookups by time."""
    mods = sorted(modules)
    return mods, [m[0] for m in mods]


def _containing(t: float, mods, starts) -> int | None:
    i = bisect.bisect_right(starts, t) - 1
    return i if i >= 0 and t < mods[i][1] else None


def scope_table(ops, modules):
    """Device seconds by scope of the ``(start, end, path)`` ops that start
    inside one of ``modules`` (``(start, end)``), and the seconds of each
    module in the order given."""
    mods, starts = _module_index(modules)
    rank = {m: i for i, m in enumerate(modules)}
    per_module = [0.0] * len(modules)
    scopes: dict[str, float] = defaultdict(float)
    for (s, _, path), self_ns in zip(ops, self_times(ops)):
        j = _containing(s, mods, starts)
        if j is None:
            continue
        scopes[scope_of(path)] += self_ns / 1e9
        per_module[rank[mods[j]]] += self_ns / 1e9
    return dict(scopes), per_module


def _starting_in(items, lo: float, hi: float) -> list:
    return [x for x in items if lo <= x[0] < hi]


def match_levels(partitions, level_spans, modules, module_s):
    """Per partition span ``(start, end)``: ``[level, seconds]`` of each
    module execution starting in it, paired in order with the level spans
    ``(start, end, level)`` starting in it; None where the counts differ."""
    out = []
    for lo, hi in partitions:
        spans = _starting_in(sorted(level_spans), lo, hi)
        execs = sorted((m[0], i) for i, m in enumerate(modules)
                       if lo <= m[0] < hi)
        if len(spans) != len(execs) or not spans:
            out.append(None)
            continue
        out.append([[sp[2], module_s[i]] for sp, (_, i) in zip(spans, execs)])
    return out


def idle_by_span(op_intervals, spans, lo: float, hi: float) -> dict:
    """Idle seconds of the device in ``[lo, hi]``, keyed by the innermost
    program span ``(start, end, name)`` open at the middle of each gap."""
    spans = sorted(spans, key=lambda h: (h[0], h[0] - h[1]))
    starts = [h[0] for h in spans]
    by: dict[str, float] = defaultdict(float)
    for s, e in trace.gaps(op_intervals, lo, hi):
        label = trace.label_at((s + e) / 2, spans, starts)
        by[NO_SPAN if label == "(no host span)" else label] += (e - s) / 1e9
    return dict(by)


def host_self(spans) -> dict:
    """Host self seconds by span name of ``(start, end, name, thread)``
    spans: nesting is counted within one thread only."""
    by_thread = defaultdict(list)
    for sp in spans:
        by_thread[sp[3]].append(sp)
    out: dict[str, float] = defaultdict(float)
    for evs in by_thread.values():
        for sp, self_ns in zip(evs, self_times(evs)):
            out[sp[2]] += self_ns / 1e9
    return dict(out)


def summarize(ops, modules, spans, lo: float, hi: float) -> dict:
    """The reduction over window ``[lo, hi]`` (ns) from plain events of one
    device: ``ops`` ``(start, end, path)``, ``modules`` ``(start, end,
    name)``, and program ``spans`` ``(start, end, name, thread, args)``."""
    ops = [o for o in ops if lo <= o[0] < hi]
    spans = [sp for sp in spans if lo <= sp[0] < hi]

    def executions(names):
        return [(s, e) for s, e, name in modules if lo <= s < hi
                and re.sub(r"\(\d+\)$", "", name) in names]

    unc = executions((UNCOARSEN_MODULE,))
    scopes, module_s = scope_table(ops, unc)
    initial_scopes, initial_s = scope_table(ops, executions(INITIAL_MODULES))
    parts = [(s, e) for s, e, name, *_ in spans if name in PARTITION_SPANS]
    level_spans = [(s, e, args.get("level")) for s, e, name, _, args in spans
                   if name == LEVEL_SPAN]
    return {
        "scopes": scopes,
        "uncoarsen_s": sum(module_s),
        "initial_s": sum(initial_s),
        "initial_scopes": initial_scopes,
        "levels": match_levels(parts, level_spans, unc, module_s),
        "partitions": len(parts),
        "idle_by_span": idle_by_span([(s, e) for s, e, _ in ops],
                                     [sp[:3] for sp in spans], lo, hi),
        "host_self": host_self([sp[:4] for sp in spans]),
    }


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, val


def _text(val) -> str:
    return bytes(val).decode("utf-8", "replace")


def _instruction_op_names(hlo) -> dict[str, str]:
    """Instruction name -> ``metadata.op_name`` of every instruction of an
    ``HloProto`` (hlo_module 1 > computations 3 > instructions 2 >
    name 1, metadata 7 > op_name 2)."""
    out = {}
    for f, module in _fields(hlo):
        if f != 1:
            continue
        for g, comp in _fields(module):
            if g != 3:
                continue
            for h, inst in _fields(comp):
                if h != 2:
                    continue
                name, op = None, ""
                for j, v in _fields(inst):
                    if j == 1:
                        name = _text(v)
                    elif j == 7:
                        op = next((_text(w) for m, w in _fields(v)
                                   if m == 2), "")
                if name:
                    out[name] = op
    return out


def hlo_op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Module name (``jit_f(<program id>)``), and program id alone, ->
    instruction name -> op_name, from the HLO protos in the
    ``/host:metadata`` plane of a serialized ``XSpace`` (planes 1 > name 2,
    event_metadata 4 > value 2 > id 1, name 2, stats 5 > bytes_value 6)."""
    out: dict[str, dict[str, str]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((_text(v) for k, v in fields if k == 2), "") != \
                METADATA_PLANE:
            continue
        for k, entry in fields:
            if k != 4:
                continue
            meta = next((v for m, v in _fields(entry) if m == 2), None)
            if meta is None:
                continue
            name, ident, names = "", None, {}
            for m, v in _fields(meta):
                if m == 1:
                    ident = v
                elif m == 2:
                    name = _text(v)
                elif m == 5:
                    for j, w in _fields(v):
                        if j == 6:
                            names.update(_instruction_op_names(w))
            if names:
                out[name] = out[str(ident)] = names
    return out


def resolve_paths(ops, modules, names):
    """``(start, end, path)`` of ``(start, end, event name)`` ops: the
    op_name of the op's instruction in the module execution ``(start,
    end, name)`` around it, from ``names`` (:func:`hlo_op_names`); an
    empty path where either is unknown."""
    mods, starts = _module_index(modules)
    out = []
    for s, e, label in ops:
        j = _containing(s, mods, starts)
        m = _INSTRUCTION.match(label)
        table = {}
        if j is not None:
            module = mods[j][2]
            table = names.get(module) or names.get(
                module.rsplit("(", 1)[-1].rstrip(")"), {})
        out.append((s, e, table.get(m.group(1), "") if m else ""))
    return out


def read_xplane(path: str, span: str) -> dict:
    """Plain events of one trace file: per device its ops and modules, the
    program spans, and the window of host span ``span``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    spans: list = []
    window = None
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            dev = {"ops": [], "modules": []}
            if trace.OPS_LINE in lines:
                dev["ops"] = [(ev.start_ns, ev.end_ns, ev.name)
                              for ev in lines[trace.OPS_LINE].events]
            if trace.MODULES_LINE in lines:
                dev["modules"] = [(ev.start_ns, ev.end_ns, ev.name)
                                  for ev in lines[trace.MODULES_LINE].events]
            devices[plane.name] = dev
        elif plane.name == trace.HOST_PLANE:
            for t, ln in enumerate(plane.lines):
                if ln.name.startswith("tf_"):
                    continue
                for ev in ln.events:
                    if window is None and ev.name == span:
                        window = (ev.start_ns, ev.end_ns)
                    if is_program_span(ev.name):
                        spans.append((ev.start_ns, ev.end_ns, ev.name, t,
                                      dict(ev.stats)))
    return {"devices": devices, "spans": spans, "window": window}


def reduce_trace(log_dir: str, span: str) -> dict:
    """The reduction of the newest trace under ``log_dir`` over host span
    ``span``, on the first device that ran an op in it; empty where there
    is no trace, no span or no device op."""
    path = trace.find_xplane(log_dir)
    if path is None:
        return {}
    ev = read_xplane(path, span)
    if ev["window"] is None:
        return {}
    lo, hi = ev["window"]
    used = [d for d in sorted(ev["devices"])
            if any(lo <= o[0] < hi for o in ev["devices"][d]["ops"])]
    if not used:
        return {}
    dev = ev["devices"][used[0]]
    with open(path, "rb") as f:
        names = hlo_op_names(f.read())
    ops = resolve_paths(dev["ops"], dev["modules"], names)
    return summarize(ops, dev["modules"], ev["spans"], lo, hi)


def main(argv=None) -> int:
    from bench import run as harness

    args = harness.parse_args(argv)
    cell, devices = harness.prepare(args.workload, harness.Registry())
    if devices is None:
        return 1
    ctx = harness.Context(config=cell["config"], traffic=cell["traffic"],
                          seed=args.seed, seconds=args.seconds, trace=True)
    out = cell["driver"].run(ctx)
    program = reduce_trace(ctx.tracer.log_dir, "bench.window")
    summary = ctx.trace_summary()  # removes the trace
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "program": program, "harness": summary,
                      "info": out["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
