"""Device time of one jitted program by the names the program gives its own
work: the share of ``module``'s device seconds, in the traced span, spent in
operations whose ``op_name`` holds ``part.<part>`` (``lzy_tpu/utils/trace.py``
``part``: a closed vocabulary of ``jax.named_scope``s; forward the name is a
component of the path, in a backward pass it sits inside
``transpose(jvp(...))``).

**Where the name is.** ``harness/trace.py`` ``op_label`` names an operation by
its kind and first result shape, because ``jax.profiler.ProfileData`` hands
out an event's own stats and not its *metadata's*. The metadata of every
``XLA Ops`` event of a device plane holds the stats ``tf_op`` (JAX's
``op_name``, then ``:`` and nothing), ``program_id`` (the number in the ``XLA
Modules`` event ``jit_decode_step(<id>)``), ``flops`` and ``bytes_accessed``.
This file reads them from the ``.xplane.pb`` itself with a decoder of the
protobuf wire format for the five message types involved (``XSpace``,
``XPlane``, ``XEventMetadata``, ``XStatMetadata``, ``XStat``); a plane's
``lines`` are skipped by their length, so what is decoded is a few thousand
metadata entries whatever the trace's size. Nothing is imported that
``benchmark/`` did not import before (``benchmark/tests/test_part_share.py``
holds the decoder to TensorFlow's ``xplane_pb2`` where that is installed).

**Whose time.** Device 0, as ``harness/trace.py`` ``reduce`` takes it. The
events of ``XLA Ops`` nest: a ``while`` lasts as long as its body, whose
operations are events of their own. An operation's time here is its *self*
time, its duration less its direct children's, so a loop's body is filed
under the body's names and the loop keeps its own bookkeeping; the parts of a
module then add up to no more than the time an operation ran in it. The
denominator is the module's own device seconds (its ``XLA Modules`` events),
so ``part="*"`` (any part) is the coverage: what share of the program's time
the program has named. A fusion goes where XLA's ``op_name`` for it goes: a
norm's reduction fused into the product that follows is the product's.

The xplane file is found as ``readers/placed_spans.py`` finds it, and loaded
through the same cached call, so a traced run pays one load for both."""

import bisect
import functools
import mmap
import re
import struct

from benchmark.harness import trace as xtrace
from benchmark.readers import placed_spans

PART = re.compile(r"(?:^|[/(])part\.([A-Za-z0-9_]+)")
_PROGRAM = re.compile(r"\((\d+)\)\s*$")
UNNAMED = "_unnamed_"

# -- the wire format: what five message types need -----------------------------


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf):
    """``(field number, wire type, value)`` of one message: an int for a
    varint or a fixed width, the bytes for a length-delimited field."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind == 1:
            value, at = int.from_bytes(buf[at:at + 8], "little"), at + 8
        elif kind == 5:
            value, at = int.from_bytes(buf[at:at + 4], "little"), at + 4
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield number, kind, value


def _signed(value: int) -> int:
    return value - (1 << 64) if value >> 63 else value


def _stat(buf):
    """``(metadata_id, value)`` of an ``XStat``; ``value`` is ``("ref", id)``
    where the string lives in the plane's ``stat_metadata``."""
    key = value = None
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = ("ref", v)
    return key, value


def _map_entry(buf):
    key = value = None
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def plane_metadata(plane) -> dict:
    """``{event metadata id: (name, {stat name: value})}`` of one ``XPlane``
    (its bytes), its ``lines`` skipped."""
    events, stat_names = [], {}
    for number, kind, v in fields(plane):
        if number == 4 and kind == 2:
            events.append(_map_entry(v)[1])
        elif number == 5 and kind == 2:
            ident, name = None, ""
            for n, _, w in fields(_map_entry(v)[1]):
                if n == 1:
                    ident = w
                elif n == 2:
                    name = bytes(w).decode("utf-8", "replace")
            stat_names[ident] = name
    out = {}
    for event in events:
        ident, name, stats = None, "", {}
        for n, _, w in fields(event):
            if n == 1:
                ident = w
            elif n == 2:
                name = bytes(w).decode("utf-8", "replace")
            elif n == 5:
                key, value = _stat(w)
                if isinstance(value, tuple):
                    value = stat_names.get(value[1], "")
                stats[stat_names.get(key, key)] = value
        out[ident] = (name, stats)
    return out


def device_metadata(path: str) -> dict:
    """``{plane name: plane_metadata}`` for the ``/device:TPU:<n>`` planes of
    an ``.xplane.pb``."""
    # mapped, not read: a trace is hundreds of megabytes of ``lines`` that
    # are stepped over
    with open(path, "rb") as f:
        space = memoryview(mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ))
    out = {}
    for number, kind, plane in fields(space):
        if number != 1 or kind != 2:
            continue
        name = next((bytes(v).decode("utf-8", "replace")
                     for n, k, v in fields(plane) if n == 2 and k == 2), "")
        if name.startswith("/device:TPU:"):
            out[name] = plane_metadata(plane)
    return out


def names_of(metadata: dict) -> dict:
    """``{(program_id, HLO text): stats}`` of one plane's operations: the
    join from an ``XLA Ops`` event (``harness/trace.py`` ``load`` gives its
    HLO text, its module event the program's id) to its ``tf_op``."""
    return {(stats["program_id"], name): stats
            for name, stats in metadata.values() if "program_id" in stats}


def part_of(tf_op) -> str:
    """The one part an ``op_name`` holds, or ``UNNAMED``."""
    found = PART.search(tf_op or "")
    return found.group(1) if found else UNNAMED


# -- from events to a table -----------------------------------------------------


def self_times(events: list):
    """``(start, self duration, name)`` of each of a line's events: its
    duration less its direct children's (an event that starts inside another
    and ends no later is its child)."""
    out, stack = [], []          # stack: [end, index into out]
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0]:
            out[stack[-1][1]][1] -= dur
        out.append([start, dur, name])
        stack.append([start + dur, len(out) - 1])
    return out


@functools.lru_cache(maxsize=1)
def operations(path: str) -> dict:
    """``{module: {"seconds": its device seconds, "runs": executions,
    "ops": {instruction: [self seconds, calls, label, tf_op, part, flops,
    bytes_accessed]}}}`` of device 0, or ``{}`` where the trace holds no
    device plane."""
    devices = placed_spans._load(path)["devices"]
    if not devices:
        return {}
    first = sorted(devices)[0]
    lines = devices[first]
    names = names_of(device_metadata(path).get(first, {}))
    programs = sorted(lines.get("XLA Modules", []))
    starts = [p[0] for p in programs]
    out: dict = {}
    for start, dur, text in programs:
        row = out.setdefault(xtrace.module_name(text),
                             {"seconds": 0.0, "runs": 0, "ops": {}})
        row["seconds"] += dur / 1e9
        row["runs"] += 1
    ids = [_PROGRAM.search(p[2]) for p in programs]
    rows: dict = {}              # (program, HLO text) -> its row in ``ops``
    for start, own, text in self_times(lines.get("XLA Ops", [])):
        at = bisect.bisect_right(starts, start) - 1
        if at < 0 or start >= programs[at][0] + programs[at][1]:
            continue
        row = rows.get((at, text))
        if row is None:
            stats = names.get((int(ids[at].group(1)), text), {}) \
                if ids[at] else {}
            tf_op = (stats.get("tf_op") or "").rstrip(":")
            ops = out[xtrace.module_name(programs[at][2])]["ops"]
            row = rows[(at, text)] = ops.setdefault(
                text.split(" = ", 1)[0].lstrip("%"),
                [0.0, 0, xtrace.op_label(text), tf_op, part_of(tf_op),
                 stats.get("flops", 0), stats.get("bytes_accessed", 0)])
        row[0] += own / 1e9
        row[1] += 1
    return out


@functools.lru_cache(maxsize=1)
def table(path: str) -> dict:
    """``{module: {"seconds": s, "parts": {part: self seconds}, "unnamed":
    {tf_op or label: self seconds}}}``."""
    out = {}
    for module, row in operations(path).items():
        parts: dict = {}
        unnamed: dict = {}
        for own, _, label, tf_op, part, _, _ in row["ops"].values():
            if part == UNNAMED:
                key = tf_op or label
                unnamed[key] = unnamed.get(key, 0.0) + own
            else:
                parts[part] = parts.get(part, 0.0) + own
        out[module] = {"seconds": row["seconds"], "parts": parts,
                       "unnamed": unnamed}
    return out


def read(obs, *, part, module, scale=100.0):
    """``part``: a name, a list of names (summed), or ``"*"`` (every part:
    the coverage). None where the run was not traced, the trace holds no
    ``tf_op``, or ``module`` names none of its work (a program without
    ``part`` scopes reports nothing, as ``op_share`` does for a kernel the
    program lacks)."""
    if "trace" not in obs:
        return None
    path = placed_spans.newest_trace()
    if path is None:
        return None
    row = table(path).get(module)
    if not row or not row["seconds"] or not row["parts"]:
        return None
    if part == "*":
        mine = sum(row["parts"].values())
    else:
        wanted = [part] if isinstance(part, str) else part
        mine = sum(row["parts"].get(p, 0.0) for p in wanted)
    return scale * mine / row["seconds"]
