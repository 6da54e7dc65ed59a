"""Device time by the program's own scopes.

The program names its device work from inside (``jax.named_scope`` at every
stage of the round, ``obs/names.py`` SCOPE_*; flax names its modules), and
XLA keeps that path on every instruction as ``metadata={op_name=...}``. A
profiler trace carries it on each device op's *event metadata* as the stat
``tf_op`` (beside ``hlo_category``, ``flops``, ``bytes_accessed``,
``program_id``); ``jax.profiler.ProfileData`` does not expose those, which
is why ``trace_reduce`` names an op by its HLO text alone.

This module joins the two: self seconds by op, which ``trace_reduce.reduce``
already computes (``ops_s``, keyed by the op's HLO text), and the op's path,
from one of two sources that hold the same string:

- :func:`xplane_op_meta`: the ``.xplane.pb`` itself, read with a small
  decoder of the protobuf wire format (no dependency beyond the standard
  library). Authoritative, and the only source of ``flops`` and
  ``bytes_accessed``.
- :func:`live_op_meta`: the HLO text of the executables alive in this
  process (``client.live_executables()``). Inside a benchmark run the
  harness deletes the profile directory before any reader runs, so this is
  what the per-layer readers use. The two agree, op for op, on 98-99.6%
  of device time; the rest is copies and pads the compiler inserted, which
  carry a path in the xplane and none in the text (PERF.md, PR 23).

``scopes.json`` holds the classification as data: ordered rules, first
match wins, for two partitions of device self time (``phase`` and
``stage``). :func:`build` applies them; :func:`read` is the reader behind
every ``*_time_share_pct`` metric that names a scope class.
"""

from __future__ import annotations

import functools
import json
import os
import re
import struct
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
RULES = os.path.join(BENCH, "scopes.json")
TOP_SCOPES = 40
#: classes every partition ends with: an op whose path matched no rule,
#: and an op that carries no path at all (compiler-inserted copies, say)
OTHER, UNSCOPED = "other_scoped", "unscoped"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
#: stats of an op's event metadata that are kept
META_STATS = ("tf_op", "hlo_category", "flops", "bytes_accessed",
              "program_id")
HLO_LINE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


# ---------- the xplane's event metadata (wire format) ----------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """``(field number, wire type, value)`` of one serialized message:
    an int for varints and fixed widths, the raw bytes for type 2."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for number, _, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf: bytes, stat_names: dict[int, str]):
    """``(name, value)`` of one XStat; a ``ref_value`` is an interned
    string, kept as the name of another stat metadata entry."""
    name, value = None, None
    for number, wire, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:  # double_value, fixed64
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif number in (3, 4):  # uint64 / int64
            value = v - (1 << 64) if number == 4 and v >> 63 else v
        elif number in (5, 6):
            value = v.decode("utf-8", "replace")
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def xplane_op_meta(path: str) -> dict[str, dict]:
    """``{op text: {tf_op, hlo_category, flops, bytes_accessed,
    program_id}}`` over the device planes of an ``.xplane.pb``: the seven
    messages XSpace, XPlane, XEventMetadata, XStatMetadata, XStat (read)
    and XLine, XEvent (skipped: ``trace_reduce`` has the events)."""
    with open(path, "rb") as f:
        space = f.read()
    out: dict[str, dict] = {}
    for number, _, plane in _fields(space):
        if number != 1:
            continue
        name, event_meta, stat_names = "", [], {}
        for n, _, v in _fields(plane):
            if n == 2:
                name = v.decode()
            elif n == 4:
                event_meta.append(_map_entry(v)[1])
            elif n == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = next(
                    (s.decode() for k, _, s in _fields(meta) if k == 2), "")
        if not DEVICE_PLANE.match(name):
            continue
        for meta in event_meta:
            text, stats = "", {}
            for n, _, v in _fields(meta):
                if n == 2:
                    text = v.decode("utf-8", "replace")
                elif n == 5:
                    key, value = _stat(v, stat_names)
                    if key in META_STATS:
                        stats[key] = value
            if text and stats:
                out[text] = stats
    return out


# ---------- the same paths from the executables of this process ----------

def hlo_op_meta(text: str, module: str = "") -> dict[str, list]:
    """``{instruction name: [(module, instruction text, op_name)]}`` of
    one HLO module's text."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        m = HLO_LINE.match(line)
        if m is None:
            continue
        path = HLO_OP_NAME.search(line)
        out.setdefault(m.group(1), []).append(
            (module, m.group(2), path.group(1) if path else ""))
    return out


def live_op_meta() -> dict[str, list]:
    """:func:`hlo_op_meta` over every executable alive in this process.
    An executable whose text cannot be had is skipped: its ops then read
    as ``unscoped``, which the guard metric shows."""
    import jax

    out: dict[str, list] = {}
    for exe in jax.devices()[0].client.live_executables():
        try:
            modules = exe.hlo_modules()
        except Exception:  # noqa: BLE001 - a runtime without the text
            continue
        for module in modules:
            for name, rows in hlo_op_meta(module.to_string(),
                                          module.name).items():
                out.setdefault(name, []).extend(rows)
    return out


def join_live(op_texts, live: dict[str, list], ran=()) -> dict[str, dict]:
    """``{op text: {tf_op}}`` for the trace's ops. An op is found by its
    instruction name, among the modules that ran in the trace (``ran``:
    their names, as the "XLA Modules" line has them) where any has it;
    where several still have that name with different paths, the one
    whose instruction text shares the longest prefix with the trace's
    (result shape, opcode, operands) is taken."""
    out: dict[str, dict] = {}
    for text in op_texts:
        m = HLO_LINE.match(text)
        rows = live.get(m.group(1), ()) if m else ()
        rows = [r for r in rows if r[0] in ran] or rows
        if not rows:
            continue
        if len({r[2] for r in rows}) > 1:
            body = m.group(2).replace("%", "")
            rows = [max(rows, key=lambda r: len(os.path.commonprefix(
                [r[1].replace("%", ""), body])))]
        out[text] = {"tf_op": rows[0][2]}
    return out


# ---------- classification ----------

@functools.lru_cache(maxsize=None)
def load_rules(path: str = RULES) -> dict:
    with open(path) as f:
        doc = json.load(f)
    return {part: [(r["class"],
                    re.compile(r["path"]) if "path" in r else None,
                    re.compile(r["name"]) if "name" in r else None)
                   for r in doc[part]]
            for part in doc["partitions"]}


def scope_path(tf_op: str) -> str:
    """``tf_op`` is ``<op_name>:<op type>``; the path is the op_name with
    a slash at both ends, so that a rule spells a scope as ``/name/``."""
    return "/" + tf_op.rsplit(":", 1)[0] + "/"


def classify(rules: list, tf_op: str, op_text: str) -> str:
    name = op_text.partition(" = ")[0]
    path = scope_path(tf_op) if tf_op else ""
    for cls, path_rx, name_rx in rules:
        m = ((path_rx.search(path) if path_rx and path else None)
             or (name_rx.search(name) if name_rx else None))
        if m:
            return m.expand(cls)
    return OTHER if tf_op else UNSCOPED


def build(ops_s: dict[str, float], meta: dict[str, dict],
          rules: dict | None = None) -> dict:
    """Device self seconds by class, for every partition of the rules,
    and by scope (the path without its last segment, the primitive), the
    ``TOP_SCOPES`` largest: ``[scope, seconds, flops, bytes_accessed]``,
    the last two where the source has them."""
    rules = rules or load_rules()
    total = sum(ops_s.values())
    parts: dict[str, dict[str, float]] = {p: {} for p in rules}
    by_scope: dict[str, list] = {}
    for text, seconds in ops_s.items():
        if not seconds:  # a while loop's time is all its body's
            continue
        m = meta.get(text, {})
        tf_op = m.get("tf_op") or ""
        for part, rows in rules.items():
            cls = classify(rows, tf_op, text)
            parts[part][cls] = parts[part].get(cls, 0.0) + seconds
        scope = (scope_path(tf_op).strip("/").rpartition("/")[0] or tf_op
                 if tf_op else UNSCOPED)
        row = by_scope.setdefault(scope, [0.0, 0, 0])
        row[0] += seconds
        row[1] += int(m.get("flops") or 0)
        row[2] += int(m.get("bytes_accessed") or 0)
    top = sorted(by_scope.items(), key=lambda kv: -kv[1][0])[:TOP_SCOPES]
    return {"busy_s": total,
            "share_pct": {p: {c: 100.0 * s / total for c, s in
                              sorted(d.items(), key=lambda kv: -kv[1])}
                          for p, d in parts.items()} if total else {},
            "scopes": [[k, *v] for k, v in top],
            "ops": len(ops_s), "ops_with_path": sum(
                1 for t in ops_s if meta.get(t, {}).get("tf_op"))}


# ---------- the reader ----------

def table_of(ctx: dict) -> dict | None:
    """The run's scope table, built once and kept on ``ctx`` (under the
    key a harness that reads the xplane itself would fill)."""
    if "scopes" not in ctx:
        tr = ctx.get("trace")
        if tr is None or not tr.get("ops_s"):
            ctx["scopes"] = None
        else:
            ctx["scopes"] = build(tr["ops_s"], join_live(
                tr["ops_s"], live_op_meta(), set(tr["modules_s"])))
            _publish(ctx["scopes"])
    return ctx["scopes"]


def _publish(table: dict) -> None:
    """Both partitions and the top scopes, as one line on standard error
    and one file under ``benchmark/out/scopes/`` (the run's own JSON is
    the harness's to write)."""
    line = json.dumps(table)
    print("[scopes] " + line, file=sys.stderr, flush=True)
    out = os.path.join(BENCH, "out", "scopes")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"{time.strftime('%Y%m%dT%H%M%S')}_{os.getpid()}.json"),
            "w") as f:
        f.write(line)


def read(spec: dict, ctx: dict):
    """Share of device busy time, in percent, in the ``classes`` of one
    ``partition``. ``None`` without a trace."""
    table = table_of(ctx)
    if not table or not table["share_pct"]:
        return None
    shares = table["share_pct"][spec["partition"]]
    return sum(shares.get(c, 0.0) for c in spec["classes"])


def main(argv: list[str]) -> int:
    """``python3 -m benchmark.scopes <trace.xplane.pb> [window span]``:
    the scope table of a recorded trace, from its own metadata."""
    from benchmark import trace_reduce

    reduced = trace_reduce.reduce(trace_reduce.load_xplane(argv[0]),
                                  argv[1] if len(argv) > 1 else None)
    print(json.dumps(build(reduced["ops_s"], xplane_op_meta(argv[0])),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
