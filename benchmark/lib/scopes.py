"""Where a step's device time goes, by the names inside the jitted programs.

The program opens a fixed set of ``jax.named_scope``s in its model files and
step programs (``nats_llm_studio_tpu/obs/spans.py SCOPE_NAMES``) and knows
what kind of program each jitted function is (``obs/roofline.py
program_kind``: prefill, decode, spec, other). A scope is part of an HLO
operation's ``op_name``; a fusion carries its root's. ``table`` makes ONE
table a traced run, which every reader under ``layer_metrics/`` shares:

    for every ``XLA Ops`` event that lies inside an ``XLA Modules`` launch
    WHOLLY inside the traced span (a launch the span's edge cuts is never
    counted: its operations are there, its module event is not whole), the
    launch's program and kind, the operation's scope (None: glue) and its
    nanoseconds; containers (``while``, ``conditional``, ``call``) left out
    as ``reduce_trace`` leaves them out.

Where the scope is found: see ``op_names``. Run as a module it prints the
whole table of a trace, for a builder or an operator's ``lmstudio.profile``
capture:

    python -m benchmark.lib.scopes <trace dir or .xplane.pb>

A program without the vocabulary (a parent commit under these files) gives
``None`` everywhere and never raises: a step that is all glue is a missing
vocabulary, not a reading.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

if __name__ == "__main__":  # run as a module from the checkout's root, or as a file
    sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.lib import reduce_trace, spans  # noqa: E402

# the sums of a kind's operations and of its launches may part by this much
# (time inside a launch that no operation covers); beyond it a reader gives None
SUMS_APART = 0.02
# scopes by the metric that reads them: a decode step's head holds the
# embedding (one row a slot), a prefill's head and embedding are glue
DECODE_GROUPS = {"seq": ("seq",), "ffn": ("ffn",), "head": ("head", "embed"), "mix": ("mix",)}
PREFILL_GROUPS = {"seq": ("seq",), "ffn": ("ffn",)}
_FINGERPRINT = re.compile(r"\((\d+)\)$")  # jit_prefill1(<fingerprint>)
_last: list = []  # [(planes, op_names handed in, table)] of the traced run read last


def _vocabulary():
    """(scope_of, program_kind) of the program, or None where it has none."""
    try:
        from nats_llm_studio_tpu.obs.roofline import program_kind
        from nats_llm_studio_tpu.obs.spans import scope_of
    except ImportError:
        return None
    return scope_of, program_kind


def op_names(src) -> dict[str, dict[str, str]]:
    """{program fingerprint: {``XLA Ops`` event name: its HLO instruction's
    ``op_name``}} of the traced run (two programs may each hold a
    ``%fusion.5``; the fingerprint is the number in the launch's name,
    ``jit_prefill1(<fingerprint>)``). A scope is a property of the instruction, not of each event:
    the trace keeps it once a distinct name, as the stat ``tf_op`` of the
    event's METADATA on the device plane (looked at by hand, PR 41: the
    event's name is the HLO line without its ``metadata={...}``, and
    ``ProfileData`` shows an event's own stats, offsets and durations, not
    its metadata's). So the file is read once more, its metadata only
    (``metadata_op_names``). A compiler-made operation (a layout ``copy``)
    has no ``tf_op`` and is glue. A test hands the mapping in under
    ``src["op_names"]``."""
    if "op_names" in src:
        return src["op_names"]
    try:
        path = src.get("trace_path") or reduce_trace.find_xplane(str(spans.TRACE_DIR))
    except FileNotFoundError:
        return {}
    return metadata_op_names(path)


def _fields(buf: memoryview):
    """(field number, wire type, value) of one protobuf message: a varint's
    number, or the bytes of a length-delimited field (not descended into)."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        val = shift = 0
        while True:
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return val

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, wire, varint()
        elif wire == 2:
            size = varint()
            yield field, wire, buf[i:i + size]
            i += size
        else:  # fixed64 / fixed32: a double or float stat, nothing read here
            i += 8 if wire == 1 else 4
            yield field, wire, None


def metadata_op_names(path: str, stat: str = "tf_op") -> dict[str, dict[str, str]]:
    """{``program_id``: {event metadata name: the string of its stat
    ``stat``}} over the device planes of an ``.xplane.pb``, by the wire
    format alone (XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5, both maps of id -> message; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64_value = 3, .str_value = 5,
    .ref_value = 7; XStatMetadata.name = 2). The lines, which are nearly all
    of the file, are stepped over, not decoded."""
    out: dict[str, dict[str, str]] = {}
    data = memoryview(Path(path).read_bytes())
    for field, wire, plane in _fields(data):
        if field != 1 or wire != 2:
            continue
        name, events, stats = "", [], {}
        for f, w, v in _fields(plane):
            if f == 2 and w == 2:
                name = bytes(v).decode()
            elif f in (4, 5) and w == 2:
                entry = next((vv for ff, ww, vv in _fields(v) if ff == 2 and ww == 2), None)
                if entry is None:
                    continue
                if f == 4:
                    events.append(entry)
                else:
                    md = {ff: vv for ff, ww, vv in _fields(entry) if ff in (1, 2)}
                    stats[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not reduce_trace.is_device_plane(name):
            continue
        ids = {n: i for i, n in stats.items()}
        for entry in events:
            ev_name, found, program = None, None, ""
            for f, w, v in _fields(entry):
                if f == 2 and w == 2:
                    ev_name = bytes(v).decode()
                elif f == 5 and w == 2:
                    st = {ff: vv for ff, ww, vv in _fields(v) if ff in (1, 3, 5, 7)}
                    if st.get(1) == ids.get(stat):
                        found = bytes(st[5]).decode() if 5 in st else stats.get(st.get(7), "")
                    elif st.get(1) == ids.get("program_id"):
                        program = str(st.get(3, ""))
            if ev_name and found:
                out.setdefault(program, {})[ev_name] = found
    return out


def table(src) -> dict | None:
    """The traced run's table (module docstring), or None where there is no
    device plane, no whole launch, or no vocabulary in the program.

    ``launches``  {program: {"kind", "n", "ns"}} of the whole launches
    ``ops``       {(program, scope or None): [ns, events]}
    ``glue``      {(program, "%name opcode"): [ns, events]} of the operations under no scope
    ``busy_ns``   the union of the operations' intervals between the first
                  whole launch's start and the last one's end, ``span_ns`` that stretch
    all averaged over the device planes as ``reduce_trace`` averages."""
    loaded, handed = spans.planes(src), src.get("op_names")
    if not _last or _last[0][0] is not loaded or _last[0][1] is not handed:
        _last[:] = [(loaded, handed, _table(src, loaded))]
    return _last[0][2]


def _table(src, loaded) -> dict | None:
    import numpy as np

    vocabulary = _vocabulary()
    devs = [l for n, l in (loaded or {}).items()
            if reduce_trace.is_device_plane(n) and l.get(reduce_trace.OPS_LINE)]
    if not devs or vocabulary is None:
        return None
    scope_of, program_kind = vocabulary
    named = op_names(src)
    launches: dict = {}
    ops: dict = {}
    glue: dict = {}
    busy = stretch = 0
    for lines in devs:
        # the trace is the span: a launch its edge cut is there with the part
        # of it that was seen (its module event starts with the trace, or ends
        # with it), so the first and the last of a plane are left out
        mods = sorted((s, s + d, n) for n, s, d in lines.get(reduce_trace.MODULES_LINE, []))[1:-1]
        if not mods:
            continue
        progs = []  # (program, fingerprint) of each whole launch
        for s, e, name in mods:
            prog, mark = reduce_trace.program_name(name), _FINGERPRINT.search(name)
            progs.append((prog, mark.group(1) if mark else ""))
            l = launches.setdefault(prog, {"kind": program_kind(prog), "n": 0, "ns": 0})
            l["n"] += 1
            l["ns"] += e - s
        # half a million events a span: one number a distinct (launch's
        # program, event name), summed by numpy, then named in a short loop
        events = lines[reduce_trace.OPS_LINE]
        ids: dict = {}
        name_id = np.fromiter((ids.setdefault(e[0], len(ids)) for e in events), np.int64,
                              len(events))
        start = np.fromiter((e[1] for e in events), np.int64, len(events))
        dur = np.fromiter((e[2] for e in events), np.int64, len(events))
        at = np.searchsorted(np.array([m[0] for m in mods]), start, side="right") - 1
        ends = np.array([m[1] for m in mods])
        inside = (at >= 0) & (start + dur <= ends[np.maximum(at, 0)])
        prog_ids: dict = {}
        prog_of = np.array([prog_ids.setdefault(pm, len(prog_ids)) for pm in progs])
        key = prog_of[at[inside]] * len(ids) + name_id[inside]
        keys, where, counts = np.unique(key, return_inverse=True, return_counts=True)
        sums = np.bincount(where, weights=dur[inside].astype(np.float64))
        names, pairs = list(ids), list(prog_ids)
        contained = np.zeros(len(keys), bool)
        for k, (code, ns, n) in enumerate(zip(keys.tolist(), sums.tolist(), counts.tolist())):
            (prog, mark), name = pairs[code // len(ids)], names[code % len(ids)]
            label, opcode = reduce_trace.op_label(name)
            if opcode in reduce_trace.CONTAINERS:
                contained[k] = True
                continue
            scope = scope_of(named.get(mark, {}).get(name, ""))
            cells = [ops.setdefault((prog, scope), [0, 0])]
            if scope is None:
                cells.append(glue.setdefault((prog, label), [0, 0]))
            for cell in cells:
                cell[0] += ns
                cell[1] += n
        # busy: the union of the counted operations' intervals
        counted = ~contained[where]
        s0, e0 = start[inside][counted], (start + dur)[inside][counted]
        order = np.argsort(s0, kind="stable")
        s0, e0 = s0[order], e0[order]
        before = np.concatenate(([s0[0]], np.maximum.accumulate(e0)[:-1])) if len(s0) else s0
        busy += int(np.maximum(e0 - np.maximum(s0, before), 0).sum())
        stretch += mods[-1][1] - mods[0][0]
    if not launches or not any(scope for _, scope in ops):
        return None
    n = len(devs)
    for l in launches.values():
        l["n"] /= n
        l["ns"] /= n
    for cells in (ops, glue):
        for cell in cells.values():
            cell[0] /= n
            cell[1] /= n
    return {"launches": launches, "ops": ops, "glue": glue, "busy_ns": busy / n,
            "span_ns": stretch / n}


def _top(scope: str | None) -> str | None:
    return scope.split("/")[0] if scope else None


def kind_split(src, kind: str) -> dict | None:
    """What the launches of one kind hold: ``{"launches", "ns", "by_top":
    {top-level scope or None: ns}, "forward"}``. ``forward`` counts the
    launches of the programs that run the model's layers (``seq`` or ``ffn``
    among their scopes): a finish, which holds the sampling alone
    (``head/sample``), is not one. None where the span holds no whole launch
    of the kind, or where operations and launches part by more than
    ``SUMS_APART``: more operation time than launch time means something was
    counted twice, less means time inside the launches that no operation
    covers."""
    t = table(src)
    if t is None:
        return None
    progs = {p: l for p, l in t["launches"].items() if l["kind"] == kind}
    total = sum(l["ns"] for l in progs.values())
    by_top: dict = {}
    for (prog, scope), (ns, _) in t["ops"].items():
        if prog in progs:
            by_top[_top(scope)] = by_top.get(_top(scope), 0) + ns
    if not total or abs(sum(by_top.values()) - total) > SUMS_APART * total:
        return None
    layers = sum(PREFILL_GROUPS.values(), ())
    modelled = {p for (p, scope) in t["ops"] if _top(scope) in layers and p in progs}
    return {"launches": sum(l["n"] for l in progs.values()), "ns": total, "by_top": by_top,
            "forward": sum(progs[p]["n"] for p in modelled)}


def _steps_a_launch(src) -> float | None:
    """Steps of a decode launch: the mean ``steps`` of the ``batcher.dispatch``
    spans of decode bursts that lie in the traced span (the program's own
    count: a burst near a row's capacity is shorter)."""
    lo, hi = spans.traced_span(src)
    steps = [a["steps"] for _, t0, _, a in spans.window_records(src, "batcher.dispatch") or []
             if lo <= t0 < hi and a and a.get("program") in ("decode", "ext") and a.get("steps")]
    return sum(steps) / len(steps) if steps else None


def _under(by_top: dict, groups: dict, group: str | None) -> float | None:
    """The nanoseconds under one group's scopes (None: under no group's, the
    glue); None where the kind holds nothing under that group."""
    named = sum(groups.values(), ())
    tops = groups[group] if group else [t for t in by_top if t not in named]
    return sum(by_top[t] for t in tops if t in by_top) if any(t in by_top for t in tops) else None


def decode_ms_per_step(src, group: str | None) -> float | None:
    """Device ms of a decode step under the scopes of ``DECODE_GROUPS[group]``
    (None: under no scope of the model, the glue)."""
    split, steps = kind_split(src, "decode"), _steps_a_launch(src)
    ns = _under(split["by_top"], DECODE_GROUPS, group) if split and steps else None
    return None if ns is None else ns / 1e6 / (split["launches"] * steps)


def prefill_ms_per_launch(src, group: str | None) -> float | None:
    """Device ms of a prefill launch under the scopes of
    ``PREFILL_GROUPS[group]`` (None: everything else: glue, and the one row
    of head, embedding and mixers). A launch here is one that runs the model
    (a chunk, a fused admit); the finishes and the prefix copies that go with
    it hold no layer of the model, and their time is shared out over those
    launches as glue (a finish's sampling too), so that the three sum to
    what a prompt's chunk costs."""
    split = kind_split(src, "prefill")
    ns = _under(split["by_top"], PREFILL_GROUPS, group) if split and split["forward"] else None
    return None if ns is None else ns / 1e6 / split["forward"]


def prefill_device_share(src) -> float | None:
    """Device seconds of the prefill-kind launches over the busy seconds of
    the same stretch (first whole launch to last), in per cent."""
    t = table(src)
    if t is None or not t["busy_ns"]:
        return None
    prefill = sum(l["ns"] for l in t["launches"].values() if l["kind"] == "prefill")
    return 100.0 * prefill / t["busy_ns"]


def main(argv: list[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[-2], file=sys.stderr)
        return 2
    path = argv[0] if argv[0].endswith(".xplane.pb") else reduce_trace.find_xplane(argv[0])
    src = {"planes": reduce_trace.load_planes(path), "trace_path": path}
    t = table(src)
    if t is None:
        print("no whole launch of a program with scopes in this trace", file=sys.stderr)
        return 1
    print(f"busy {t['busy_ns'] / 1e9:.4f} s of {t['span_ns'] / 1e9:.4f} s "
          "(first whole launch to last)")
    print(f"{'program':34} {'kind':8} {'scope':14} {'seconds':>10} {'events':>9} {'mean us':>9}")
    for prog, l in sorted(t["launches"].items(), key=lambda kv: -kv[1]["ns"]):
        print(f"{prog:34} {l['kind']:8} {'(launches)':14} {l['ns'] / 1e9:10.5f} "
              f"{l['n']:9.0f} {l['ns'] / l['n'] / 1e3:9.1f}")
        rows = sorted(((s, v) for (p, s), v in t["ops"].items() if p == prog),
                      key=lambda kv: -kv[1][0])
        for scope, (ns, n) in rows:
            print(f"{'':34} {'':8} {scope or '(glue)':14} {ns / 1e9:10.5f} {n:9.0f} "
                  f"{ns / n / 1e3:9.1f}")
    print("\nglue by operation (under no scope), the 40 largest")
    for (prog, label), (ns, n) in sorted(t["glue"].items(), key=lambda kv: -kv[1][0])[:40]:
        print(f"{prog:34} {label[:60]:60} {ns / 1e9:10.5f} {n:9.0f} {ns / n / 1e3:9.1f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
