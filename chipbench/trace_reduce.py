"""From a profiler trace (``.xplane.pb``) to device busy / idle time,
per-program and per-operation device time, and the longest idle gaps
labelled by what the host was doing.

Two steps, so that the arithmetic can be checked on a small recorded
trace without the profiler: ``extract`` turns the xplane file into plain
event lists, ``reduce`` turns event lists into numbers.

The program has no ``named_scope`` / ``TraceAnnotation`` yet, so programs
and operations are matched by what XLA prints. A program execution is an
event of a device plane's "XLA Modules" line; the serving programs are
jitted ``functools.partial`` objects, which XLA names ``jit__unknown(<id>)``,
so a module is recognised by an operation it contains (``contains_op``: the
ragged program carries a (1, token budget, hidden) activation through its
layer loop, the decode program a (slots, 1, hidden) one). An operation is
an event of the "XLA Ops" line, named by its whole HLO text
(``%closed_call.30 = bf16[16,512,8,128]{...} custom-call(...)``). Events on
one line nest (a ``while`` holds the layer scan's body), so an operation is
charged its SELF time: its duration minus what its children cover.

Run as a child process (it imports jax for ``ProfileData``; it touches no
device); the arguments are in ``main``'s docstring.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
import sys

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MIN_HOST_EVENT_NS = 20_000   # host events shorter than this label no gap
MIN_GAP_NS = 100_000         # device gaps shorter than this are not listed
MIN_PROGRAM_NS = 10_000      # shorter modules (dtype casts) bound no gap


def _is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", name) is not None


def extract(path: str) -> dict:
    """xplane -> {"devices": {plane: {"modules": [...], "ops": [...]}},
    "host": {line: [...]}}; an event is [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES_LINE, OPS_LINE):
                    lines[line.name] = [
                        [e.name, int(e.start_ns), int(e.duration_ns)]
                        for e in line.events]
            devices[plane.name] = {"modules": lines.get(MODULES_LINE, []),
                                   "ops": lines.get(OPS_LINE, [])}
        elif plane.name == "/host:CPU":
            # the thread that dispatches the programs is the one whose
            # work can explain a gap on the device
            best = 0
            for line in plane.lines:
                evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                       for e in line.events
                       if e.duration_ns >= MIN_HOST_EVENT_NS]
                n = sum(1 for name, _, _ in evs
                        if name.startswith("PjitFunction"))
                if n > best:
                    best, host = n, {line.name: evs}
    return {"devices": devices, "host": host}


def union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events) -> list:
    """[name, self_ns] per event of one line, children subtracted."""
    out, stack = [], []   # stack of [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            n, _, s = stack.pop()
            out.append([n, s])
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    while stack:
        n, _, s = stack.pop()
        out.append([n, s])
    return out


def _label(text: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", text).strip("_")[:64]


def op_label(hlo: str) -> str:
    """``%closed_call.30 = bf16[16,512,8,128]{3,2,1,0:T(8,128)} custom-call(..``
    -> ``closed_call.30_custom-call_bf16_16_512_8_128``."""
    name, eq, rest = hlo.partition(" = ")
    kind = re.search(r" ([a-z][a-z0-9\-]*)\(", " " + rest) if eq else None
    if not kind:
        return _label(hlo)
    shape = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest[:kind.start() + 1])
    return _label(f"{name.lstrip('%')}_{kind.group(1)}_"
                  f"{shape.group(0) if shape else ''}")


def classify_modules(dev: dict, programs: dict) -> dict:
    """{module name: program label}. ``programs`` maps a label to
    {"module": regex over the module's name, "contains_op": regex over the
    HLO text of the operations that run inside it}; either may be absent."""
    ops = sorted(dev["ops"], key=lambda e: e[1])
    starts = [e[1] for e in ops]
    out = {}
    for name, start, dur in dev["modules"]:
        if name in out:
            continue
        inside = {o[0] for o in ops[bisect.bisect_left(starts, start):
                                    bisect.bisect_left(starts, start + dur)]}
        out[name] = "other"
        for label, rule in programs.items():
            if "module" in rule and not re.search(rule["module"], name):
                continue
            if "contains_op" in rule and not any(
                    re.search(rule["contains_op"], o) for o in inside):
                continue
            out[name] = label
            break
    return out


def _host_label(host: dict, mid_ns: int, own_files: set) -> str:
    """What the dispatching thread was doing at ``mid_ns``: the deepest
    Python frame of the program's own files (``$engine.py:405 step``), or
    the deepest event of any kind when no such frame covers the moment."""
    own = anything = None
    for line, evs in host.items():
        for name, start, dur in evs:
            if not start <= mid_ns < start + dur:
                continue
            if anything is None or start > anything[1]:
                anything = (name, start)
            m = re.match(r"\$([\w.\-]+\.py):\d+ ", name)
            if m and m.group(1) in own_files and (
                    own is None or start > own[1]):
                own = (name, start)
    hit = own or anything
    return hit[0].lstrip("$") if hit else "nothing_recorded"


def reduce(events: dict, programs: dict, own_files=frozenset()) -> dict:
    """``programs``: see classify_modules. ``own_files``: base names of the
    program's Python files, for labelling idle gaps."""
    devs = events["devices"]
    if not devs:
        return {}
    # the traced window is what the device planes cover. The host threads
    # are recorded for longer (the Python tracer starts before and stops
    # after the device tracer, by seconds), so they do not bound it.
    everything = [e for d in devs.values() for k in d for e in d[k]]
    t0 = min(e[1] for e in everything)
    t1 = max(e[1] + e[2] for e in everything)
    window_ns = t1 - t0
    busy, op_self, op_count = [], {}, {}
    prog = {}
    for plane in sorted(devs):
        d = devs[plane]
        src = d["ops"] or d["modules"]
        busy.append(union_ns((s, s + du) for _, s, du in src))
        for name, self_ns in self_times(d["ops"]):
            op_self[name] = op_self.get(name, 0) + self_ns
            op_count[name] = op_count.get(name, 0) + 1
    first = devs[sorted(devs)[0]]
    mods = sorted(first["modules"], key=lambda e: e[1])
    which = classify_modules(first, programs)
    for name, _, dur in mods:
        prog.setdefault(which[name], []).append(dur)
    gaps = {}
    mods = [m for m in mods if m[2] >= MIN_PROGRAM_NS]
    for a, b in zip(mods, mods[1:]):
        gap = b[1] - (a[1] + a[2])
        if gap < MIN_GAP_NS:
            continue
        label = _label(
            f"after_{which[a[0]]}_before_{which[b[0]]}__"
            + _host_label(events["host"], a[1] + a[2] + gap // 2, own_files))
        gaps[label] = gaps.get(label, 0) + gap
    n = len(busy)
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "n_devices": n,
        "programs": {k: {"count": len(v), "total_s": sum(v) / 1e9,
                         "durations_ms": [x / 1e6 for x in v]}
                     for k, v in prog.items()},
        # per-op self seconds, averaged over devices
        "ops": [[op_label(k), v / n / 1e9, op_count[k] // n, k[:400]]
                for k, v in top(op_self)],
        "idle_gaps": [[k, v / 1e9] for k, v in top(gaps)],
    }


def load_events(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def own_python_files(root: str) -> set:
    return {f for _, _, fs in os.walk(root) for f in fs if f.endswith(".py")}


def main(argv) -> int:
    """trace_reduce.py <xplane or events.json[.gz]> <out.json> <programs
    JSON> <program source dir>"""
    src, dst, programs, own = argv[1], argv[2], json.loads(argv[3]), argv[4]
    events = load_events(src) if ".json" in src else extract(src)
    with open(dst, "w") as f:
        json.dump(reduce(events, programs, own_python_files(own)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
