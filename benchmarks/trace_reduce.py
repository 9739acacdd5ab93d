"""The one reduction from a profiler ``.xplane.pb`` to device time.

    JAX_PLATFORMS=cpu python3 benchmarks/trace_reduce.py <xplane.pb> <out.json>

(``jax.profiler.ProfileData`` reads the file and needs no device; it runs in
a helper of its own because the benchmark's parent never imports jax.)

A TPU chip is a plane ``/device:TPU:<n>``.  Its line ``XLA Ops`` carries one
event per HLO op as it ran, ``XLA Modules`` one event per executed program;
the other lines (``Steps``, ``XLA TraceMe``, ...) repeat the same time under
other names.  So, to count nothing twice: busy time is the UNION of the
``XLA Ops`` intervals (nested ops of a loop overlap their parent), a
program's device time is its ``XLA Modules`` event, an op's time the sum of
its ``XLA Ops`` events with children's time taken out of their parents.
Times are seconds from the start of the capture.
"""

from __future__ import annotations

import json
import re
import sys

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def union(intervals: list[tuple[float, float]]):
    """Merged, sorted intervals and their total length."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged, sum(b - a for a, b in merged)


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Per-name self time of possibly nested events on one line."""
    out: dict[str, float] = {}
    stack: list[list] = []                  # [end, name, self]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            _, n, s = stack.pop()
            out[n] = out.get(n, 0.0) + s
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, name, b - a])
    for _, n, s in stack:
        out[n] = out.get(n, 0.0) + s
    return out


def short(name: str) -> str:
    """``%fusion.1 = f32[67129345]{0:T(1024)} fusion(...)`` -> ``fusion.1
    f32[67129345]``: the name the trace prints, without its operand list."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head.lstrip('%')} {shape}".strip()[:80]


def reduce_planes(planes) -> dict:
    """``planes``: [(name, [(line name, [(start_s, end_s, event name)])])]."""
    chips = []
    op_time: dict[str, float] = {}
    modules = []
    for name, lines in planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", name)
        if not m:
            continue
        by_line = dict(lines)
        ops = by_line.get(OPS_LINE, [])
        merged, busy = union([(a, b) for a, b, _ in ops])
        for n, s in self_times(ops).items():
            op_time[short(n)] = op_time.get(short(n), 0.0) + s
        chip = int(m.group(1))
        for a, b, n in by_line.get(MODULES_LINE, []):
            modules.append({"chip": chip, "name": n, "start_s": a,
                            "dur_s": b - a})
        gaps = [(merged[i][1], merged[i + 1][0])
                for i in range(len(merged) - 1)]
        chips.append({"chip": chip, "busy_s": busy, "n_ops": len(ops),
                      "first_s": merged[0][0] if merged else None,
                      "last_s": merged[-1][1] if merged else None,
                      "gaps": sorted(gaps, key=lambda g: g[0] - g[1])[:24]})
    return {"chips": sorted(chips, key=lambda c: c["chip"]),
            "ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:40],
            "modules": modules}


def load(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9, e.name)
                for e in line.events]))
        planes.append((plane.name, lines))
    return planes


def describe(path: str) -> list[str]:
    """Planes and lines of a trace, for looking at one by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            ev = list(line.events)
            head = [(e.name[:60], round(e.start_ns / 1e9, 6),
                     round(e.duration_ns / 1e9, 6)) for e in ev[:3]]
            out.append(f"  line {line.name!r}: {len(ev)} events {head}")
    return out


if __name__ == "__main__":
    if sys.argv[1] == "--describe":
        print("\n".join(describe(sys.argv[2])))
    else:
        with open(sys.argv[2], "w") as fh:
            json.dump(reduce_planes(load(sys.argv[1])), fh)
