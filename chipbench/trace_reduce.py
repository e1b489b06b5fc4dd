"""From a `jax.profiler` trace (`*.xplane.pb`) to the numbers the
per-layer readers and the result line use. Read with
`jax.profiler.ProfileData` alone.

A TPU plane (`/device:TPU:<n>`) has a line of module executions
("XLA Modules": one event for each run of a compiled program) and a
line of operations ("XLA Ops"). The steady window is taken between
step boundaries, so that the edges of the trace do not count as idle:
from the start of the first execution of the step program (the module
with the most device time) to the start of its last execution. Inside
it:

    steps      executions of the step program that start in the window
    busy_s     union of the intervals in which an operation ran
    window_s   length of the window
    ops        {operation as the trace names it (the HLO instruction's
               text): [seconds, events]}, innermost events only, so a
               loop and its body are not both counted
    device_ops [short name, seconds], longest first, instructions that
               differ only in their number taken together (`copy.3` and
               `copy.7` are `copy`; a plain `fusion.<n>` stays apart,
               with the shapes it produces)
    idle_gaps  seconds of the gaps between operations, by the benchmark's
               host span that covers most of each gap, longest first.
               The spans come from the benchmark (`run.Spans`, on the
               host's clock) with the host time at which the last traced
               step was seen to end; that instant is the end of the step
               program's last execution in the trace, which ties the
               two clocks

With several device planes the seconds are averaged over them.
"""

from __future__ import annotations

import collections
import glob
import os
import re

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def short_name(name: str) -> str:
    """`%fusion.3 = (f32[8]{0:T(8)}, ...) fusion(...)` ->
    `fusion.3 fusion (f32[8], ...)`: the instruction, its opcode and the
    shapes it produces, without layouts, at most 120 characters."""
    m = re.match(r"%?([\w.\-]+) = (.*?) ([\w\-]+)\(", name)
    if not m:
        return name[:120]
    shapes = re.sub(r"\{[^}]*\}|/\*[^*]*\*/", "", m[2])
    return f"{m[1]} {m[3]} {shapes}"[:120]


def family(name: str) -> str:
    """The name an instruction shares with its numbered siblings."""
    short = short_name(name)
    stem = re.sub(r"[.\d]+$", "", short.split(" ", 1)[0])
    return short if stem == "fusion" else stem


def _events(line):
    return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                  for e in line.events)


def _union(intervals):
    """Merged [start, end) intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _innermost(events):
    """Events that contain no later-starting event (sorted by start)."""
    out = []
    for i, (s, e, name) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < e \
                and events[i + 1][1] <= e:
            continue
        out.append((s, e, name))
    return out


def _covering(spans, s, e):
    best, name = 0, "no_benchmark_span"
    for a, b, n in spans:
        if a >= e:
            break
        over = min(b, e) - max(a, s)
        if over > best:
            best, name = over, n
    return name


def describe(path: str) -> list:
    """Planes, lines and event counts: what one reads by hand first."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            ev = list(line.events)
            names = collections.Counter(e.name for e in ev).most_common(5)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(ev), "top": names})
    return out


def reduce_file(path: str, host_spans=(), host_ns_at_end=None):
    """None where the trace holds no device plane (a CPU run).
    `host_spans`: (name, start, end) in host nanoseconds."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    per_device = []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if not plane.name.startswith("/device:") or OPS_LINE not in lines:
            continue
        ops = _events(lines[OPS_LINE])
        if not ops:
            continue
        w0, w1, steps = ops[0][0], ops[-1][1], 0
        module, spans = None, []
        if MODULES_LINE in lines:
            mods = _events(lines[MODULES_LINE])
            by_name = collections.Counter()
            for s, e, n in mods:
                by_name[n] += e - s
            if by_name:
                module = by_name.most_common(1)[0][0]
                starts = [s for s, _, n in mods if n == module]
                if len(starts) >= 2:
                    w0, w1, steps = starts[0], starts[-1], len(starts) - 1
                if host_ns_at_end is not None:
                    shift = max(e for _, e, n in mods
                                if n == module) - host_ns_at_end
                    spans = sorted((a + shift, b + shift, name)
                                   for name, a, b in host_spans)
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                  if e > w0 and s < w1]
        merged = _union((s, e) for s, e, _ in inside)
        busy = sum(e - s for s, e in merged)
        by_op, n_op = collections.Counter(), collections.Counter()
        for s, e, n in _innermost(inside):
            by_op[n] += e - s
            n_op[n] += 1
        gaps = collections.Counter()
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps[_covering(spans, a, b)] += b - a
        per_device.append({"window": w1 - w0, "busy": busy, "steps": steps,
                           "ops": by_op, "counts": n_op, "gaps": gaps,
                           "module": module})
    if not per_device:
        return None
    n = len(per_device)
    ops, counts, gaps = (collections.Counter(), collections.Counter(),
                         collections.Counter())
    for d in per_device:
        ops.update(d["ops"])
        counts.update(d["counts"])
        gaps.update(d["gaps"])
    families = collections.Counter()
    for k, v in ops.items():
        families[family(k)] += v
    return {
        "devices": n,
        "step_module": per_device[0]["module"],
        "steps": per_device[0]["steps"],
        "window_s": sum(d["window"] for d in per_device) / n * 1e-9,
        "busy_s": sum(d["busy"] for d in per_device) / n * 1e-9,
        "ops": {k: [v / n * 1e-9, counts[k] / n] for k, v in ops.items()},
        "device_ops": [[k, v / n * 1e-9] for k, v in families.most_common()],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in gaps.most_common()],
    }


def find_trace(directory: str):
    files = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def reduce_dir(directory: str, host_spans=(), host_ns_at_end=None):
    path = find_trace(directory)
    return reduce_file(path, host_spans, host_ns_at_end) if path else None
