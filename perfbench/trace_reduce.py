"""Profiler trace -> device busy and idle time, and the ``breakdown``.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  The traced window
is the benchmark's own ``perfbench.window`` annotation on the host plane.
Device busy time is the union of the intervals of the operations on each
TPU plane's ``XLA Ops`` line, clipped to the window and averaged over the
TPU planes.  Idle gaps are the holes in that union on the first TPU
plane, cut where a host annotation (``job.run``, ``job.read``, the
engine's ``dampr.*``...) starts or ends, each piece named by the innermost
annotation over it.

The device's timestamps lead the host's by about 1.7 ms in a v5e trace
(the recorded trace in ``tests/data``: each program starts that long
before the host's launch of it).  Against windows of seconds that is
noise, and the reduction does not correct it.
"""

import bisect
import glob
import heapq
import os

WINDOW = "perfbench.window"
#: The annotations that name what the host was doing: the benchmark's own
#: and the engine's (``dampr.<cat>.<name>``, one at each ``obs.trace.span``).
HOST_PREFIXES = ("job.", "perfbench.", "dampr.")


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError("expected one .xplane.pb under {}, found {}".format(
            trace_dir, len(paths)))
    return paths[0]


def union(intervals):
    """Merged, sorted, disjoint intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _device_planes(planes):
    return [p for p in planes if p.name.startswith("/device:TPU:")]


def _line(plane, name):
    for line in plane.lines:
        if line.name == name:
            return list(line.events)
    return []


def _op_names(plane):
    """Each ``XLA Ops`` event with a short name, ``<program>/<op>``: the
    program is the ``XLA Modules`` event the op starts in, without its
    fingerprint, and the op is the HLO instruction's name."""
    modules = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                     for ev in _line(plane, "XLA Modules"))
    starts = [m[0] for m in modules]
    for ev in _line(plane, "XLA Ops"):
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        module = modules[i][2] if i >= 0 and ev.start_ns < modules[i][1] \
            else "?"
        op = ev.name.split(" = ")[0].lstrip("%")
        yield ev, "{}/{}".format(module, op)


def _pieces(gaps, host):
    """``(start, end, name)`` for each piece of the sorted, disjoint
    ``gaps``, cut where a ``host`` span starts or ends, and named by the
    innermost span that covers it: the shortest, the first listed among
    equals.  One sweep over the spans' edges: a span enters a heap at its
    start, and leaves once a piece ends past its end."""
    edges = sorted({t for s in host for t in s[:2]})
    by_start = sorted(range(len(host)), key=lambda i: host[i][0])
    active = []
    k = 0
    for a, b in gaps:
        cuts = ([a] + edges[bisect.bisect_right(edges, a):
                            bisect.bisect_left(edges, b)] + [b])
        for x, y in zip(cuts, cuts[1:]):
            while k < len(by_start) and host[by_start[k]][0] <= x:
                i = by_start[k]
                heapq.heappush(active, (host[i][1] - host[i][0], i))
                k += 1
            while active and host[active[0][1]][1] < y:
                heapq.heappop(active)
            yield x, y, (host[active[0][1]][2] if active
                         else "perfbench.between")


def reduce_planes(planes, top=10):
    """The reduction over ``ProfileData.planes``; see the module doc."""
    planes = list(planes)
    spans = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    windows = [s for s in spans if s[2] == WINDOW]
    if len(windows) != 1:
        raise ValueError("expected one {} span, found {}".format(
            WINDOW, len(windows)))
    w0, w1, _ = windows[0]
    devices = _device_planes(planes)
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    busy_ns = []
    by_op = {}
    first = None
    for plane in devices:
        ivs = []
        for ev, key in _op_names(plane):
            a, b = max(ev.start_ns, w0), min(ev.end_ns, w1)
            if b <= a:
                continue
            ivs.append((a, b))
            by_op[key] = by_op.get(key, 0.0) + (b - a) / len(devices)
        merged = union(ivs)
        if first is None:
            first = merged
        busy_ns.append(sum(b - a for a, b in merged))
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    window_s = (w1 - w0) / 1e9
    gaps = []
    prev = w0
    for a, b in first + [[w1, w1]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    named = []
    for x, y, name in _pieces(gaps, [s for s in spans if s[2] != WINDOW]):
        if named and named[-1][2] == x and named[-1][0] == name:
            named[-1][1] += (y - x) / 1e9
            named[-1][2] = y
        else:
            named.append([name, (y - x) / 1e9, y])
    named = sorted(([n, secs] for n, secs, _end in named),
                   key=lambda g: -g[1])
    ops = sorted(([k, v / 1e9] for k, v in by_op.items()),
                 key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": window_s,
            "breakdown": {"device_ops": ops[:top],
                          "idle_gaps": named[:top]}}


def reduce_trace(trace_dir):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    return reduce_planes(data.planes)  # the planes live while ``data`` does
