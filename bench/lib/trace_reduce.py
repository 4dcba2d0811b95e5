"""From a profiler trace to device busy time, idle gaps and their causes.

All times here are nanoseconds on the profiler's clock.  The program's
host spans are put on that clock by the window marker: the harness opens a
profiler annotation named ``bench.window`` and notes the host clock at the
same moment.
"""

from __future__ import annotations

import glob
import os

WINDOW_MARK = "bench.window"
# the device line that holds one event per executed XLA operation
OPS_LINES = ("XLA Ops",)


def load_xplane(path: str) -> dict:
    """Device ops and the window marker of one ``.xplane.pb`` file.

    Returns ``{"devices": {plane: [(name, start, dur), ...]},
    "mark": (start, dur) or None}``.  A device is a ``/device:`` plane
    that has an ``XLA Ops`` line (the TPU cores; not the host, not the
    interconnect's trace plane)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, mark = {}, None
    for plane in pd.planes:
        lines = list(plane.lines)
        for line in lines:
            for e in line.events:
                if e.name == WINDOW_MARK and mark is None:
                    mark = (float(e.start_ns), float(e.duration_ns))
        ops = [ln for ln in lines if ln.name in OPS_LINES]
        if not plane.name.startswith("/device:") or not ops:
            continue
        devices[plane.name] = [(e.name, float(e.start_ns),
                                float(e.duration_ns))
                               for ln in ops for e in ln.events]
    return {"devices": devices, "mark": mark}


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of ``[lo, hi]`` between disjoint busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def timeline(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """What the host was doing over ``[lo, hi]``: consecutive segments, each
    labelled by the innermost open span (the one opened last), or ``no
    span``.  ``spans`` are ``(name, start, end)``."""
    cuts = sorted({lo, hi} | {t for _, s, e in spans for t in (s, e)
                              if lo < t < hi})
    ordered = sorted(spans, key=lambda x: x[1])
    out, j, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while j < len(ordered) and ordered[j][1] <= a:
            open_.append(ordered[j])
            j += 1
        open_ = [x for x in open_ if x[2] > a]
        name = max(open_, key=lambda x: x[1])[0] if open_ else "no span"
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def attribute(gap_list, segments) -> dict[str, float]:
    """Idle time per host label: the gaps cut along the timeline."""
    out: dict[str, float] = {}
    i = 0
    for g0, g1 in gap_list:
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        k = i
        while k < len(segments) and segments[k][0] < g1:
            a, b, name = segments[k]
            cover = min(b, g1) - max(a, g0)
            if cover > 0:
                out[name] = out.get(name, 0.0) + cover
            k += 1
    return out


def reduce(trace: dict, spans, lo: float, hi: float, top: int = 10) -> dict:
    """Busy and idle time of the window ``[lo, hi]``, averaged over the
    devices, with the ``top`` device ops by time and the idle time summed
    by what the host was doing (its innermost open span), ``top`` labels
    by time."""
    segments = timeline(spans, lo, hi)
    per_dev, ops, idle = [], {}, {}
    for events in trace["devices"].values():
        ivs = union(clip([(s, s + d) for _, s, d in events], lo, hi))
        per_dev.append(busy_ns(ivs))
        for name, s, d in events:
            if s >= lo and s + d <= hi:
                ops[name] = ops.get(name, 0.0) + d
        for k, v in attribute(gaps(ivs, lo, hi), segments).items():
            idle[k] = idle.get(k, 0.0) + v
    n = max(1, len(per_dev))
    return {
        "busy_s": sum(per_dev) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }
