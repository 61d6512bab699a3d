"""From a profiler trace to device busy time, program time and idle gaps.

A trace is reduced to plain lists first (:func:`reduce_xplane`), so that
the arithmetic below runs the same on a recorded trace in a test as on a
fresh one from the chip:

- ``modules``: one ``(program, start_ns, dur_ns)`` per execution of a
  compiled program on a device (the TPU plane's ``XLA Modules`` line), the
  program named without its fingerprint (``jit_go_segment``);
- ``ops``: ``(program/op, start_ns, dur_ns)`` per device operation (``XLA
  Ops``), the op named by its HLO instruction (``fusion.2``);
- ``host``: ``(name, start_ns, dur_ns)`` of host spans (the benchmark's own
  annotations and JAX's dispatch spans).

Busy time is the union of the program intervals inside the window; the
idle share is one minus busy over the window.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"
_FINGERPRINT = re.compile(r"\(\d+\)$")


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def reduce_xplane(log_dir: str) -> dict:
    """The lists above from the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"modules": [], "ops": [], "host": [], "devices": 0}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Modules" not in lines:
                continue
            out["devices"] += 1
            out["modules"] += [(program_name(e.name), e.start_ns,
                                e.duration_ns)
                               for e in lines["XLA Modules"].events]
            out["ops"] += [(op_name(e.name), e.start_ns, e.duration_ns)
                           for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in ln.events if e.duration_ns > 0]
    return out


def window_of(trace: dict) -> tuple[float, float]:
    """``(start_ns, end_ns)`` of the benchmark's window span."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return max(spans, key=lambda w: w[1] - w[0])


def _clip(events, w0: float, w1: float):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: dict, window) -> float:
    """Nanoseconds of the window in which some program ran on a device,
    averaged over the devices traced."""
    w0, w1 = window
    busy = sum(b - a for a, b in union(
        (a, b) for _, a, b in _clip(trace["modules"], w0, w1)))
    return busy / max(int(trace.get("devices", 1)), 1)


def idle_share_pct(trace: dict, window) -> float | None:
    """Percent of the window in which no program ran on a device."""
    w = window[1] - window[0]
    if w <= 0:
        return None
    return 100.0 * (1.0 - busy_ns(trace, window) / w)


def program_ns(trace: dict, window, names, exact: bool = False) -> float:
    """Device nanoseconds in the window of the programs called one of
    ``names`` (``exact``) or starting with one of them."""
    w0, w1 = window
    names = tuple(names)
    match = (lambda n: n in names) if exact else (
        lambda n: n.startswith(names))
    return sum(b - a for n, a, b in _clip(trace["modules"], w0, w1)
               if match(n))


def program_seconds(trace: dict, window) -> dict:
    w0, w1 = window
    out: dict[str, float] = {}
    for n, a, b in _clip(trace["modules"], w0, w1):
        out[n] = out.get(n, 0.0) + (b - a) / 1e9
    return out


def top_ops(trace: dict, window, n: int = 10) -> list:
    """The ``n`` device operations that took most time in the window, as
    ``[program/op, seconds]``: each op named with the program whose
    execution encloses it."""
    w0, w1 = window
    mods = sorted((s, s + d, name) for name, s, d in trace["modules"])
    starts = [m[0] for m in mods]
    total: dict[str, float] = {}
    for name, a, b in _clip(trace["ops"], w0, w1):
        i = bisect.bisect_right(starts, a) - 1
        prog = mods[i][2] if i >= 0 and mods[i][1] >= a else "?"
        key = f"{prog}/{name}"
        total[key] = total.get(key, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, window, n: int = 10) -> list:
    """The ``n`` longest stretches of the window with no program on a
    device, as ``[host activity, seconds]``: the activity is the
    shortest host span that covers the gap's middle (the window span
    itself when no other does)."""
    w0, w1 = window
    busy = union((a, b) for _, a, b in _clip(trace["modules"], w0, w1))
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [(s, s + d, name) for name, s, d in trace["host"]]
    out = []
    for a, b in gaps[:n]:
        mid = (a + b) / 2
        cover = [(e - s, name) for s, e, name in host if s <= mid <= e]
        label = min(cover)[1] if cover else "none"
        out.append([label, (b - a) / 1e9])
    return out
