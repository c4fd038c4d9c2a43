"""From a profiler trace to the numbers the per-layer metrics read.

A traced run brackets its window with a host span ``bench.window`` and
wraps its calls into the program in ``bench.<what>`` spans. ``extract``
reads the ``.xplane.pb`` the profiler wrote into a small record:

  {"window_ns": [start, end],
   "device": [[op name, start_ns, dur_ns, is_pallas], ...],   # per op
   "host":   [[span name, start_ns, dur_ns], ...]}            # bench spans

and ``reduce`` turns a record into busy time, Pallas-kernel time, the
device operations that took most time and the idle gaps by what the host
was doing. The reduction is checked on a small recorded record kept with
the benchmark.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

WINDOW = "bench.window"
DEVICE_OPS_LINE = "XLA Ops"


def is_pallas(hlo: str) -> bool:
    """A Pallas kernel runs as a custom call to ``tpu_custom_call``; the
    device op's name in the trace is its HLO instruction text."""
    return "tpu_custom_call" in hlo


def op_name(hlo: str) -> str:
    """The instruction's name from its HLO text: ``%shard_spmm.2 = f32[..]
    custom-call(..)`` -> ``shard_spmm.2``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def extract(logdir: str) -> dict:
    """Read the newest ``.xplane.pb`` under ``logdir`` into a record."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    device, host, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for ev in line.events:
                    device.append([op_name(ev.name), int(ev.start_ns),
                                   int(ev.duration_ns),
                                   is_pallas(ev.name)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = [int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)]
                    elif ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    if window is None:
        raise ValueError(f"no {WINDOW} span in the trace")
    return {"window_ns": window, "device": device, "host": host}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(rec: dict, top: int = 10) -> dict:
    """busy_s (union of device-op intervals inside the window), window_s,
    pallas_s, ops (count of device ops in the window), device_ops (the
    ``top`` op names by total time) and idle_gaps (idle time inside the
    window summed by the bench span that covers most of each gap, longest
    first)."""
    w0, w1 = rec["window_ns"]
    ops = [(n, max(s, w0), min(s + d, w1), p) for n, s, d, p in rec["device"]
           if s + d > w0 and s < w1]
    busy = _merge([[s, e] for _, s, e, _ in ops if e > s])
    busy_ns = sum(e - s for s, e in busy)
    by_name = collections.Counter()
    pallas_ns = 0
    for n, s, e, p in ops:
        by_name[n] += e - s
        if p:
            pallas_ns += e - s
    gaps, cur = [], w0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < w1:
        gaps.append((cur, w1))
    spans = sorted((s, s + d, n) for n, s, d in rec["host"])
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0)
    idle = collections.Counter()
    for g0, g1 in gaps:
        cover = collections.Counter()
        for s, e, n in spans[bisect.bisect_left(starts, g0 - longest):
                             bisect.bisect_left(starts, g1)]:
            ov = min(e, g1) - max(s, g0)
            if ov > 0:
                cover[n] += ov
        # the gap goes to the one span that covers most of it
        name, ov = cover.most_common(1)[0] if cover else ("", 0)
        if ov:
            idle[name] += ov
        if g1 - g0 > ov:
            idle["no bench span"] += g1 - g0 - ov
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "pallas_s": pallas_ns / 1e9,
        "ops": len(ops),
        "device_ops": [[n, ns / 1e9] for n, ns in by_name.most_common(top)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in idle.most_common(top)],
    }
