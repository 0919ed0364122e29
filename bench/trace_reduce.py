"""From a JAX profiler trace to the benchmark's device numbers.

The harness wraps its measured window in a host annotation named
:data:`WINDOW`; everything here is clipped to that interval.

* busy: the union of the intervals in which an operation ran on a device
  (lines named ``XLA Ops`` of the ``/device:`` planes that have one),
  averaged over the devices; idle share is 1 − busy / window.  Ops nest
  (a ``while`` holds its body's ops): the union counts each instant once;
* prox time: the device time of operations whose name holds
  :data:`PROX` (the sorted-ℓ1 prox kernel), over the same devices;
* device_ops: the ten operations (instruction name and first shape) with
  the most device time, nested ones each counted;
* idle_gaps: the ten longest intervals in which no device ran, each named
  by the host event that covers most of it (the shortest such event).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

import numpy as np

WINDOW = "bench.window"
PROX = "prox_pool"


def load(path: str, device_prefix: str = "/device:",
         op_line: str | None = "XLA Ops", op_filter=None):
    """``(devices, host)`` from an ``.xplane.pb``: per device plane (one
    with a line named ``op_line``) a list of ``(start_ns, end_ns, name)``
    operations, and the host events (every line of the ``/host:``
    planes).  ``op_line=None`` takes every line of every plane named with
    ``device_prefix``; ``op_filter`` keeps only operations whose name it
    accepts."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            lines = [ln for ln in plane.lines
                     if op_line is None or ln.name == op_line]
            if not lines:
                continue  # not a device that runs ops (a custom plane)
            ops = devices.setdefault(plane.name, [])
            for line in lines:
                for e in line.events:
                    if op_filter is None or op_filter(e.name):
                        ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                    op_name(e.name)))
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.append((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name))
    return devices, host


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_name(hlo: str) -> str:
    """``%fusion.5 f32[200,4096]`` from an op's HLO text: the instruction
    name and the first shape it produces."""
    head, _, rest = hlo.partition(" = ")
    shape = _SHAPE.search(rest)
    return f"{head} {shape.group(0)}" if rest and shape else head


def _union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(ops, w0, w1):
    return [(max(s, w0), min(e, w1), n) for s, e, n in ops
            if e > w0 and s < w1]


def reduce(devices: dict, host: list) -> dict:
    wins = [(s, e) for s, e, n in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} host event in the trace")
    w0, w1 = max(wins, key=lambda se: se[1] - se[0])
    window_ns = w1 - w0
    busy, prox, gaps = [], [], []
    per_op = defaultdict(float)
    for ops in devices.values():
        ops = _clip(ops, w0, w1)
        merged = _union([(s, e) for s, e, _ in ops])
        busy.append(sum(e - s for s, e in merged))
        prox.append(sum(e - s for s, e, n in ops if PROX in n))
        for s, e, n in ops:
            per_op[n] += (e - s) / len(devices)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    ndev = max(1, len(devices))
    busy_ns = sum(busy) / ndev
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
        "prox_s": (sum(prox) / ndev) * 1e-9,
        "n_devices": len(devices),
        "device_ops": [[n, t * 1e-9] for n, t in top_ops],
        "idle_gaps": [[_name_gap(host, g0, g1), (g1 - g0) * 1e-9]
                      for g0, g1 in gaps],
    }


def _name_gap(host, g0, g1) -> str:
    """The host event that overlaps the gap most; of those, the shortest."""
    if not host:
        return "?"
    s = np.fromiter((h[0] for h in host), np.int64, len(host))
    e = np.fromiter((h[1] for h in host), np.int64, len(host))
    overlap = np.minimum(e, g1) - np.maximum(s, g0)
    names = [h[2] for h in host]
    keep = np.array([n != WINDOW for n in names]) & (overlap > 0)
    if not keep.any():
        return "(no host event)"
    best = overlap[keep].max()
    cand = np.flatnonzero(keep & (overlap == best))
    j = cand[np.argmin((e - s)[cand])]
    return names[j]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def reduce_dir(log_dir: str) -> dict:
    return reduce(*load(find_xplane(log_dir)))
