"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
compact form: planes, their lines, and events as [name, start_ns,
duration_ns].  ``reduce`` works on that form only, so it is checked on a
small recorded trace kept with the tests.

The measured window is the host span ``bench.window`` that the harness
opens around its traffic.  On a device plane (``/device:TPU:<n>``) the
line ``XLA Ops`` holds one event per operation run, named by its whole
HLO instruction, and the line ``XLA Modules`` one per program run
(``jit_program(<hash>)``).  ``load`` shortens an operation's name to the
instruction's name and result shape, with `` tpu_custom_call`` appended
for a Pallas kernel (a Mosaic custom call: ``conv2d_ws``,
``conv2d_ws_pipe``, ``matmul_ws`` by the wrapper that built it).  Busy time
is the union of the operation intervals inside the window.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"
HOST_MARK = "bench."                 # the harness's own host spans
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL = "tpu_custom_call"
HOST_MIN_NS = 20_000                 # shorter host events are not kept


def short_name(hlo: str) -> str:
    """'%conv2d_ws_pipe.14 = s8[32,112,112,128]{...} custom-call(...),
    custom_call_target="tpu_custom_call", ...' becomes
    'conv2d_ws_pipe.14 s8[32,112,112,128] tpu_custom_call'."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    shape = rest.split("{")[0].split(" ")[0]
    mark = f" {KERNEL}" if f'custom_call_target="{KERNEL}"' in rest else ""
    return f"{head.lstrip('%')} {shape}{mark}"


def load(trace_dir: str) -> dict:
    """The compact form of the newest ``.xplane.pb`` under ``trace_dir``:
    every event of the device planes, and host events of at least
    ``HOST_MIN_NS`` (all of the harness's own spans)."""
    import jax
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name) if device else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(HOST_MARK)
                      or e.duration_ns >= HOST_MIN_NS]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                        # averaged over the device planes
    kernel_s: float                      # Pallas kernels, all device planes
    runs: int                            # program runs wholly in the window
    run_kernel_s: float                  # Pallas kernel time of those runs
    devices: int
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _window(tr: dict) -> Tuple[int, int]:
    for plane in tr["planes"]:
        if plane["name"].startswith(DEVICE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    raise LookupError(f"no {WINDOW!r} span in the trace")


def _clip(events: Sequence[list], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((s, e))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def is_kernel(name: str) -> bool:
    return name.endswith(" " + KERNEL)


class _HostEvents:
    """The host events of a trace (all but the window), for ``label``."""

    def __init__(self, tr: dict):
        events = [ev for plane in tr["planes"]
                  if not plane["name"].startswith(DEVICE_PREFIX)
                  for line in plane["lines"] for ev in line["events"]
                  if ev[0] != WINDOW]
        self.names = [name for name, _, _ in events]
        self.start = np.array([s for _, s, _ in events], np.int64)
        self.end = self.start + np.array([d for _, _, d in events], np.int64)
        self.mine = np.array([n.startswith(HOST_MARK) for n in self.names],
                             bool)

    def label(self, lo: int, hi: int) -> str:
        """What the host was doing in [lo, hi): the harness span that
        overlaps it most, else the longest-overlapping host event, else
        'no host span'."""
        overlap = np.minimum(self.end, hi) - np.maximum(self.start, lo)
        for mine in (True, False):
            fits = np.where((self.mine == mine) & (overlap > 0), overlap, 0)
            if fits.size and fits.max() > 0:
                return self.names[int(np.argmax(fits))]
        return "no host span"


def reduce(tr: dict, top: int = 10) -> Reduction:
    lo, hi = _window(tr)
    devices = [p for p in tr["planes"] if p["name"].startswith(DEVICE_PREFIX)
               and _line(p, OPS_LINE)]
    if not devices:
        raise LookupError("no device plane with operations in the trace")
    busy = kernel = run_kernel = 0
    runs = 0
    op_time: Dict[str, int] = {}
    gaps: List[Tuple[int, int, int]] = []
    for n, plane in enumerate(devices):
        ops = _line(plane, OPS_LINE)
        merged = _union(_clip(ops, lo, hi))
        busy += sum(e - s for s, e in merged)
        for name, start, dur in ops:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                op_time[name] = op_time.get(name, 0) + (e - s)
                if is_kernel(name):
                    kernel += e - s
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i + 1] - edges[i], edges[i], n)
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        kernels = sorted((start, start + dur) for name, start, dur in ops
                         if is_kernel(name))
        kernel_starts = [s for s, _ in kernels]
        for _, start, dur in _line(plane, MODULES_LINE):
            end = start + dur
            if start < lo or end > hi:
                continue
            inside = []
            k = bisect.bisect_left(kernel_starts, start)
            while k < len(kernels) and kernels[k][0] < end:
                s, e = kernels[k]
                if e <= end:
                    inside.append(e - s)
                k += 1
            if inside:
                runs += 1
                run_kernel += sum(inside)
    gaps.sort(reverse=True)
    host = _HostEvents(tr)
    idle = [(f"{host.label(s, s + g)} (device {n}, "
             f"+{(s - lo) / 1e6:.3f} ms)", g / 1e9)
            for g, s, n in gaps[:top]]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(hi - lo) / 1e9, busy_s=busy / len(devices) / 1e9,
        kernel_s=kernel / 1e9, runs=runs, run_kernel_s=run_kernel / 1e9,
        devices=len(devices),
        device_ops=[(name, t / 1e9) for name, t in ops_top],
        idle_gaps=idle)
