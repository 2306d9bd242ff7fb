"""What the program itself puts in a traced run: the engine worker's spans,
each device operation's node, and its compile marks.

The program names its parts on the profiler's clock (``repro.obs``): with
obs enabled, the engine's worker thread is always inside one of
``WORKER``'s spans; ``int8_forward`` puts each operation under a
``jax.named_scope`` (``input``, the node's name, ``output``); and every jit
cache miss leaves a zero-length ``jax.compile`` mark.  ``trace.load``
keeps none of these, so this module reads the run's ``.xplane.pb`` again
into a compact form of its own:

* ``window``: the ``bench.window`` span, [start_ns, end_ns];
* ``devices``: per device plane, its ``XLA Ops`` as [name, start_ns,
  duration_ns, node], the name shortened as ``trace.short_name`` does and
  the node read from the op's ``op_name`` path (None where it has none);
* ``threads``: per host thread, its ``WORKER`` spans, compile marks and
  the harness's own ``bench.*`` spans as [name, start_ns, duration_ns,
  args].

Everything below works on that form, so it is checked on hand-made
traces.  A trace without spans or scopes reads as absent (None), never as
0; compile marks are counted only for a program that counts compiles, and
there 0 is a true reading.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from typing import Dict, List, Optional, Tuple

from perfbench.harness import manifest
from perfbench.harness import trace as trace_mod

WORKER = ("engine.wait", "engine.stage", "engine.put", "sched.run",
          "engine.ready", "engine.fetch", "engine.resolve")
HOST_WORK = WORKER[1:]               # the worker busy on the host path
COMPILE_MARK = "jax.compile"
INPUT = "input"
NO_SPAN = "no worker span"
_KEPT = frozenset(WORKER + (COMPILE_MARK, trace_mod.WINDOW))
_OP_NAME = re.compile(r'op_name="([^"]+)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def node_of(op_path: Optional[str]) -> Optional[str]:
    """The ``jax.named_scope`` an op sits in directly inside the program's
    ``jit``: 'jit(program)/conv1_2/jit(conv2d_ws)/dot_general' is
    'conv1_2'.  An op outside any such scope has none: the component after
    the ``jit(...)`` is then a nested transform or the op itself
    ('jit(program)/jit(conv2d_ws)/pad', 'jit(program)/concatenate')."""
    if not op_path:
        return None
    parts = op_path.split("/")
    for k, part in enumerate(parts[:-2]):
        if "(" in part:
            scope = parts[k + 1]
            return None if "(" in scope else scope
    return None


def _op_path(name: str, stats: Dict[str, object]) -> Optional[str]:
    """An op's ``op_name`` metadata: the ``tf_op`` stat where the profile
    carries it, else an ``op_name="..."`` in any text of the event.  A v5e
    profile read through ``ProfileData`` has neither (its op events carry
    device offsets and durations, and their names are HLO text without
    metadata), so there no op has a node."""
    tf_op = stats.get("tf_op")
    if isinstance(tf_op, str) and tf_op:
        return tf_op
    for text in (name, *stats.values()):
        if isinstance(text, str):
            m = _OP_NAME.search(text)
            if m:
                return m.group(1)
    return None


def _operands(hlo: str) -> List[str]:
    """The instructions an op's HLO text reads ('%copy.6 = ...
    copy(f32[...] %Arg_0.1)' reads 'Arg_0.1')."""
    _, sep, rest = hlo.partition(" = ")
    return _OPERAND.findall(rest) if sep else []


def _inherit(nodes: Dict[str, Optional[str]],
             operands: Dict[str, List[str]]) -> Dict[str, Optional[str]]:
    """Give an op without a scope (a relayout copy the compiler inserted)
    the node of an op that consumes it, else of one it reads."""
    consumers: Dict[str, List[str]] = {}
    for op, reads in operands.items():
        for src in reads:
            consumers.setdefault(src, []).append(op)
    out = dict(nodes)
    for _ in range(4):                   # chains of inserted copies
        changed = False
        for op, node in out.items():
            if node is not None:
                continue
            near = consumers.get(op, []) + operands.get(op, [])
            found = next((out[n] for n in near if out.get(n)), None)
            if found is not None:
                out[op] = found
                changed = True
        if not changed:
            break
    return out


def newest_trace(root: str) -> Optional[str]:
    """The newest ``.xplane.pb`` the harness wrote under ``root``."""
    paths = glob.glob(os.path.join(root, ".bench_trace", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> dict:
    """The compact form of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    window = None
    devices, threads = [], []
    for plane in data.planes:
        if plane.name.startswith(trace_mod.DEVICE_PREFIX):
            raw, hlo_of = [], {}
            for line in plane.lines:
                if line.name != trace_mod.OPS_LINE:
                    continue
                for e in line.events:
                    full = e.name
                    if full not in hlo_of:
                        hlo_of[full] = node_of(_op_path(full, dict(e.stats)))
                    raw.append((full, int(e.start_ns), int(e.duration_ns)))
            short = {full: trace_mod.short_name(full) for full in hlo_of}
            instr = {full: name.split(" ")[0] for full, name in short.items()}
            nodes = _inherit({instr[f]: n for f, n in hlo_of.items()},
                             {instr[f]: _operands(f) for f in hlo_of})
            devices.append({"name": plane.name, "ops": [
                [short[full], start, dur, nodes[instr[full]]]
                for full, start, dur in raw]})
        elif plane.name.startswith("/host:CPU"):
            for k, line in enumerate(plane.lines):
                kept = []
                for e in line.events:
                    if e.name not in _KEPT and \
                            not e.name.startswith(trace_mod.HOST_MARK):
                        continue
                    start, dur = int(e.start_ns), int(e.duration_ns)
                    if e.name == trace_mod.WINDOW:
                        window = [start, start + dur]
                        continue
                    kept.append([e.name, start, dur, dict(e.stats)])
                if kept:
                    threads.append({"name": f"{line.name}/{k}",
                                    "events": kept})
    return {"window": window, "devices": devices, "threads": threads}


# -- reductions of the compact form -------------------------------------------


def _clip(s: int, e: int, lo: int, hi: int) -> Tuple[int, int]:
    return max(s, lo), min(e, hi)


def _busy(ops: List[list], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The union of the ops' intervals inside [lo, hi), as ``trace`` takes
    it."""
    return trace_mod._union(trace_mod._clip([op[:3] for op in ops], lo, hi))


def node_seconds(tr: dict) -> Optional[Dict[Optional[str], float]]:
    """Device time per node in the window, summed over the devices; None
    unless some op carries a node."""
    if tr is None or tr["window"] is None:
        return None
    lo, hi = tr["window"]
    out: Dict[Optional[str], float] = {}
    for dev in tr["devices"]:
        for _, start, dur, node in dev["ops"]:
            s, e = _clip(start, start + dur, lo, hi)
            if e > s:
                out[node] = out.get(node, 0.0) + (e - s) / 1e9
    if not any(node is not None for node in out):
        return None
    return out


def input_share_percent(tr: dict) -> Optional[float]:
    """Device time of the ops under the ``input`` scope over the device's
    busy time (the union of its op intervals), in the window."""
    per_node = node_seconds(tr)
    if per_node is None:
        return None
    lo, hi = tr["window"]
    busy = sum(e - s for dev in tr["devices"]
               for s, e in _busy(dev["ops"], lo, hi)) / 1e9
    return 100.0 * per_node.get(INPUT, 0.0) / busy if busy > 0 else None


def _worker(tr: dict) -> Optional[List[list]]:
    """The worker thread's spans (the thread that stages batches)."""
    for th in tr["threads"]:
        if any(ev[0] == "engine.stage" for ev in th["events"]):
            return sorted((ev for ev in th["events"] if ev[0] in WORKER),
                          key=lambda ev: ev[1])
    return None


def _idle(dev: dict, lo: int, hi: int) -> List[Tuple[int, int]]:
    """The stretches of [lo, hi) in which the device runs no op."""
    edges = [lo] + [x for iv in _busy(dev["ops"], lo, hi) for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def idle_by_state(tr: dict) -> Optional[Dict[str, float]]:
    """Seconds of the window with no device op running, split by the
    worker span open at the time (``NO_SPAN`` where none is), averaged
    over the devices; None without worker spans.  The worker's spans do
    not overlap, so one sweep over gaps and spans, both in time order,
    splits every gap."""
    if tr is None or tr["window"] is None:
        return None
    spans = _worker(tr)
    if not spans or not tr["devices"]:
        return None
    lo, hi = tr["window"]
    out = {name: 0.0 for name in WORKER + (NO_SPAN,)}
    for dev in tr["devices"]:
        first = 0
        for s, e in _idle(dev, lo, hi):
            while first < len(spans) and sum(spans[first][1:3]) <= s:
                first += 1
            covered, k = 0, first
            while k < len(spans) and spans[k][1] < e:
                name, start, dur, _ = spans[k]
                a, b = _clip(start, start + dur, s, e)
                if b > a:
                    out[name] += (b - a) / 1e9
                    covered += b - a
                k += 1
            out[NO_SPAN] += (e - s - covered) / 1e9
    n = len(tr["devices"])
    return {name: t / n for name, t in out.items()}


def idle_host_percent(tr: dict) -> Optional[float]:
    """Share of the window with no device op running while the worker is
    on the host path (staging, copying, launching, waiting for, fetching
    or resolving a batch)."""
    split = idle_by_state(tr)
    if split is None:
        return None
    lo, hi = tr["window"]
    return 100.0 * sum(split[n] for n in HOST_WORK) / ((hi - lo) / 1e9)


def host_path_ms(tr: dict) -> Optional[List[float]]:
    """Per batch staged in the window: ``engine.stage`` start to the end
    of the ``sched.run`` that launched it, in ms."""
    if tr is None or tr["window"] is None:
        return None
    spans = _worker(tr)
    if not spans:
        return None
    lo, hi = tr["window"]
    out, stage = [], None
    for name, start, dur, _ in spans:
        if name == "engine.stage":
            stage = start if lo <= start < hi else None
        elif name == "sched.run" and stage is not None:
            out.append((start + dur - stage) / 1e6)
            stage = None
    return out or None


def host_path_p50_ms(tr: dict) -> Optional[float]:
    """Exact median of ``host_path_ms`` over the window's batches."""
    path = host_path_ms(tr)
    return statistics.median(path) if path else None


def compiles_in_window(tr: dict) -> Optional[int]:
    """How many ``jax.compile`` marks fall in the window."""
    if tr is None or tr["window"] is None:
        return None
    lo, hi = tr["window"]
    return sum(1 for th in tr["threads"] for ev in th["events"]
               if ev[0] == COMPILE_MARK and lo <= ev[1] <= hi)


def idle_gaps(tr: dict, top: int = 10) -> List[Tuple[str, float, float]]:
    """The ``top`` longest stretches of the window with no device op, as
    (label, start ms into the window, seconds): labelled by the worker
    span that overlaps each most, else by the harness's ``bench.*`` span,
    else 'no host span'."""
    if tr is None or tr["window"] is None or not tr["devices"]:
        return []
    lo, hi = tr["window"]
    events = sorted((ev for th in tr["threads"] for ev in th["events"]
                     if ev[0] != COMPILE_MARK), key=lambda ev: ev[1])
    gaps = [(e - s, s) for dev in tr["devices"] for s, e in _idle(dev, lo, hi)]
    out = []
    for g, s in sorted(gaps, key=lambda gs: (-gs[0], gs[1]))[:top]:
        best: Dict[bool, Tuple[int, str]] = {}
        for name, start, dur, _ in events:
            if start >= s + g:
                break
            a, b = _clip(start, start + dur, s, s + g)
            mine = name in WORKER
            if b > a and b - a > best.get(mine, (0, ""))[0]:
                best[mine] = (b - a, name)
        label = (best.get(True) or best.get(False) or (0, "no host span"))[1]
        out.append((label, (s - lo) / 1e6, g / 1e9))
    return out


def summary_lines(tr: dict, top: int = 10) -> List[str]:
    """The run log's view: device time per node, the idle time split by
    the worker's state, the host path per batch and the longest idle
    gaps."""
    lines = []
    per_node = node_seconds(tr)
    if per_node:
        busy = sum(per_node.values())
        ranked = sorted(per_node.items(), key=lambda kv: -kv[1])[:top]
        lines.append("device time by node: " + ", ".join(
            f"{node or 'no scope'} {100 * t / busy:.2f}% ({t:.3f} s)"
            for node, t in ranked) + f"; input share of busy time "
            f"{input_share_percent(tr):.3f}%")
    split = idle_by_state(tr)
    if split:
        lo, hi = tr["window"]
        window = (hi - lo) / 1e9
        lines.append("idle by worker state: " + ", ".join(
            f"{name} {100 * t / window:.3f}%" for name, t in split.items()
            if t > 0) + f"; host-path idle {idle_host_percent(tr):.3f}%")
    path = host_path_ms(tr)
    if path:
        lines.append(f"host path (stage to launch) p50 "
                     f"{host_path_p50_ms(tr):.3f} ms over {len(path)} "
                     f"batches, max {max(path):.3f} ms")
    if split:
        lines.append("longest idle gaps: " + ", ".join(
            f"{label} at +{at:.1f} ms {1e3 * g:.3f} ms"
            for label, at, g in idle_gaps(tr, top)))
    return lines


# -- per-layer metric readers ------------------------------------------------


def run_trace(run) -> Optional[dict]:
    """The compact form of the traced run just made, read once per run:
    the readers of one run share it through ``run``, and its summary goes
    to the run log."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_trace"):
        path = newest_trace(manifest.ROOT)
        run.program_trace = None if path is None else load(path)
        for line in summary_lines(run.program_trace):
            print(f"[perfbench] {line}", flush=True)
    return run.program_trace


def compiles(run) -> Optional[int]:
    """Jit cache misses in the window: the program's ``jax.compiles``
    counter, as the ``jax.compile`` marks it leaves in the trace.  None
    for a program that does not count them."""
    from repro import obs
    name = getattr(obs, "COMPILES", None)
    if name is None or name not in obs.metrics.names():
        return None
    return compiles_in_window(run_trace(run))
