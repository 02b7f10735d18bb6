"""Reduce a JAX profiler trace of the measured window to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` (JAX alone, no TensorFlow):

* device planes (``/device:TPU:<i>``): the op events of the ``XLA Ops``
  line (named by their HLO instruction; a ``while`` op and the ops of its
  body both appear, so op times overlap) and the program events of the
  ``XLA Modules`` line (one per jitted program run);
* the host plane (``/host:CPU``): the harness's ``bench.*`` spans and the
  host events of the Python thread.

Busy time is the union of op intervals inside the traced window, averaged
over the device planes; the window is the span from the first to the last
``bench.*`` span on the host.  Device time per program comes from the
module events.  Each idle gap of device 0 is named by the ``bench.*`` span
open over it and the innermost host event covering its midpoint."""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def base_name(name: str) -> str:
    """``jit_foo(12)`` -> ``jit_foo``, ``fusion.3`` -> ``fusion``."""
    prev = None
    while prev != name:
        prev, name = name, _SUFFIX.sub("", name)
    return name


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Sub-intervals of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Op:
    name: str         # HLO instruction name, e.g. ``pair_scores.1``
    module: str       # jitted program it ran in, e.g. ``jit_pair_scores``
    start: float      # seconds on the trace clock
    dur: float
    kernel: bool      # a Pallas kernel (``tpu_custom_call``)


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    ops: List[Op]                          # device 0, inside the window
    modules: List[Tuple[str, float]]       # device 0: (program, seconds)
    idle: List[Tuple[str, float]]          # (host activity, seconds)
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self) -> Dict[str, float]:
        out: Dict[str, float] = collections.defaultdict(float)
        for name, secs in self.modules:
            out[name] += secs
        return dict(out)

    def kernel_calls(self, module: str) -> List[Op]:
        """Pallas kernel calls of device 0 inside the jitted program
        ``module``."""
        return [op for op in self.ops if op.kernel and op.module == module]

    def breakdown(self) -> dict:
        mods = sorted(self.module_time().items(), key=lambda kv: -kv[1])
        agg: Dict[str, float] = collections.defaultdict(float)
        for name, s in self.idle:
            agg[name] += s
        idle = sorted(agg.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[n, s] for n, s in mods[:10]],
                "idle_gaps": [[n, s] for n, s in idle[:10]]}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9


def instruction(hlo: str) -> Tuple[str, bool]:
    """(instruction name, is a Pallas kernel) of an ``XLA Ops`` event,
    whose name is the HLO text ``%name = type op(...), ...``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return name, 'custom_call_target="tpu_custom_call"' in hlo


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> Reduction:
    return reduce_file(find_xplane(trace_dir))


def reduce_profile(pd) -> Reduction:
    devices: List[Tuple[list, list]] = []
    host_spans: List[Tuple[str, float, float]] = []
    host_events: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = list(_events(line))
                elif line.name == MODULES_LINE:
                    mods = list(_events(line))
            devices.append((ops, mods))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name.startswith("bench."):
                        host_spans.append((name, s, s + d))
                    elif line.name.startswith("python"):
                        host_events.append((name, s, s + d))
    if not devices:
        raise ValueError("trace has no TPU device plane")
    if not host_spans:
        raise ValueError("trace has no bench.* host span")
    lo = min(s for _, s, _ in host_spans)
    hi = max(e for _, _, e in host_spans)
    busy = []
    for ops, _ in devices:
        iv = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
              if s + d > lo and s < hi]
        busy.append(union_length(iv))
    ops0, mods0 = devices[0]
    mods0 = sorted(mods0, key=lambda m: m[1])
    starts = [m[1] for m in mods0]

    def module_of(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and mods0[i][1] <= t < mods0[i][1] + mods0[i][2]:
            return base_name(mods0[i][0])
        return ""

    ops = [Op(*_op_fields(name, module_of(s + d / 2), s, d))
           for name, s, d in ops0 if s + d > lo and s < hi]
    modules = [(base_name(n), min(s + d, hi) - max(s, lo))
               for n, s, d in mods0 if s + d > lo and s < hi]
    host_spans.sort(key=lambda x: x[1])
    host_events.sort(key=lambda x: x[1])
    span_starts = [s for _, s, _ in host_spans]
    event_starts = [s for _, s, _ in host_events]
    idle = []
    for g0, g1 in gaps([(o.start, o.start + o.dur) for o in ops], lo, hi):
        mid = (g0 + g1) / 2
        span = _innermost(host_spans, span_starts, mid)
        inner = _innermost(host_events, event_starts, mid)
        name = (span or "outside") + ("/" + base_name(inner) if inner
                                      else "")
        idle.append((name, g1 - g0))
    return Reduction(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                     ops=ops, modules=modules, idle=idle,
                     n_devices=len(devices))


def _op_fields(hlo: str, module: str, s: float, d: float):
    name, kernel = instruction(hlo)
    return name, module, s, d, kernel


def _innermost(spans: List[Tuple[str, float, float]], starts: List[float],
               t: float, walk: int = 4096) -> Optional[str]:
    """Name of the latest-starting span (``spans`` sorted by start, with
    ``starts`` their starts) that covers ``t``: on one thread's nested
    spans, the innermost one."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - walk, -1), -1):
        name, s, e = spans[j]
        if s <= t < e:
            return name
    return None
