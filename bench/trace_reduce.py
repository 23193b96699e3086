"""From a profiler trace (``.xplane.pb``) to the device-side numbers.

Reads the trace with ``jax.profiler.ProfileData`` and nothing else.  Device
planes are ``/device:TPU:<n>``; each has an ``XLA Ops`` line (one event per
operation run) and an ``XLA Modules`` line (one event per program run).

The trace records no host events (its host tracer is off, see
:mod:`bench.harness`), so the host's side comes from the benchmark's own
records on ``time.monotonic``: the measured window and what the host was
doing in it.  They are put on the trace's clock, whose zero is the start of
the trace, by the train steps: a step's loss reaches the host after its
program ends on the device, and the closest such pair fixes the offset.

* busy time: the union of a device's op intervals in the window;
* op and module time: summed event durations by name;
* collective time: the ops whose name says they exchange between chips;
* idle gaps: the stretches between busy intervals, each named by the
  innermost host activity that covers its middle.

Every per-device number is the mean over the devices that ran anything.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_MODULE = re.compile(r"train_step")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
LAYOUT = re.compile(r"\{[^}]*\}")
OUTSIDE = "host: between the benchmark's calls"
TOP = 10

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def op_name(name: str) -> str:
    """An op event is named by its whole HLO instruction
    (``%fusion.8 = f32[32,28,28,64]{3,0,2,1:T(8,128)} fusion(...)``); keep
    the name and the result's type and shape (``fusion.8 f32[32,28,28,64]``),
    which tell apart ops of the same name in different programs."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return head.lstrip("%")
    return f"{head.lstrip('%')} {LAYOUT.sub('', rest.split(' ', 1)[0])}"


def _events(line) -> Iterable[Tuple[str, float, float]]:
    for e in line.events:
        yield op_name(e.name), float(e.start_ns), \
            float(e.start_ns + e.duration_ns)


def _clip(events, lo: float, hi: float):
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield name, a, b


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _device_lines(pd):
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                yield plane.name, lines


def offset_ns(pd, done_s: Sequence[float]) -> float:
    """Trace time of host time 0, where ``done_s`` are the host times (from
    the trace's start) at which each traced step's loss reached the host:
    the least lead of a loss over the end of its step's program on the
    first device.  0 where the steps and the programs do not pair up."""
    ends = []
    for _, lines in _device_lines(pd):
        if MODULES_LINE in lines:
            ends = sorted(b for name, _, b in _events(lines[MODULES_LINE])
                          if STEP_MODULE.search(name))
        break
    if not ends or len(ends) != len(done_s):
        return 0.0
    return -min(d * 1e9 - e for d, e in zip(sorted(done_s), ends))


def _label(t: float, host) -> str:
    best, best_len = OUTSIDE, None
    for name, a, b in host:
        if a <= t <= b and (best_len is None or b - a < best_len):
            best, best_len = name, b - a
    return best


def reduce(pd, lo_s: float, hi_s: float,
           host: Sequence[Tuple[str, float, float]] = (),
           done_s: Sequence[float] = ()) -> Dict:
    """The device numbers of the window ``[lo_s, hi_s)``.  Every host time
    is in seconds from the trace's start; ``host`` names what the host was
    doing when, ``done_s`` are the traced steps' loss times (see
    :func:`offset_ns`)."""
    off = offset_ns(pd, done_s)
    ns = lambda t: t * 1e9 + off  # noqa: E731
    lo, hi = ns(lo_s), ns(hi_s)
    host = [(name, ns(a), ns(b)) for name, a, b in host]
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    module_s: Dict[str, float] = defaultdict(float)
    module_n: Dict[str, int] = defaultdict(int)
    busy_s, coll_s, idle = [], [], []
    for _, lines in _device_lines(pd):
        ops = list(_clip(_events(lines[OPS_LINE]), lo, hi))
        if not ops:
            continue
        coll = 0.0
        for name, a, b in ops:
            op_s[name] += (b - a) * 1e-9
            op_n[name] += 1
            if COLLECTIVE.search(name):
                coll += (b - a) * 1e-9
        coll_s.append(coll)
        if MODULES_LINE in lines:
            for name, a, b in _clip(_events(lines[MODULES_LINE]), lo, hi):
                module_s[name] += (b - a) * 1e-9
                module_n[name] += 1
        busy = union((a, b) for _, a, b in ops)
        busy_s.append(sum(b - a for a, b in busy) * 1e-9)
        idle.extend(sorted(gaps(busy, lo, hi),
                           key=lambda g: g[0] - g[1])[:TOP])
    n = len(busy_s)
    if n == 0:
        raise ValueError("no device ran an operation in the window")
    idle.sort(key=lambda g: g[0] - g[1])
    per_dev = lambda d: {k: v / n for k, v in d.items()}  # noqa: E731
    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_s) / n,
        "collective_s": sum(coll_s) / n,
        "op_s": per_dev(op_s),
        "op_n": per_dev(op_n),
        "module_s": per_dev(module_s),
        "module_n": per_dev(module_n),
        "device_ops": [[k, v / n] for k, v in
                       sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label((a + b) / 2, host), (b - a) * 1e-9]
                      for a, b in idle[:TOP]],
    }


def matching(d: Dict[str, float], pattern: str) -> float:
    """Sum of ``d``'s values whose key matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in d.items() if rx.search(k))


def reduce_dir(trace_dir: str, *args, **kw) -> Dict:
    return reduce(load(find_xplane(trace_dir)), *args, **kw)
