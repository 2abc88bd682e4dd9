"""From a profiler trace to the numbers the per-layer readers share.

`jax.profiler` writes `<host>.xplane.pb` and `<host>.trace.json.gz`
under `<dir>/plugins/profile/<time>/`. This module reads the second
with `gzip` and `json`: it carries, for every device operation, what
the readers select by (`hlo_category`, `tf_op`: the JAX scope path;
also XLA's own `model_flops` and `bytes_accessed`, unused so far), where
`ProfileData` shows an operation's name, start and duration only.

What a trace of this system looks like (TPU v5 lite, PR 22's and PR
23's chip runs): one process `/device:TPU:<n>` per chip with the
threads `Steps` (one event per executed step program, back to back),
`XLA Modules` and `XLA Ops` (the operations, one after another on the
core: their durations add up to the step); one process `/host:CPU`
whose thread `python3` holds the benchmark's `TraceAnnotation`s
(`kfb.dispatch`, `kfb.fetch`) on the same clock, in microseconds.

Only whole steps count: the first and the last event of `Steps` are
dropped (the profiler starts and stops mid-step), and an operation
belongs to the window if it starts inside it.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "kfb."
# a trace viewer export drops events beyond this many
MAX_EVENTS = 1_000_000


@dataclass
class Op:
    name: str
    start: float        # microseconds on the trace's clock
    dur: float
    category: str       # hlo_category
    tf_op: str          # JAX scope path; "" where XLA gives none

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class DeviceTrace:
    name: str
    steps: list = field(default_factory=list)   # (start, dur), whole
    ops: list = field(default_factory=list)     # inside the window

    @property
    def window(self) -> tuple:
        first, last = self.steps[0], self.steps[-1]
        return first[0], last[0] + last[1]

    @property
    def window_us(self) -> float:
        t0, t1 = self.window
        return t1 - t0


@dataclass
class Trace:
    devices: list                                # DeviceTrace, by chip
    spans: dict = field(default_factory=dict)    # name -> [(start, dur)]


def find_trace(log_dir: str) -> str:
    """The newest trace the profiler wrote under `log_dir`."""
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not found:
        raise FileNotFoundError(f"no *.trace.json.gz under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def reduce(raw: dict) -> Trace:
    """Sort a trace's events into devices, whole steps, the operations
    inside them, and the benchmark's host spans."""
    events = raw["traceEvents"]
    if len(events) >= MAX_EVENTS:
        raise ValueError(
            f"{len(events)} events: the export is cut at {MAX_EVENTS}; "
            "trace fewer steps")
    planes, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e["name"] == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e["name"] == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = {pid: DeviceTrace(name) for pid, name in planes.items()
               if DEVICE_PLANE.match(name)}
    raw_ops = {pid: [] for pid in devices}
    spans: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        pid = e["pid"]
        if pid in devices:
            thread = threads.get((pid, e["tid"]))
            if thread == "Steps":
                devices[pid].steps.append((e["ts"], e["dur"]))
            elif thread == "XLA Ops":
                a = e.get("args", {})
                raw_ops[pid].append(Op(
                    e["name"], e["ts"], e["dur"],
                    a.get("hlo_category", ""), a.get("tf_op", "")))
        elif (planes.get(pid) == HOST_PLANE
              and e["name"].startswith(SPAN_PREFIX)):
            spans.setdefault(e["name"], []).append((e["ts"], e["dur"]))
    out = []
    for pid in sorted(devices, key=lambda p: int(
            DEVICE_PLANE.match(devices[p].name).group(1))):
        dev = devices[pid]
        dev.steps.sort()
        if len(dev.steps) < 3:
            raise ValueError(
                f"{dev.name}: {len(dev.steps)} steps traced; at least "
                "three are needed for one whole step")
        dev.steps = dev.steps[1:-1]
        t0, t1 = dev.window
        dev.ops = sorted((o for o in raw_ops[pid] if t0 <= o.start < t1),
                         key=lambda o: o.start)
        out.append(dev)
    if not out:
        raise ValueError("the trace holds no /device:TPU plane")
    for v in spans.values():
        v.sort()
    return Trace(out, spans)


def select(dev: DeviceTrace, tf_op: str | None = None,
           not_tf_op: str | None = None, name: str | None = None) -> list:
    """The device's operations whose fields match every pattern given
    (`re.search`; `not_tf_op` must not match)."""
    pats = [(re.compile(p), f) for p, f in (
        (tf_op, "tf_op"), (name, "name")) if p]
    neg = re.compile(not_tf_op) if not_tf_op else None
    return [o for o in dev.ops
            if all(p.search(getattr(o, f)) for p, f in pats)
            and not (neg and neg.search(o.tf_op))]


def ms_per_step(dev: DeviceTrace, ops) -> float:
    """Summed duration of `ops` per whole step, in milliseconds.
    Operations on `XLA Ops` run one after another, so a sum is a time."""
    return sum(o.dur for o in ops) / len(dev.steps) / 1000.0


def busy_intervals(dev: DeviceTrace) -> list:
    """Union of the operations' intervals, as merged (start, end)."""
    merged: list = []
    for o in dev.ops:
        if merged and o.start <= merged[-1][1]:
            if o.end > merged[-1][1]:
                merged[-1][1] = o.end
        else:
            merged.append([o.start, o.end])
    return merged


def busy_us(dev: DeviceTrace) -> float:
    t1 = dev.window[1]
    return sum(min(e, t1) - s for s, e in busy_intervals(dev))


def idle_share(dev: DeviceTrace) -> float:
    return 1.0 - busy_us(dev) / dev.window_us


def idle_gaps(dev: DeviceTrace) -> list:
    """(start, dur) of every stretch of the window in which no
    operation ran on the device."""
    t0, t1 = dev.window
    gaps, at = [], t0
    for s, e in busy_intervals(dev):
        if s > at:
            gaps.append((at, s - at))
        at = max(at, e)
    if t1 > at:
        gaps.append((at, t1 - at))
    return gaps


def covering_span(spans: dict, t: float) -> str:
    """Name of the benchmark's host span that covers time `t`."""
    for name, items in spans.items():
        for s, d in items:
            if s <= t < s + d:
                return name
    return "outside-spans"


def fold(text: str) -> str:
    """`Block_3/Dense_0` -> `Block_N/Dense_N`, `copy-done.12` ->
    `copy-done`: one name for every instance of a layer."""
    return re.sub(r"_\d+", "_N", re.sub(r"\.\d+$", "", text))


def group_name(op: Op) -> str:
    return f"{op.category}|{fold(op.tf_op.rstrip(':') or op.name)}"


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operation groups with most time (seconds per step)
    and the idle time by what the host was doing (seconds per step),
    both on the device that idles most."""
    dev = max(trace.devices, key=idle_share)
    n = len(dev.steps)
    groups: dict = {}
    for o in dev.ops:
        key = group_name(o)
        groups[key] = groups.get(key, 0.0) + o.dur
    gaps: dict = {}
    for s, d in idle_gaps(dev):
        key = covering_span(trace.spans, s)
        gaps[key] = gaps.get(key, 0.0) + d

    def ranked(d):
        return [[k, v / n / 1e6] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(groups), "idle_gaps": ranked(gaps)}


def device_seconds(trace: Trace) -> tuple:
    """(busy_s, window_s) averaged over the chips: what the result
    line's `device` carries in a traced run."""
    n = len(trace.devices)
    busy = sum(busy_us(d) for d in trace.devices) / n / 1e6
    window = sum(d.window_us for d in trace.devices) / n / 1e6
    return busy, window
