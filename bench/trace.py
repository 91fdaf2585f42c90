"""From a profiler trace to device busy and idle time, time per device
operation and per program, and idle gaps named by what the host was doing.

`capture` traces a block and keeps a compact form of the trace: for each
device, its operations and its programs (`XLA Ops` and `XLA Modules`) as
`[name, start_ns, duration_ns]`, and the window's two ends on the trace's
clock and on the host's. `reduce` works on that compact form alone, so it
is tested on a small trace recorded on the chip (`bench/testdata/`).
"""
from __future__ import annotations

import contextlib
import glob
import re
import shutil
import time
from pathlib import Path

import numpy as np

WINDOW = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# ops that only run others: counted in busy time, not as operations
_CONTAINERS = ("while", "conditional", "call")


def op_label(text: str) -> str:
    """`%name opcode type` of an HLO instruction's text, layouts left
    out, with a custom call's target: `%fusion.14 fusion f32[539392]`."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, rest = "tuple", rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
        typ = re.sub(r"\{[^}]*\}", "", typ)
    label = f"{name} {rest.split('(', 1)[0]} {typ}"
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return label + (f" {target.group(1)}" if target else "")


@contextlib.contextmanager
def capture(trace_dir: Path, out: dict):
    """Trace the block. On exit `out` holds the compact trace, and the
    profiler's own files are deleted."""
    import jax
    trace_dir = Path(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            h0 = time.perf_counter()
            yield
            h1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")))
    out.update(compact(files[-1]))
    out["host_window"] = [h0, h1]
    shutil.rmtree(trace_dir, ignore_errors=True)


def compact(xplane_path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane_path))
    devices, hosts = [], []
    labels: dict[str, str] = {}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if not key:
                    continue
                for e in line.events:
                    label = labels.get(e.name)
                    if label is None:
                        label = labels[e.name] = op_label(e.name)
                    dev[key].append([label, int(e.start_ns),
                                     int(e.duration_ns)])
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            hosts.append(plane)
    window = _window(hosts, head=200) or _window(hosts)
    if window is None:
        raise RuntimeError("the trace holds no window annotation")
    return {"window_ns": window, "devices": devices}


def _window(hosts: list, head: int | None = None):
    """The window annotation's span on the trace clock. It opens as the
    trace starts, so it lies among the first events of its thread's line:
    with `head`, only that many events of each line are read."""
    for plane in hosts:
        for line in plane.lines:
            for i, e in enumerate(line.events):
                if head is not None and i >= head:
                    break
                if e.name == WINDOW:
                    return [int(e.start_ns), int(e.start_ns + e.duration_ns)]
    return None


def host_spans(tr: dict, events) -> list:
    """Host spans `(name, t0, t1)` on the host's clock, mapped onto the
    trace's clock by the window's two ends."""
    (w0, w1), (h0, h1) = tr["window_ns"], tr["host_window"]
    scale = (w1 - w0) / (h1 - h0)
    return [[n, round(w0 + (s - h0) * scale), round(w0 + (e - h0) * scale)]
            for n, s, e in events if e > h0 and s < h1]


def _union(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """Disjoint sorted intervals covering the union of `iv` within
    [lo, hi]."""
    if iv.size == 0:
        return np.zeros((0, 2), np.int64)
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if iv.size == 0:
        return np.zeros((0, 2), np.int64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[new, 0]
    ends = reach[np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]]
    return np.stack([starts, ends], axis=1)


def _gaps(busy: np.ndarray, lo: int, hi: int) -> np.ndarray:
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def _innermost(spans: list, points: np.ndarray) -> list[str]:
    """Name of the innermost host span covering each time point (spans of
    one thread nest or are disjoint); "host idle" where none does."""
    if not spans:
        return ["host idle"] * len(points)
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    starts = np.array([s[1] for s in spans])
    ends = np.array([s[2] for s in spans])
    parent = [-1] * len(spans)
    stack: list[int] = []
    for i, (_, s, e) in enumerate(spans):
        while stack and ends[stack[-1]] <= s:
            stack.pop()
        parent[i] = stack[-1] if stack else -1
        stack.append(i)
    out = []
    for i, p in zip(np.searchsorted(starts, points, side="right") - 1,
                    points):
        while i >= 0 and ends[i] <= p:
            i = parent[i]
        out.append(spans[i][0] if i >= 0 else "host idle")
    return out


def _top(d: dict, k: int = 10) -> list:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def reduce(tr: dict, host: list | None = None) -> dict:
    """Busy and idle seconds of the window, averaged over the devices; time
    per device operation and program; idle seconds by the innermost host
    span at each gap's middle (`host`: `[name, t0_ns, t1_ns]`)."""
    lo, hi = tr["window_ns"]
    n = len(tr["devices"])
    if n == 0:
        raise RuntimeError("the trace holds no device")
    busy_ns = 0
    ops: dict[str, float] = {}
    programs: dict[str, list] = {}
    idle: dict[str, float] = {}
    for dev in tr["devices"]:
        events = dev["ops"] or dev["modules"]
        iv = np.array([[s, s + d] for _, s, d in events], np.int64)
        busy = _union(iv.reshape(-1, 2), lo, hi)
        busy_ns += int((busy[:, 1] - busy[:, 0]).sum())
        for name, s, d in dev["ops"]:
            if lo <= s < hi and name.split(" ")[1:2] not in (
                    [c] for c in _CONTAINERS):
                ops[name] = ops.get(name, 0.0) + d / 1e9 / n
        for name, s, d in dev["modules"]:
            if lo <= s < hi:
                programs.setdefault(re.sub(r"\(\d+\)$", "", name),
                                    []).append(d / 1e9)
        gaps = _gaps(busy, lo, hi)
        names = _innermost(host or [], (gaps[:, 0] + gaps[:, 1]) // 2)
        for name, (a, b) in zip(names, gaps):
            idle[name] = idle.get(name, 0.0) + (b - a) / 1e9 / n
    window_s = (hi - lo) / 1e9
    return {"window_s": window_s, "busy_s": busy_ns / 1e9 / n,
            "ops": ops, "programs": programs, "idle_by_host": idle,
            "breakdown": {"device_ops": _top(ops), "idle_gaps": _top(idle)}}
