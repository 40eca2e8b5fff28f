"""From a profiler trace to the numbers the per-layer metrics read.

A traced run records the JAX profiler over a sub-window of its measured
window, marked by the host span ``bench.window``. :func:`load` reduces the
``.xplane.pb`` to a small record, and the functions below reduce that:

    {"window": [start_ns, end_ns],
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns, kernel or null], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's operations (the trace's "XLA Ops" line, where an
operation's name is its HLO text): a Pallas kernel is a
``tpu_custom_call`` named after its spec, so a kernel is found by its name
and not by which program holds it. ``modules`` are the executions of compiled programs ("XLA
Modules"). ``host`` holds the benchmark's own spans (``bench.*``).
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Tracer:
    """Profiles ``seconds`` of a run's window, ending about a second before
    the window does (so it sees the steady state, not the start), marked by
    the host span ``bench.window``; does nothing when not enabled. The
    driver calls :meth:`tick` at each step boundary with the time since the
    window began; the trace starts and stops at step boundaries."""

    def __init__(self, enabled: bool, seconds: float, window: float,
                 directory: str):
        self.enabled, self.seconds, self.dir = enabled, seconds, directory
        self.start_at = max(0.0, window - seconds - 1.0)
        self.active = self.done = False
        self._win = None

    def span(self, name: str):
        if not self.active:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def tick(self, t: float, sync=None):
        """At a step boundary ``t`` seconds into the window: start or stop
        the trace when its time has come (``sync`` waits for the device
        before the trace's window closes)."""
        if not self.enabled or self.done:
            return
        if not self.active and t >= self.start_at:
            import jax

            jax.profiler.start_trace(self.dir)
            self._win = jax.profiler.TraceAnnotation(WINDOW)
            self._win.__enter__()
            self.active = True
            self._t0 = t
        elif self.active and t - self._t0 >= self.seconds:
            if sync is not None:
                sync()
            self.stop()

    def stop(self):
        if self.active:
            import jax

            self._win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active, self.done = False, True


# an operation's text in the trace: "%name.N = shape op(...), ..."; a Pallas
# kernel is a tpu_custom_call named after its spec
_OP = re.compile(r"^%?([A-Za-z0-9_.-]+?)(?:\.\d+)? = ")


def op_name(text: str) -> str:
    m = _OP.match(text)
    return m.group(1) if m else text


def kernel_of(text: str) -> str | None:
    if 'custom_call_target="tpu_custom_call"' in text:
        return op_name(text)
    return None


def load(trace_dir: str, device_count: int) -> dict:
    """The record of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"bench: {len(paths)} traces under {trace_dir}")
    data = ProfileData.from_file(paths[0])
    rec = {"window": None, "devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [[e.name, int(e.start_ns),
                                   int(e.duration_ns), kernel_of(e.name)]
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [[e.name, int(e.start_ns),
                                       int(e.duration_ns)]
                                      for e in line.events]
            rec["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        span = [e.name, int(e.start_ns), int(e.duration_ns)]
                        rec["host"].append(span)
                        if e.name == WINDOW:
                            rec["window"] = [span[1], span[1] + span[2]]
    rec["devices"].sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    rec["devices"] = rec["devices"][:device_count]
    if rec["window"] is None or not rec["devices"]:
        raise RuntimeError("bench: the trace holds no window span or no "
                           "device plane")
    return rec


# ----------------------------------------------------------- reductions
def _clip(start, dur, window):
    lo, hi = max(start, window[0]), min(start + dur, window[1])
    return (lo, hi) if hi > lo else None


def busy_intervals(dev: dict, window) -> list[tuple[int, int]]:
    """The union of the device's operation intervals inside the window,
    as sorted disjoint ``(start, end)`` pairs."""
    spans = sorted(filter(None, (_clip(s, d, window)
                                 for _, s, d, _ in dev["ops"])))
    out = []
    for lo, hi in spans:
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def window_s(rec) -> float:
    return (rec["window"][1] - rec["window"][0]) / 1e9


def busy_s(rec) -> float:
    """Seconds in which an operation ran, averaged over the chips used."""
    tot = sum(hi - lo for dev in rec["devices"]
              for lo, hi in busy_intervals(dev, rec["window"]))
    return tot / len(rec["devices"]) / 1e9


def idle_share(rec, device: int = 0) -> float:
    """1 - busy / window on one chip, in percent."""
    dev = rec["devices"][device]
    busy = sum(hi - lo for lo, hi in busy_intervals(dev, rec["window"]))
    return 100.0 * (1.0 - busy / (rec["window"][1] - rec["window"][0]))


def kernel_calls(rec, kernel: str, device: int = 0) -> list[int]:
    """Durations (ns) of the kernel's operations that start in the window."""
    w = rec["window"]
    return [d for _, s, d, k in rec["devices"][device]["ops"]
            if k == kernel and w[0] <= s < w[1]]


def programs_with(rec, kernel: str, device: int = 0) -> list[int]:
    """Durations (ns) of the program executions in the window that hold a
    call of ``kernel``: the step that contains it, whatever its jit name."""
    dev = rec["devices"][device]
    w = rec["window"]
    starts = sorted(s for _, s, _, k in dev["ops"] if k == kernel)
    out = []
    for _, s, d in dev["modules"]:
        if not (w[0] <= s and s + d <= w[1]):
            continue
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] < s + d:
            out.append(d)
    return out


def self_times(dev: dict, window) -> list[tuple[str, int]]:
    """Each operation's (name, self time in ns) inside the window: its
    duration less that of the operations nested in it (a loop's body runs
    inside the loop's own interval)."""
    ops = sorted(((s, d, kern or op_name(name)) for name, s, d, kern
                  in dev["ops"] if _clip(s, d, window)),
                 key=lambda o: (o[0], -o[1]))
    out, stack = [], []                   # stack: [end, index in out]
    for s, d, name in ops:
        while stack and s >= stack[-1][0]:
            stack.pop()
        c = _clip(s, d, window)
        if stack:
            out[stack[-1][1]][1] -= c[1] - c[0]
        out.append([name, c[1] - c[0]])
        stack.append((s + d, len(out) - 1))
    return out


def top_ops(rec, k: int = 10, device: int = 0) -> list:
    """The device operations that took most time in the window, by self
    time: kernels by name, other operations by their HLO name without its
    numeric suffix."""
    tot = {}
    for name, t in self_times(rec["devices"][device], rec["window"]):
        tot[name] = tot.get(name, 0) + t
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / 1e9] for n, t in best]


def idle_gaps(rec, k: int = 10, device: int = 0) -> list:
    """The longest idle gaps in the window, each named by the innermost
    host span of the benchmark that covers its middle ("untraced" where
    none does)."""
    busy = busy_intervals(rec["devices"][device], rec["window"])
    edges = [rec["window"][0]] + [x for iv in busy for x in iv] \
        + [rec["window"][1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    spans = [h for h in rec["host"] if h[0] != WINDOW]
    out = []
    for lo, hi in gaps:
        mid = (lo + hi) / 2
        cover = [h for h in spans if h[1] <= mid <= h[1] + h[2]]
        name = min(cover, key=lambda h: h[2])[0] if cover else "untraced"
        out.append([name, (hi - lo) / 1e9])
    return out


def breakdown(rec) -> dict:
    return {"device_ops": top_ops(rec), "idle_gaps": idle_gaps(rec)}
