"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A device
plane is ``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per
executed HLO operation (a ``while`` holds its body's events nested inside
it) and its ``XLA Modules`` line one event per executed program. Host
planes hold the harness's ``bench/...`` annotations on the same clock.

On the TPU an op event's name is the whole HLO instruction
(``%fusion.66 = bf16[1600]{...} fusion(...), kind=kLoop, ...``); a Mosaic
kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``. Ops are told apart by the
instruction's own name and result type (``op_key``), never by operand text.

For each device: the busy time (union of op intervals), the time per
operation (SELF time: an op's duration less the ops nested inside it),
the program executions, the collective time that no compute op covered,
and the idle gaps, each attributed to what the host was doing.
"""

import collections
import glob
import os
import re

from benchmark import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all)")
HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])")
INSIDE_PROGRAM = "inside_program"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def instruction_name(name):
    """``%all-gather-start.3 = ...`` -> ``all-gather-start.3``; a name that
    is not an HLO instruction comes back whole."""
    m = HLO.match(name)
    return m.group("name") if m else name.lstrip("%").split(" = ", 1)[0]


def op_key(name):
    """A stable, short name for an op: the instruction's name without its
    instance number, and its (first) result type — ``fusion.66 =
    bf16[1600]{..} fusion(..)`` -> ``fusion bf16[1600]`` — so that per-op
    sums group one kind of op and survive renumbering."""
    m = HLO.match(name)
    base = re.sub(r"[.\d]+$", "", instruction_name(name)) or name
    return f"{base} {m.group('type').lstrip('(')}" if m else base


def is_collective(name):
    return bool(COLLECTIVE.match(instruction_name(name)))


class DeviceTrace:
    def __init__(self, name):
        self.name = name
        self.ops = []        # (start, end, name) of every op event
        self.modules = []    # (start, end, name) of every program execution
        self.async_ops = []  # (start, end, name): copies/collectives in flight


class Trace:
    """What ``load`` found: per-device ops and modules, host annotations
    (``bench/...`` events: name without the prefix, start, end), all in
    seconds on the trace's own clock."""

    def __init__(self):
        self.devices = []
        self.annotations = []

    @property
    def window(self):
        """The traced window: from the first ``bench/window`` annotation if
        the harness wrote one, else the extent of all device events."""
        for name, s, e in self.annotations:
            if name == "window":
                return s, e
        evs = [x for d in self.devices for x in d.ops + d.modules]
        if not evs:
            return 0.0, 0.0
        return min(x[0] for x in evs), max(x[1] for x in evs)


def load(source):
    """``source``: a path to an ``.xplane.pb`` or a ``ProfileData``."""
    import jax

    data = jax.profiler.ProfileData.from_file(source) \
        if isinstance(source, (str, os.PathLike)) else source
    trace = Trace()
    names = {}               # one str object per distinct (long) name

    def events(line):
        out = []
        for e in line.events:
            name = e.name
            out.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                        names.setdefault(name, name)))
        return out

    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = events(line)
                elif line.name == MODULES_LINE:
                    dev.modules = events(line)
                elif line.name == ASYNC_LINE:
                    dev.async_ops = events(line)
            trace.devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        trace.annotations.append(
                            (e.name[len("bench/"):], e.start_ns * 1e-9,
                             e.end_ns * 1e-9))
    trace.devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    trace.annotations.sort(key=lambda a: a[1])
    return trace


def nested(ops):
    """One sweep over the nested (start, end, name) events of a line ->
    [(start, end, name, self seconds, is_leaf)]: an event's self time is its
    duration less the events directly inside it; a leaf holds none."""
    out = []
    stack = []               # [start, end, name, child seconds, has child]
    close = lambda t: out.append((t[0], t[1], t[2], t[1] - t[0] - t[3], not t[4]))
    for s, e, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
            stack[-1][4] = True
        stack.append([s, e, name, 0.0, False])
    while stack:
        close(stack.pop())
    return out


def classify_host(annotations, labels, default):
    """-> f(t0, t1) that splits an interval by what the host was in.
    ``labels`` is an ordered list of (annotation name, gap label): the
    first annotation that covers a moment names it; none -> ``default``."""
    by_name = collections.defaultdict(list)
    for name, s, e in annotations:
        by_name[name].append((s, e))
    merged = [(label, stats.union(by_name.get(name, [])))
              for name, label in labels]

    def split(t0, t1):
        rest = [(t0, t1)]
        out = {}
        for label, ivs in merged:
            if not rest:
                break
            left = stats.subtract(rest, ivs)
            took = stats.total(rest) - stats.total(left)
            if took > 0:
                out[label] = out.get(label, 0.0) + took
            rest = left
        if rest:
            out[default] = out.get(default, 0.0) + stats.total(rest)
        return out

    return split


def reduce(trace, gap_labels=(), gap_default="host_other"):
    """The numbers the readers use, over the trace's window, averaged over
    the devices:

    ``window_s``, ``busy_s``, ``idle_frac``; ``op_seconds`` {op key: self
    seconds} and ``op_text_seconds`` {whole instruction text: self seconds}
    (what a metric's ``match`` pattern is tried on); ``collective_s`` and
    ``collective_exposed_s`` (collective leaf ops' time on the core's own
    line, and the part of it no other leaf op covered on that chip: what the
    core WAITED for) and ``collective_inflight_s`` (the union of the async
    collectives' start-to-done intervals);
    ``idle_gaps`` {label: seconds} — gaps inside a program execution are
    ``inside_program``, gaps between executions are named by the host
    annotation that covered them; ``modules`` {name: [durations]} of
    device 0 with ``module_events`` [(start, end, name)]."""
    w0, w1 = trace.window
    n = max(1, len(trace.devices))
    split = classify_host(trace.annotations, list(gap_labels), gap_default)
    busy = coll = exposed = inflight = 0.0
    op_seconds = collections.Counter()
    op_text_seconds = collections.Counter()
    gaps = collections.Counter()
    for dev in trace.devices:
        ops = [(max(s, w0), min(e, w1), nm) for s, e, nm in dev.ops
               if min(e, w1) > max(s, w0)]
        busy_iv = stats.union([(s, e) for s, e, _ in ops])
        busy += stats.total(busy_iv)
        swept = nested(ops)
        for _, _, name, sec, _ in swept:
            op_text_seconds[name] += sec
        leaves = [(s, e, nm) for s, e, nm, _, leaf in swept if leaf]
        collective = {nm: is_collective(nm) for nm in
                      {x[2] for x in leaves} | {x[2] for x in dev.async_ops}}
        c_iv = stats.union([(s, e) for s, e, nm in leaves if collective[nm]])
        o_iv = stats.union([(s, e) for s, e, nm in leaves
                            if not collective[nm]])
        coll += stats.total(c_iv)
        exposed += stats.total(stats.subtract(c_iv, o_iv))
        inflight += stats.total(stats.union(stats.clip(
            [(s, e) for s, e, nm in dev.async_ops if collective[nm]],
            w0, w1)))
        mod_iv = stats.union(stats.clip([(s, e) for s, e, _ in dev.modules],
                                        w0, w1))
        idle = stats.subtract([(w0, w1)], busy_iv)
        between = stats.subtract(idle, mod_iv)
        inside = stats.total(idle) - stats.total(between)
        if inside > 0:
            gaps[INSIDE_PROGRAM] += inside
        for s, e in between:
            for label, sec in split(s, e).items():
                gaps[label] += sec
    for name, sec in op_text_seconds.items():
        op_seconds[op_key(name)] += sec
    window = w1 - w0
    dev0 = trace.devices[0] if trace.devices else DeviceTrace("")
    events = [(s, e, nm) for s, e, nm in dev0.modules if s >= w0 and e <= w1]
    modules = collections.defaultdict(list)
    for s, e, nm in events:
        modules[nm].append(e - s)
    return {
        "window_s": window, "busy_s": busy / n,
        "idle_frac": 1.0 - (busy / n) / window
        if window > 0 and trace.devices else None,
        "op_seconds": {k: v / n for k, v in op_seconds.items()},
        "op_text_seconds": {k: v / n for k, v in op_text_seconds.items()},
        "collective_s": coll / n, "collective_exposed_s": exposed / n,
        "collective_inflight_s": inflight / n,
        "idle_gaps": {k: v / n for k, v in gaps.items()},
        "modules": dict(modules), "module_events": events,
        "annotations": [a for a in trace.annotations
                        if a[1] >= w0 and a[2] <= w1],
        "n_devices": len(trace.devices),
    }


def breakdown(summary, top=10):
    """The result line's ``breakdown``: the device operations that took
    most (self) time and the idle gaps by what the host was doing."""
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in order(summary["op_seconds"])],
            "idle_gaps": [[k, v] for k, v in order(summary["idle_gaps"])]}
