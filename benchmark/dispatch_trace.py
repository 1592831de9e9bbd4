"""The program's ``dispatch`` records joined to the device's executions.

The serving front-end writes one ``dispatch`` span for every call that hands
the device a program (``program`` ``prefill`` | ``decode_chunk``, ``index``,
``behind``, ``seq`` = its place in the process's dispatch order) and its
``tick_wait`` names the ``seq`` it blocked on. Three parts read them, each
without a guess:

- ONE CLOCK (``clock``): every harness span exists twice, in ``ctx.rec
  .spans`` on ``time.monotonic()`` and in ``ctx.trace["annotations"]`` on the
  trace's clock, and ``Recorder.span`` enters the annotation BEFORE it reads
  ``t0`` and leaves it AFTER it reads ``t1``: each pair bounds the offset
  from both sides (``a_start - t0 <= offset <= a_end - t1``) and the
  tightest of hundreds of such bounds is microseconds wide.
- ONE EXECUTION A DISPATCH (``join``): the dispatches in ``seq`` order beside
  ``ctx.trace["module_events"]`` in start order, kind against kind, anchored
  by the clock (an execution cannot start before its dispatch began). A
  dispatch at the window's edge whose execution lies outside is dropped, a
  stretch of executions the profiler wrote as ONE event is stepped over and
  said; any other disagreement reads as no join.
- EVERY GAP HAS AN OWNER (``owners``): the device's idle intervals between
  executions, each split by seconds of overlap among the spans of the serve
  loop's thread and its tick workers, the deepest first.

The metric readers at the bottom return None, and the harness leaves the
metric out, where there is no device plane, where the program writes no
``dispatch`` records (the commit before them), where the span ring wrapped
past the traced window and, for those that need them, where clock or join
read as None; ``ctx.notes`` then says which.
"""

import bisect
import collections
import re
import types

from benchmark import manifest as mf
from benchmark import program_spans as ps
from benchmark import stats, trace_reduce

CLOCK_MAX_BRACKET_S = 100e-6
# a harness span and its annotation start this close on the rough mapping
# (the ``window`` pair), and the annotation is this much longer at most
PAIR_START_S, PAIR_EXCESS_S = 1e-3, 1e-3
# the device plane's stamps sit on the trace's clock to a shift of the
# profiling session's: up to ~2 ms seen (my chip runs, PR 51); more than this
# is no join
DEVICE_SKEW_S = 4e-3
# the spans that say what the serve loop's thread (and its tick workers) is
# in; ``admission_wait`` is a request's state while OTHERS are served
ROOTS = ("request", "status_write", "queue_empty")
NOT_WORK = ("admission_wait",)
UNSPANNED = "unspanned"
Pair = collections.namedtuple("Pair", "dispatch start end")


def _program_match(metric):
    """The pattern a standing metric file matches module names with."""
    return re.compile(mf.load_json(
        mf.BENCH_DIR / "layer_metrics" / f"{metric}.json")["params"]["match"])


def _note(ctx, why):
    ctx.notes["dispatch_trace"] = why


# ------------------------------------------------------------------ one clock
def clock(ctx):
    """Seconds to ADD to a ``time.monotonic()`` stamp of this process to get
    the trace's, or None: the middle of the bracket ``[max(a_start - t0),
    min(a_end - t1)]`` over the harness spans paired with their annotations
    by name and order. ``clock_bracket_s`` (its width) and ``clock_pairs``
    go to the notes; an empty bracket or one wider than 100 us is no clock."""
    if hasattr(ctx, "_dispatch_clock"):
        return ctx._dispatch_clock
    ctx._dispatch_clock = None
    host, traced = collections.defaultdict(list), collections.defaultdict(list)
    for name, t0, t1, _ in ctx.rec.spans:
        host[name].append((t0, t1))
    for name, s, e in ctx.trace["annotations"]:
        traced[name].append((s, e))
    if not host["window"] or not traced["window"]:
        _note(ctx, "no clock: the window has no annotation")
        return None
    rough = min(traced["window"])[0] - min(host["window"])[0]
    lo, hi, n = float("-inf"), float("inf"), 0
    for name, anns in traced.items():
        spans, j = sorted(host[name]), 0
        for s, e in sorted(anns):
            while j < len(spans) and spans[j][0] + rough < s - PAIR_START_S:
                j += 1
            if j == len(spans) or spans[j][0] + rough > s + PAIR_START_S:
                continue
            t0, t1 = spans[j]
            j += 1
            if 0 <= (e - s) - (t1 - t0) <= PAIR_EXCESS_S:
                lo, hi, n = max(lo, s - t0), min(hi, e - t1), n + 1
    ctx.notes["clock_pairs"] = n
    ctx.notes["clock_bracket_s"] = hi - lo if n else None
    if not n or not 0 <= hi - lo <= CLOCK_MAX_BRACKET_S:
        _note(ctx, "no clock: the bracket of the harness spans' offsets is "
                   "empty or wider than 100 us")
        return None
    ctx._dispatch_clock = (lo + hi) / 2
    return ctx._dispatch_clock


# --------------------------------------------------- the program's own spans
def ring(ctx):
    """Every closed span the program's ring holds (those that straddle the
    traced window too), oldest first, or None: no device plane, no recorder,
    a ring that wrapped past the window (``program_spans.window_spans``
    decides and notes it), or a program that writes no ``dispatch``."""
    if hasattr(ctx, "_dispatch_ring"):
        return ctx._dispatch_ring
    ctx._dispatch_ring = None
    inside = ps.window_spans(ctx)
    if inside is None:
        return None
    spans = [s for s in ps._live_tracer().snapshot() if s.t1 is not None]
    if any(s.name == "dispatch" and s.cat == "serving" for s in spans):
        ctx._dispatch_ring = spans
    return ctx._dispatch_ring


# ------------------------------------------------- one execution a dispatch
def _kind_of(name, kinds):
    for kind, rx in kinds:
        if rx.search(name):
            return kind
    return None


def join(ctx):
    """[Pair(dispatch span, execution start, end on the trace's clock)] in
    execution order, or None with a note that names the first mismatch.

    The device plane's stamps lie on the trace's clock only to a
    millisecond or two, by a shift of the profiling session's: the join
    bounds it from both sides (no execution starts before its dispatch
    began or ends after the host saw it ready) and takes the run of
    dispatches that needs the least of it; ``device_line_early_s`` in the
    notes is that bracket. ``owners`` moves the device's line by its middle,
    so what splits a gap among spans is good to half its width; a time on
    the device's own line is not touched by it.

    ONE fault of a profile is stepped over, and said (``executions_merged``
    in the notes): an execution event that starts as its dispatch's would
    and ends long after the ``tick_wait`` that names it is a stretch of
    executions the profiler wrote as one event (seen in a 15 s window: one
    event of 3.84 s over 46 chunks). It is left out with the dispatches under
    it, and the executions after it are given to their dispatches anew, by
    the same rule. Any other disagreement in count or kind is no join."""
    if hasattr(ctx, "_dispatch_join"):
        return ctx._dispatch_join
    ctx._dispatch_join = None
    spans = ring(ctx)
    offset = clock(ctx) if spans is not None else None
    if offset is None:
        return None
    kinds = (("decode_chunk", _program_match("tpot.decode_tick_device_p50_s")),
             ("prefill", _program_match("ttft.prefill_device_p50_s")))
    execs = sorted(x for x in ((s, e, _kind_of(nm, kinds))
                               for s, e, nm in ctx.trace["module_events"])
                   if x[2])
    sent = sorted((s for s in spans if s.name == "dispatch"
                   and s.cat == "serving"), key=lambda s: s.args["seq"])
    waits = {s.args["seq"]: s for s in spans
             if s.name == "tick_wait" and "seq" in s.args}
    w0, w1 = next((s, e) for nm, s, e in ctx.trace["annotations"]
                  if nm == "window")
    if not execs:
        _note(ctx, "no join: no execution of a serving program in the window")
        return None
    began = [d.t0 + offset for d in sent]
    pairs, merged, lo, hi, i, used = [], [], float("-inf"), float("inf"), 0, 0
    while i < len(execs):
        # from the latest dispatch that can have begun before this execution
        # did, back over what one tick sends together
        first = bisect.bisect_right(began, execs[i][0] + DEVICE_SKEW_S) - 1
        runs = [_run(sent, j, execs, i, offset, waits)
                for j in range(first, max(first - 4, used - 1), -1) if j >= 0]
        if not runs:
            _note(ctx, f"no join: no dispatch can have sent execution {i}")
            return None
        n, need, j, bracket, why = max(runs, key=lambda r: (r[0], -r[1]))
        if not i and j and n and began[j - 1] - bracket[1] >= w0:
            _note(ctx, f"no join: dispatch seq {sent[j - 1].args['seq']} "
                       "began inside the window and has no execution")
            return None
        for d in sent[used:j] if i else ():
            # what a merged event hides was seen ready before it ended
            wait = waits.get(d.args["seq"])
            if not merged or wait is None or wait.t1 + offset \
                    > merged[-1][1] + 2 * DEVICE_SKEW_S:
                _note(ctx, f"no join: dispatch seq {d.args['seq']} has no "
                           "execution")
                return None
        pairs += [Pair(sent[j + k], *execs[i + k][:2]) for k in range(n)]
        lo, hi = max(lo, bracket[0]), min(hi, bracket[1])
        i, used = i + n, j + n
        if i < len(execs):
            if not _is_merged(sent, used, execs[i], offset, waits):
                _note(ctx, "no join: " + why)
                return None
            merged.append(execs[i][:2])
            i, used = i + 1, used + 1
    after = sent[used] if used < len(sent) else None
    wait = waits.get(after.args["seq"]) if after is not None else None
    if wait is not None and wait.t1 + offset - lo <= w1:
        _note(ctx, f"no join: dispatch seq {after.args['seq']} was waited for "
                   "inside the window and has no execution")
        return None
    if lo > hi:
        _note(ctx, "no join: no one shift of the device line serves every "
                   "run of executions")
        return None
    ctx._dispatch_join = pairs
    ctx._dispatch_device_early = (lo + hi) / 2 if hi < float("inf") \
        else max(lo, 0.0)
    ctx.notes["dispatches_joined"] = len(pairs)
    ctx.notes["device_line_early_s"] = [lo, hi]
    if merged:
        ctx.notes["executions_merged"] = [e - s for s, e in merged]
    return pairs


def _run(sent, j, execs, i, offset, waits):
    """How far ``execs[i:]`` can be the executions of ``sent[j:]`` -> (the
    number that fit, how far the device line must be shifted at least, j,
    the bracket of that shift, why the next one does not fit): kind against
    kind, and a shift of the device line under ``DEVICE_SKEW_S`` with which
    no execution starts before its dispatch began or ends after its
    ``tick_wait`` did."""
    lo, hi, n, need = float("-inf"), float("inf"), 0, 0.0
    why = "the window's executions are given out"
    while i + n < len(execs):
        if j + n >= len(sent):
            why = (f"execution {i + n} and those after it have no dispatch "
                   "left that can have sent them")
            break
        (s, e, kind), d = execs[i + n], sent[j + n]
        if d.args["program"] != kind:
            why = (f"execution {i + n} is a {kind}, dispatch seq "
                   f"{d.args['seq']} a {d.args['program']}")
            break
        wait = waits.get(d.args["seq"])
        lo_, hi_ = max(lo, d.t0 + offset - s), hi if wait is None \
            else min(hi, wait.t1 + offset - e)
        need_ = max(lo_, 0.0) if hi_ >= 0 else -hi_
        if lo_ > hi_ or need_ > DEVICE_SKEW_S:
            why = (f"no shift of the device line under "
                   f"{DEVICE_SKEW_S * 1e3:g} ms puts execution {i + n} "
                   f"between the start of dispatch seq {d.args['seq']} and "
                   "its tick_wait's end")
            break
        lo, hi, need, n = lo_, hi_, need_, n + 1
    return n, need, j, (lo, hi), why


def _is_merged(sent, j, exec_, offset, waits):
    """An event of the right kind that starts no earlier than ``sent[j]``
    began, ends after the ``tick_wait`` that names it AND holds the end of
    the wait for the dispatch after it: a stretch of executions the profiler
    wrote as one (an execution never outlasts the wait for the next)."""
    if j + 1 >= len(sent):
        return False
    (s, e, kind), d = exec_, sent[j]
    wait, then = (waits.get(x.args["seq"]) for x in sent[j:j + 2])
    return d.args["program"] == kind and None not in (wait, then) \
        and d.t0 + offset - s <= DEVICE_SKEW_S \
        and e - (wait.t1 + offset) > DEVICE_SKEW_S \
        and e - (then.t1 + offset) > DEVICE_SKEW_S


# --------------------------------------------------- every gap has an owner
def _depths(spans):
    """{span id: (depth, root name)} by the parent chain (a parent the ring
    lost ends the chain)."""
    by_id = {s.id: s for s in spans}
    out = {}

    def walk(s):
        if s.id not in out:
            parent = by_id.get(s.parent)
            if parent is None:
                out[s.id] = (0, s.name)
            else:
                depth, root = walk(parent)
                out[s.id] = (depth + 1, root)
        return out[s.id]

    for s in spans:
        walk(s)
    return out


def idle_between(ctx):
    """The device's idle intervals BETWEEN executions, from the first
    execution's start to the last one's end (``module_events``: device 0,
    every program); what is idle inside an execution stays there."""
    busy = stats.union([(s, e) for s, e, _ in ctx.trace["module_events"]])
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:])]


def owners(ctx):
    """[(idle start, end, {span name: seconds})] over ``idle_between``: each
    interval split by seconds of overlap among the serving spans of the
    serve loop's thread and its tick workers (``request`` and all under it,
    ``status_write``, ``queue_empty``) mapped through the clock, the deepest
    in the parent chain first (the shorter of two as deep); what none covers
    is ``unspanned``. The intervals are the device line's own; the spans
    meet them through the clock and the join's shift of that line. None
    where there is no join."""
    if hasattr(ctx, "_dispatch_owners"):
        return ctx._dispatch_owners
    ctx._dispatch_owners = None
    if join(ctx) is None:
        return None
    spans = ring(ctx)
    offset = clock(ctx) - ctx._dispatch_device_early    # host -> device line
    depths = _depths(spans)
    named = sorted((s.t0 + offset, s.t1 + offset, -depths[s.id][0],
                    s.t1 - s.t0, s.name) for s in spans
                   if s.cat == "serving" and depths[s.id][1] in ROOTS
                   and s.name not in NOT_WORK)
    out, k, open_ = [], 0, []
    for a, b in idle_between(ctx):
        while k < len(named) and named[k][0] < b:
            open_.append(named[k])
            k += 1
        open_ = [c for c in open_ if c[1] > a]
        rest, split = [(a, b)], {}
        for s, e, _, _, name in sorted(open_, key=lambda c: c[2:4]):
            left = stats.subtract(rest, [(s, e)])
            took = stats.total(rest) - stats.total(left)
            if took > 0:
                split[name] = split.get(name, 0.0) + took
            rest = left
        if rest:
            split[UNSPANNED] = stats.total(rest)
        out.append((a, b, split))
    ctx._dispatch_owners = out
    return out


def _idle_in(gaps, starts, lo, hi):
    """-> (idle seconds, {span: seconds}) of ``owners``' intervals (their
    starts beside them) that lie inside [lo, hi]."""
    seconds, by_span = 0.0, collections.Counter()
    for a, b, split in gaps[bisect.bisect_left(starts, lo):]:
        if b > hi:
            break
        seconds += b - a
        by_span.update(split)
    return seconds, by_span


def analysis(ctx):
    """What the device-side readers share, or None: the chunk gaps (idle
    between consecutive executions of ONE request), the request edges (idle
    from a request's last execution to the NEXT served request's prefill)
    and the identity that holds them to the trace's own idle total."""
    if hasattr(ctx, "_dispatch_analysis"):
        return ctx._dispatch_analysis
    ctx._dispatch_analysis = None
    gaps = owners(ctx)
    if gaps is None:
        return None
    pairs, starts = join(ctx), [a for a, _, _ in gaps]
    # (idle s, the next was sent behind) and (idle s, {span: s})
    chunk, edge = [], []
    for a, b in zip(pairs, pairs[1:]):
        da, db = a.dispatch.args, b.dispatch.args
        if db["seq"] != da["seq"] + 1:
            continue            # a merged event's dispatches lie between
        idle, by_span = _idle_in(gaps, starts, a.end, b.start)
        if da["request"] == db["request"]:
            if db["index"] == da["index"] + 1:
                chunk.append((idle, bool(db["behind"])))
        elif db["index"] == 0:
            edge.append((idle, by_span))
    interior = sum(b - a for a, b, _ in gaps)
    between = sum(v for k, v in ctx.trace["idle_gaps"].items()
                  if k != trace_reduce.INSIDE_PROGRAM)
    total = collections.Counter()
    for _, _, split in gaps:
        total.update(split)
    classed = sum(g for g, _ in chunk) + sum(g for g, _ in edge)
    ctx.notes["idle_identity"] = {
        "between_execution_s": between,
        "chunk_gaps_s": sum(g for g, _ in chunk),
        "request_edges_s": sum(g for g, _ in edge),
        # before the window's first and after its last execution: what the
        # trace's own total has beyond the intervals between executions
        "window_edges_s": between - interior,
        "other_gaps_s": interior - classed,
        "residual_frac": (interior - classed) / between if between else 0.0}
    ctx._dispatch_analysis = types.SimpleNamespace(
        chunk=chunk, edge=edge, by_span=dict(total))
    return ctx._dispatch_analysis


# ------------------------------------------------------------- the readers
def chunk_gap(ctx, p):
    """``tpot.chunk_gap_device_p50_s``: over consecutive executions the
    dispatch records give to ONE request (prefill -> chunk 1, chunk k ->
    k + 1), the device's idle time from the end of one to the start of the
    next; an interval of the device's own line, so never below zero. The
    notes tell the chunks sent ``behind`` from those sent by their own tick."""
    an = analysis(ctx)
    if an is None:
        return None
    ps._count(ctx, "chunk_gap", len(an.chunk))
    for key, flag in (("behind", True), ("serial", False)):
        mid = stats.percentile([g for g, b in an.chunk if b is flag], p["q"])
        if mid is not None:
            ctx.notes[f"chunk_gap_{key}_p{p['q']}_s"] = mid
    return stats.percentile([g for g, _ in an.chunk], p["q"])


def chunks_behind(ctx, p):
    """``tpot.chunks_behind_frac``: of the window's decode-chunk ``dispatch``
    records, the share sent ``behind`` a program still to be waited for."""
    if ring(ctx) is None:
        return None
    sent = [s for s in ps._select(ps.window_spans(ctx), "dispatch", "serving")
            if s.args["program"] == p["program"]]
    if not sent:
        return None
    ps._count(ctx, "dispatch/" + p["program"], len(sent))
    return 100.0 * sum(bool(s.args["behind"]) for s in sent) / len(sent)


def request_edge_idle(ctx, p):
    """``serve.request_edge_idle_p50_s``: the device's idle time from the end
    of a request's last execution to the start of the next served request's
    prefill; ``request_edge_by_span``: the median seconds of it under each
    span of the serve loop (``queue_empty``: the callers')."""
    an = analysis(ctx)
    if an is None:
        return None
    ps._count(ctx, "request_edge", len(an.edge))
    names = sorted({n for _, split in an.edge for n in split})
    ctx.notes["request_edge_by_span"] = {
        n: stats.percentile([split.get(n, 0.0) for _, split in an.edge],
                            p["q"]) for n in names}
    return stats.percentile([g for g, _ in an.edge], p["q"])


def prefill_dispatch(ctx, p):
    """``ttft.prefill_dispatch_p50_s``: the prefill tick's entry -> its
    ``dispatch`` returned: what a request waits in its own tick before the
    device has its program. The notes give the two parts, the worker
    thread's spawn and the call itself."""
    if ring(ctx) is None:
        return None
    inside = ps.window_spans(ctx)
    ticks = {s.id: s for s in ps._select(inside, "prefill", "serving")}
    sent = [s for s in ps._select(inside, "dispatch", "serving", "prefill")
            if s.args["program"] == "prefill"]
    ps._count(ctx, "prefill/dispatch", len(sent))
    for name, values in (
            ("prefill_worker_start", [
                s.t1 - s.t0 for s in ps._select(inside, "worker_start",
                                                "serving", "prefill")]),
            ("prefill_dispatch_call", [s.t1 - s.t0 for s in sent])):
        mid = stats.percentile(values, p["q"])
        if mid is not None:
            ctx.notes[f"{name}_p{p['q']}_s"] = mid
    return stats.percentile([s.t1 - ticks[s.parent].t0 for s in sent],
                            p["q"])


def idle_in_program(ctx, p):
    """``serve.idle_in_program_frac``: the between-execution idle of the
    window while a span of the program OTHER than ``queue_empty`` is open,
    over the window. Counted between the window's first and last execution
    (``idle_identity.window_edges_s`` is what lies outside them), so it
    cannot pass ``serve.device_idle_frac``. ``idle_by_program_span``
    {span: s}; ``idle_unaccounted_s``: idle no span of the program covers."""
    an = analysis(ctx)
    if an is None or not ctx.trace["window_s"]:
        return None
    ps._count(ctx, "idle_between", len(owners(ctx)))
    ctx.notes["idle_by_program_span"] = {
        k: v for k, v in sorted(an.by_span.items()) if k != UNSPANNED}
    ctx.notes["idle_unaccounted_s"] = an.by_span.get(UNSPANNED, 0.0)
    mine = sum(v for k, v in an.by_span.items()
               if k not in (UNSPANNED, "queue_empty"))
    return 100.0 * mine / ctx.trace["window_s"]
