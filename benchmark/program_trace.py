"""The program's own spans and scopes in a profiler trace, beside the
harness's (``benchmark.trace``).

``benchmark.trace`` keeps the harness's ``bench.*`` spans and each device
op by name. This module keeps, in the same capture, what raft_tpu emits
itself:

* the serving executor's ``serving.*`` spans (``raft_tpu.serving.executor.
  SPANS``) with their trace stats: ``batch_id`` on each, ``bucket`` and
  ``n_requests`` on ``serving.pack``. They are ``core.annotate`` ranges,
  so the capture is started through ``raft_tpu.core.annotate.start_trace``
  (:func:`start`), which turns them on for its length;
* each device op's named scope (``ivf.*`` of the grouped IVF program,
  ``knn.*`` of the brute-force program). A TPU v5e's op events carry no
  ``op_name``, so the scopes come from the compiled programs' optimized
  HLO (:func:`hlo_scopes`, over ``--xla_dump_to`` text dumps), matched
  to the events by each op's signature.

The plain form is ``benchmark.trace``'s with optional keys, so
``trace.reduce`` reads it unchanged::

    {"devices": [{"name", "ops", "modules"}],
     "spans": [[name, start_ns, dur_ns], ...],            # bench.*
     "program_spans": [[name, start_ns, dur_ns, {stat: value}], ...],
     "hlo_scopes": {program: {op signature: scope}}}

:func:`reduce` adds, over the ``bench.window`` span: device milliseconds
of each scope per run of each program, the share of the program's op
time that carries a scope, the pairing of each batch's spans with its
program run, and idle gaps put down to the program's spans before the
harness's.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import re

from benchmark import trace

SERVING_PREFIX = "serving."
# raft_tpu.serving.executor.SPANS, written out so that the reduction
# reads a trace without importing the program (a test holds them equal)
PACK, WINDOW_WAIT, STAGE, DISPATCH, DEMUX = (
    "serving.pack", "serving.window_wait", "serving.stage",
    "serving.dispatch", "serving.demux")
SPANS = (PACK, WINDOW_WAIT, STAGE, DISPATCH, DEMUX)
GROUPED = "jit__grouped_impl"
BRUTE_FORCE = "jit__fused_l2_knn_impl"
# each program's scopes: raft_tpu.spatial.ann.ivf_flat.GROUPED_SCOPES and
# raft_tpu.spatial.fused_knn.KNN_SCOPES
SCOPES = {
    GROUPED: ("ivf.probe", "ivf.list_slabs", "ivf.scan", "ivf.merge"),
    BRUTE_FORCE: ("knn.prepare", "knn.chunk_mins", "knn.select",
                  "knn.rescore"),
}
# what the host was doing in a device gap, most telling first; a span of
# the program's takes a gap before any of the harness's
GAP_CAUSES = (DISPATCH, STAGE, WINDOW_WAIT, PACK, DEMUX) + trace.GAP_CAUSES


def start(log_dir: str) -> None:
    """Start the profiler as ``benchmark.trace.start`` does (Python
    tracer off, host spans kept), through the program's own
    ``start_trace``, which turns its ranges on for the capture."""
    import jax

    from raft_tpu.core.annotate import start_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    start_trace(log_dir, opts)


def stop() -> None:
    from raft_tpu.core.annotate import stop_trace

    stop_trace()


def load(log_dir: str, hlo_dir: str = None) -> dict:
    """``benchmark.trace.load``'s plain form of the newest ``.xplane.pb``
    under ``log_dir``, with the program's spans; ``hlo_dir`` adds the
    scopes of :func:`hlo_scopes`."""
    from jax.profiler import ProfileData

    out = trace.load(log_dir)
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    out["program_spans"] = [
        [e.name, e.start_ns, e.duration_ns, dict(e.stats)]
        for plane in ProfileData.from_file(files[-1]).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith(SERVING_PREFIX)]
    if hlo_dir:
        out["hlo_scopes"] = hlo_scopes(hlo_dir)
    return out


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_CALLED_SET = re.compile(r"(?:branch|called)_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")


def _close(text: str, i: int) -> int:
    """Index past the parenthesis that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j + 1
    return len(text)


def _split(text: str):
    """``(signature, rest)`` of an HLO instruction's text, or None: the
    signature ``name = shape opcode`` is what an op event's text and the
    dumped module's line share (the event spells operand types out)."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    if not text.startswith("%") or " = " not in text:
        return None
    head, rest = text[1:].split(" = ", 1)
    # the shape: a tuple's is parenthesized, with spaces inside
    at = _close(rest, 0) if rest.startswith("(") else rest.find(" ")
    opening = rest.find("(", at)
    if at < 0 or opening < 0:
        return None
    return f"{head} = {rest[:opening]}", rest[opening:]


def signature(text: str) -> str:
    """The signature of an op event's (or HLO line's) text, or ""."""
    got = _split(text)
    return got[0] if got else ""


def scope_of(path: str, scopes) -> str:
    """The innermost of ``scopes`` in an op_name path, or None:
    ``jit(f)/ivf.scan/while/body/ivf.list_slabs/dynamic_slice`` ->
    ``ivf.list_slabs``."""
    for part in reversed(path.split("/")):
        if part in scopes:
            return part
    return None


def _module_scopes(text: str, scopes) -> dict:
    """signature -> scope over one HLO module's text. An op with no
    op_name metadata (the compiler made it) takes, in turn: the scope of
    the ops fused into it; of the nearest op that reads it (into a
    loop's body, through the loop's tuple: a copy made for a loop takes
    the scope of what the body does with it); of the op that runs its
    computation (a gather the compiler expanded into a loop lends the
    loop's body its scope); of the nearest op it reads."""
    own, comp_of, sig, opcode, index, body = {}, {}, {}, {}, {}, {}
    users, operands = collections.defaultdict(list), {}
    members = collections.defaultdict(list)   # computation -> its ops
    called, caller, param = {}, {}, {}
    comp = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head and " = " not in line.split("(", 1)[0]:
            comp = head.group(1)
            continue
        got = _split(line)
        if got is None:
            continue
        signature_, rest = got
        name = signature_.split(" = ", 1)[0]
        meta = _OP_NAME.search(rest)
        own[name] = scope_of(meta.group(1), scopes) if meta else None
        sig[name], comp_of[name] = signature_, comp
        opcode[name] = signature_.rsplit(" ", 1)[-1]
        members[comp].append(name)
        operands[name] = _OPERAND.findall(rest[:_close(rest, 0)])
        for a in operands[name]:
            users[a].append(name)
        calls = _CALLED.findall(rest) + [
            c.strip().lstrip("%") for group in _CALLED_SET.findall(rest)
            for c in group.split(",")]
        called[name] = calls
        for c in calls:
            caller.setdefault(c, name)
        if opcode[name] == "parameter":
            param.setdefault(comp, name)
        elif opcode[name] == "get-tuple-element":
            index[name] = int(re.search(r"index=(\d+)", rest).group(1))
        elif opcode[name] == "while":
            body[name] = re.search(r"body=%([\w.\-]+)", rest).group(1)

    def readers(n, m):
        """What reads ``n`` through ``m``: a loop's body reads element
        i of the loop's tuple through its get-tuple-element i."""
        if opcode.get(m) != "tuple":
            return [m]
        out = [m]
        pos = operands[m].index(n)
        for w in users.get(m, ()):
            if opcode.get(w) == "while" and body.get(w) in param:
                out += [g for g in users[param[body[w]]]
                        if index.get(g) == pos]
        return out

    def by_readers(name):
        seen, frontier, fallback = {name}, [name], None
        while frontier:
            nxt = []
            for n in frontier:
                for m0 in users.get(n, ()):
                    for m in readers(n, m0):
                        if m in seen:
                            continue
                        seen.add(m)
                        if opcode.get(m) == "while" and own.get(m):
                            # a loop's own scope only where its body says
                            # nothing more; the innermost such loop's
                            fallback = own[m]
                        elif own.get(m):
                            return own[m]
                        else:
                            nxt.append(m)
            frontier = nxt
        return fallback

    def by_operands(name):
        seen, frontier = {name}, [name]
        while frontier:
            nxt = []
            for n in frontier:
                for m in operands.get(n, ()):
                    if m in seen or comp_of.get(m) != comp_of[name]:
                        continue
                    if own.get(m):
                        return own[m]
                    seen.add(m)
                    nxt.append(m)
            frontier = nxt
        return None

    def resolve(name, depth=0):
        if own.get(name) or depth > 16:
            return own.get(name)
        inside = [own[m] for c in called.get(name, ())
                  for m in members.get(c, ()) if own.get(m)]
        if inside:
            return collections.Counter(inside).most_common(1)[0][0]
        up = caller.get(comp_of[name])
        return (by_readers(name)
                or (resolve(up, depth + 1) if up else None)
                or by_operands(name))

    return {sig[n]: resolve(n) for n in own}


def hlo_scopes(hlo_dir: str) -> dict:
    """program -> {op signature: scope} from the optimized HLO text that
    ``--xla_dump_to`` wrote for each program of :data:`SCOPES` (or its
    gzip copy). Where
    two compiled programs of one name (two batch shapes) give one
    signature different scopes, it is left out."""
    out = {}
    for prog, scopes in SCOPES.items():
        merged, clash = {}, set()
        for path in sorted(glob.glob(os.path.join(
                hlo_dir, f"*.{prog}.*after_optimizations.txt*"))):
            opener = gzip.open if path.endswith(".gz") else open
            with opener(path, "rt") as f:
                for op, s in _module_scopes(f.read(), scopes).items():
                    if s is None or merged.get(op, s) != s:
                        clash.add(op)
                    merged.setdefault(op, s)
        found = {op: s for op, s in merged.items() if op not in clash}
        if found:
            out[prog] = found
    return out


def _self_times(ops):
    """Each op's own duration: less that of the ops nested in it (on one
    line, a while loop's event encloses its body's)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [b - a for _, a, b in ops]
    stack = []  # [index, end]
    for i in order:
        _, a, b = ops[i]
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1][0]] -= min(b, stack[-1][1]) - a
        stack.append((i, b))
    return own


@dataclasses.dataclass
class Batch:
    """One batch's spans paired with its program run (profiler clock,
    ns; a span the capture missed reads None)."""

    batch_id: int
    run: tuple                 # (start, end) of its program run
    demux: tuple               # (start, end) of serving.demux
    dispatch: tuple = None     # (start, end) of serving.dispatch
    window_wait_ns: float = None
    stage_ns: float = None
    pack: dict = None          # serving.pack's stats

    @property
    def device_queue_ns(self):
        return None if self.dispatch is None else (
            self.run[0] - self.dispatch[1])

    @property
    def demux_lag_ns(self):
        return self.demux[0] - self.run[1]


@dataclasses.dataclass
class ProgramSummary:
    """What the program's spans and scopes say over the traced window."""

    base: trace.Summary
    # program -> {scope: device ms per run}; "" sums the ops with none
    scope_ms: dict
    # program -> share of its op time (own time, in runs wholly in the
    # window) that carries a scope
    scoped_share: dict
    # the window's batches paired with their runs of GROUPED, or None
    # where the pairing is void (or there are no serving spans)
    batches: list
    gaps: list                 # [(cause, seconds)], longest first

    def _mean_ms(self, values):
        values = [v for v in values if v is not None]
        if not self.batches or not values:
            return None
        return sum(values) / len(values) * 1e-6

    def window_wait_ms(self):
        return self._mean_ms(b.window_wait_ns for b in self.batches or ())

    def device_queue_ms(self):
        return self._mean_ms(b.device_queue_ns for b in self.batches or ())

    def demux_lag_ms(self):
        return self._mean_ms(b.demux_lag_ns for b in self.batches or ())


def _runs(dev, program, lo, hi):
    return sorted((a, a + d) for name, a, d in dev["modules"]
                  if trace.base_name(name) == program
                  and a >= lo and a + d <= hi)


def _scopes(trace_: dict, lo, hi):
    """Per program: ms per run of each scope, and the scoped share."""
    hlo = trace_.get("hlo_scopes", {})
    sums = collections.defaultdict(collections.Counter)
    runs_n = collections.Counter()
    for dev in trace_["devices"]:
        ops = [(t, a, a + d) for t, a, d in dev["ops"]]
        own = _self_times(ops)
        for prog in SCOPES:
            runs = _runs(dev, prog, lo, hi)
            if not runs:
                continue
            runs_n[prog] += len(runs)
            starts = [a for a, _ in runs]
            names = hlo.get(prog, {})
            for (text, a, b), t in zip(ops, own):
                j = bisect.bisect_right(starts, a) - 1
                if j < 0 or b > runs[j][1]:
                    continue
                sums[prog][names.get(signature(text), "")] += t
    scope_ms, share = {}, {}
    for prog, c in sums.items():
        total = sum(c.values())
        scope_ms[prog] = {s: v * 1e-6 / runs_n[prog] for s, v in c.items()}
        share[prog] = (total - c.get("", 0)) / total if total else None
    return scope_ms, share


def _by_batch(program_spans):
    out = collections.defaultdict(dict)
    for name, s, d, stats in program_spans:
        bid = stats.get("batch_id")
        if bid is not None:
            out[int(bid)][name] = (s, s + d, stats)
    return out


def pair(trace_: dict, lo, hi, program: str = GROUPED):
    """The window's batches, each with its run of ``program``; None
    where there are none or the pairing is void.

    The device runs one program at a time in dispatch order, and the
    drain demuxes in completion order, so each run wholly in the window
    belongs to the batch whose ``serving.demux`` is the first to start
    at or after the run's end. The pairing is void unless the batches
    so found are consecutive, one a run, and each batch's
    ``serving.dispatch`` (where the capture holds it) ended before its
    run began."""
    spans = _by_batch(trace_.get("program_spans", ()))
    demux = sorted((v[DEMUX][0], bid) for bid, v in spans.items()
                   if DEMUX in v)
    devs = [d for d in trace_["devices"] if d["ops"]]
    if not demux or len(devs) != 1:
        return None
    starts = [s for s, _ in demux]
    out = []
    for a, b in _runs(devs[0], program, lo, hi):
        j = bisect.bisect_left(starts, b)
        if j == len(demux):
            return None
        bid = demux[j][1]
        if out and bid != out[-1].batch_id + 1:
            return None
        v = spans[bid]
        disp = v.get(DISPATCH)
        if disp is not None and disp[1] > a:
            return None
        ww = v.get(WINDOW_WAIT)
        st = v.get(STAGE)
        out.append(Batch(
            batch_id=bid, run=(a, b), demux=v[DEMUX][:2],
            dispatch=None if disp is None else disp[:2],
            window_wait_ns=None if ww is None else ww[1] - ww[0],
            stage_ns=None if st is None else st[1] - st[0],
            pack=None if PACK not in v else v[PACK][2]))
    return out or None


def _cause(a, b, spans):
    """The span that covers most of the gap [a, b]: the program's where
    any covers it, else the harness's; the most telling first on ties;
    ``no_bench_span`` where none does."""
    cover = collections.Counter()
    for name, s, d in spans:
        if name in GAP_CAUSES:
            cover[name] += max(0, min(b, s + d) - max(a, s))
    for tier in (SPANS, trace.GAP_CAUSES):
        causes = [n for n in GAP_CAUSES if n in tier]
        best = max(causes, key=lambda n: (cover[n], -causes.index(n)))
        if cover[best] > 0:
            return best
    return "no_bench_span"


def _gaps(trace_: dict, lo, hi):
    spans = list(trace_["spans"]) + [
        s[:3] for s in trace_.get("program_spans", ())]
    gaps = []
    for dev in trace_["devices"]:
        if not dev["ops"]:
            continue
        merged = trace._union([(a, b) for _, a, b in
                               trace._clip(dev["ops"], lo, hi)])
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps.extend((a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a)
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    return [(_cause(a, b, spans), (b - a) * 1e-9) for a, b in gaps[:10]]


def reduce(trace_: dict) -> ProgramSummary:
    """Reduce a plain trace over its ``bench.window`` span."""
    base = trace.reduce(trace_)
    lo, hi = trace._window(trace_["spans"])
    scope_ms, share = _scopes(trace_, lo, hi)
    return ProgramSummary(base=base, scope_ms=scope_ms, scoped_share=share,
                          batches=pair(trace_, lo, hi),
                          gaps=_gaps(trace_, lo, hi))
