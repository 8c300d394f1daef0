"""From a profiler trace to tables that name the engine's parts.

`trace_reduce` reads the device's planes alone. This module reads the
same `.xplane.pb` a second time for what the program itself wrote there:

- the host's spans (`jax.profiler.TraceAnnotation`: `engine.step` and the
  phases of a tick under it, `server.deliver`, `train.step`) with their
  arguments, from the `/host:CPU` plane, on the profiler's clock;
- for every operation on the device the scope it was traced under
  (`jax.named_scope`: `attn`, `mlp`, `loss_head`, ...). `ProfileData`
  does not give it out: it is the `tf_op` stat of the operation's event
  metadata, which `op_scopes` reads from the file's own encoding.

- the runtime's own record, on the host's clock, of when it handed each
  program to the device (`DoEnqueueProgram`, with the `run_id` that the
  program's event on `XLA Modules` carries too).

The last is what joins the two sides. The host's and the device's events
are NOT on one clock to better than a millisecond (on the v5e of PR 25
the device's ran 1.1-1.2 ms ahead: a program "started" that long before
the host says it was enqueued; `clock_check` measures it in every
capture). So nothing here lays a device time over a host span. A program
belongs to the `engine.dispatch` span inside which the host enqueued it,
and an idle interval of the device, which ends when some program starts,
is laid on the host's clock so as to end when that program was enqueued.
No offset is fitted.

A capture is `{"spans": [[thread, name, start_ns, end_ns, {arg: value}]],
"events": [[plane, line, name, start_ns, duration_ns, scope, run_id]],
"enqueues": {run_id: host start_ns}}`, which is also the form of the
recorded fixtures. Every reduction below takes a capture and nothing
else, so each is tested on a fixture. With a program that writes no span
(the parent of the PR that brought this file) `load` finds none, and
every reader built on it returns nothing.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import statistics
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from . import trace_reduce as tr
from .harness import ROOT

TICK, TRAIN_STEP, DELIVER = "engine.step", "train.step", "server.deliver"
DISPATCH, READBACK = "engine.dispatch", "engine.readback_wait"
ENQUEUE = "DoEnqueueProgram"      # the runtime's host event, with run_id
SPAN_PREFIXES = ("engine.", "server.", "train.")
# the device program each kind of dispatch launches
PROGRAM_OF = {"ragged": "jit_run", "decode": "jit_step"}
SCOPES = ("embed", "attn", "mlp", "lm_head", "sample", "loss_head",
          "optimizer")
# the Pallas kernels by their `name=`; a decode tick runs one of two
RAGGED_KERNELS = ("ragged_paged_attention",)
DECODE_KERNELS = ("paged_decode", "paged_decode_mp")
FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
# labels of idle time that say what held the device; the others
# ("other": in a tick but in no phase; "outside spans": before the first
# and after the last span of the capture) say only where it was
BETWEEN, NO_WORK, OTHER, OUTSIDE = ("between ticks", "no work", "other",
                                    "outside spans")


# ---- reading the file --------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def op_scopes(path: str) -> Dict[str, str]:
    """{an operation's event name: the scope path it was traced under}
    for the device planes of an `.xplane.pb`. The file is an `XSpace`:
    planes (field 1), each with a name (2), lines (3, skipped), event
    metadata (4: a map to id 1, name 2, stats 5) and stat metadata (5:
    a map to id 1, name 2). An operation's scope is the `tf_op` stat of
    its metadata, a string (5) or a reference to a stat name (7)."""
    out: Dict[str, str] = {}
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf in (4, 5):
                entry = dict(_fields(pv)).get(2)
                if entry is None:
                    continue
                if pf == 4:
                    events.append(entry)
                else:
                    meta = dict(_fields(entry))
                    stat_names[meta.get(1, 0)] = bytes(
                        meta.get(2, b"")).decode()
        if not name.startswith(tr.DEVICE_PLANE):
            continue
        for entry in events:
            ev_name, scope = "", ""
            for ef, ev in _fields(entry):
                if ef == 2:
                    ev_name = bytes(ev).decode()
                elif ef == 5:
                    stat = dict(_fields(ev))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    if 5 in stat:
                        scope = bytes(stat[5]).decode()
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
            if scope:
                out[ev_name] = scope.rstrip(":")
    return out


def newest_trace(root: str = ROOT) -> Optional[str]:
    """The newest `.xplane.pb` under chiprun_out/benchmark/*/trace that
    was written after this process started: this run's own."""
    paths = glob.glob(os.path.join(root, "chiprun_out", "benchmark", "*",
                                   "trace", "**", "*.xplane.pb"),
                      recursive=True)
    try:
        started = os.stat(f"/proc/{os.getpid()}").st_ctime
    except OSError:
        started = 0.0
    paths = [p for p in paths if os.path.getmtime(p) >= started]
    return max(paths, key=os.path.getmtime) if paths else None


def load(path: str) -> Dict[str, Any]:
    """The capture of one `.xplane.pb`."""
    from jax.profiler import ProfileData
    scopes = op_scopes(path)
    spans: List[list] = []
    events: List[list] = []
    enqueues: Dict[int, int] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append([line.name, ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      dict(ev.stats)])
                    elif ev.name == ENQUEUE:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            enqueues[int(run_id)] = int(ev.start_ns)
        elif plane.name.startswith(tr.DEVICE_PLANE):
            for line in plane.lines:
                if line.name not in (tr.MODULES, tr.OPS):
                    continue
                ops = line.name == tr.OPS
                for ev in line.events:
                    events.append([
                        plane.name, line.name,
                        tr.op_name(ev.name) if ops else ev.name,
                        int(ev.start_ns), int(ev.duration_ns),
                        scopes.get(ev.name, "") if ops else "",
                        0 if ops else int(
                            dict(ev.stats).get("run_id", 0))])
    spans.sort(key=lambda s: (s[2], -s[3]))
    return {"path": path, "spans": spans, "events": events,
            "enqueues": enqueues}


_CAPTURES: Dict[str, Optional[Dict[str, Any]]] = {}


def capture(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """This run's capture, read once for all readers: None where the run
    was not traced, left no file, or its program wrote no span."""
    if not run.get("events"):
        return None
    path = newest_trace()
    if path is None:
        return None
    if path not in _CAPTURES:
        cap = load(path)
        _CAPTURES[path] = cap if cap["spans"] else None
        if cap["spans"]:
            report(cap, run)
    return _CAPTURES[path]


# ---- the host's timeline -----------------------------------------------

def _named(cap, *names: str) -> List[list]:
    return [s for s in cap["spans"] if s[1] in names]


def ticks(cap) -> List[list]:
    """The top-level spans in order: engine ticks, or train steps."""
    return _named(cap, TICK, TRAIN_STEP)


def _flatten(spans: Sequence[list]) -> List[Tuple[int, int, str]]:
    """Spans of one thread (sorted by start, outer first) as segments
    that do not overlap, each labelled by its innermost span. A tick's
    own time, under no phase, is OTHER."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, str]] = []         # (end, label), outer first
    at = 0

    def close(upto: int) -> None:
        nonlocal at
        while stack and stack[-1][0] <= upto:
            end, label = stack.pop()
            if end > at:
                out.append((at, end, label))
                at = end

    for _, name, start, end, _ in spans:
        close(start)
        if stack and start > at:
            out.append((at, start, stack[-1][1]))
        at = max(at, start)
        stack.append((end, OTHER if name == TICK else name))
    close(1 << 62)
    return out


def timeline(cap) -> List[Tuple[int, int, str]]:
    """The capture from its first span to its last as labelled segments
    in order, none overlapping: inside a tick the innermost phase;
    between two ticks `server.deliver` where the pump was delivering,
    else BETWEEN if the tick before left work and NO_WORK if it did
    not."""
    engine = [s for s in cap["spans"] if s[1] != DELIVER]
    segs: List[Tuple[int, int, str]] = []
    for thread in sorted({s[0] for s in engine}):
        segs.extend(_flatten([s for s in engine if s[0] == thread]))
    segs.sort()
    delivers = sorted((s[2], s[3]) for s in _named(cap, DELIVER))
    tops = ticks(cap)
    top_ends = [t[3] for t in tops]
    out: List[Tuple[int, int, str]] = []

    def fill(a: int, b: int) -> None:
        """The hole [a, b) between two ticks."""
        i = bisect.bisect_right(top_ends, a) - 1
        work = tops[i][4].get("work", 1) if i >= 0 else 1
        rest = BETWEEN if work else NO_WORK
        for d0, d1 in delivers:
            d0, d1 = max(d0, a), min(d1, b)
            if d1 > d0:
                if d0 > a:
                    out.append((a, d0, rest))
                out.append((d0, d1, DELIVER))
                a = d1
        if b > a:
            out.append((a, b, rest))

    at = segs[0][0] if segs else 0
    for start, end, label in segs:
        if start > at:
            fill(at, start)
        start = max(start, at)
        if end > start:
            out.append((start, end, label))
            at = end
    return out


# ---- the device, joined to the host ------------------------------------

def chip0(cap, line: str) -> List[list]:
    names = tr.planes(cap["events"])
    if not names:
        return []
    return sorted((e for e in cap["events"]
                   if e[0] == names[0] and e[1] == line),
                  key=lambda e: e[3])


def enqueued_at(cap, module: Sequence) -> Optional[int]:
    """When, on the host's clock, the runtime handed this `XLA Modules`
    event's program to the device."""
    return cap["enqueues"].get(module[6]) if len(module) > 6 else None


def programs(cap) -> List[Dict[str, Any]]:
    """Every `jit_run` / `jit_step` joined to the `engine.dispatch` span
    that launched it: the last span to start before the host enqueued
    it, which has to be of its kind. `wait_end` is the end of the `engine.readback_wait`
    that names the span's tick."""
    spans = _named(cap, DISPATCH)
    starts = [s[2] for s in spans]
    waits = {s[4].get("of"): s[3] for s in _named(cap, READBACK)}
    kind_of = {module: kind for kind, module in PROGRAM_OF.items()}
    out: List[Dict[str, Any]] = []
    for m in chip0(cap, tr.MODULES):
        kind = kind_of.get(tr.module_name(m[2]))
        at = enqueued_at(cap, m)
        if kind is None or at is None:
            continue
        # the runtime enqueues once the program's uploads have landed,
        # which can be just after the span's Python call has returned:
        # the span is the last of its kind to start before that
        i = bisect.bisect_right(starts, at) - 1
        if i < 0 or spans[i][4].get("kind") != kind:
            continue              # launched before the capture began
        args = spans[i][4]
        out.append({"kind": kind, "module": tr.module_name(m[2]),
                    "start": m[3], "end": m[3] + m[4], "enqueued": at,
                    "span_start": spans[i][2], "span_end": spans[i][3],
                    "args": args, "wait_end": waits.get(args.get("tick"))})
    return out


def clock_check(cap) -> Dict[str, Any]:
    """How far the host's and the device's clocks disagree, each as the
    largest violation in ns of what must hold on one clock (0 where it
    holds): a program starts after its dispatch span starts, it ends
    before the wait for its tokens ends, and it starts after the runtime
    says it enqueued it."""
    progs = programs(cap)
    waited = [p for p in progs if p["wait_end"] is not None]
    enq = [at - m[3] for m in chip0(cap, tr.MODULES)
           for at in [enqueued_at(cap, m)] if at is not None]
    return {
        "programs": len(progs),
        "program_before_dispatch_ns": max(
            [p["span_start"] - p["start"] for p in progs] + [0]),
        "waits": len(waited),
        "wait_ends_before_program_ns": max(
            [p["end"] - p["wait_end"] for p in waited] + [0]),
        "enqueues": len(enq),
        "program_before_enqueue_ns": max(enq + [0]),
    }


def idle_intervals(cap) -> List[Tuple[int, int]]:
    names = tr.planes(cap["events"])
    if not names:
        return []
    busy = tr.busy_intervals(cap["events"], names[0])
    return [(a_end, b_start)
            for (_, a_end), (b_start, _) in zip(busy, busy[1:])
            if b_start > a_end]


def idle_by_label(cap) -> Dict[str, int]:
    """Idle ns on chip 0 inside its window, by what covered it. A gap
    inside one program is the program's own (`in jit_run`). Any other
    ends when a program starts: it is laid on the host's clock so as to
    end when the runtime enqueued that program, and split over the
    host's timeline there; with no such record it stays OUTSIDE."""
    mods = chip0(cap, tr.MODULES)
    mod_starts = [m[3] for m in mods]
    segs = timeline(cap)
    seg_starts = [s[0] for s in segs]
    out: Dict[str, int] = {}

    def add(label: str, ns: int) -> None:
        if ns > 0:
            out[label] = out.get(label, 0) + ns

    for a, b in idle_intervals(cap):
        i = bisect.bisect_right(mod_starts, a) - 1
        if i >= 0 and mods[i][3] + mods[i][4] >= b:
            add("in " + tr.module_name(mods[i][2]), b - a)
            continue
        at = enqueued_at(cap, mods[i + 1]) if i + 1 < len(mods) else None
        covered = 0
        if at is not None:
            lo_t, hi_t = at - (b - a), at
            j = max(bisect.bisect_right(seg_starts, lo_t) - 1, 0)
            while j < len(segs) and segs[j][0] < hi_t:
                lo, hi = max(segs[j][0], lo_t), min(segs[j][1], hi_t)
                if hi > lo:
                    add(segs[j][2], hi - lo)
                    covered += hi - lo
                j += 1
        add(OUTSIDE, (b - a) - covered)
    return out


def idle_summary(cap) -> Optional[Dict[str, Any]]:
    """Idle time per tick under a tick and between ticks, and the share
    of all idle time that carries a label saying what held the device."""
    tops = ticks(cap)
    if not tops or not cap["enqueues"]:
        return None
    by = idle_by_label(cap)
    total = sum(by.values())
    between = by.get(DELIVER, 0) + by.get(BETWEEN, 0)
    apart = between + by.get(NO_WORK, 0) + by.get(OUTSIDE, 0)
    in_program = sum(v for k, v in by.items() if k.startswith("in "))
    in_tick = total - apart - in_program
    unlabelled = by.get(OTHER, 0) + by.get(OUTSIDE, 0)
    return {
        "ticks": len(tops),
        "idle_ms": total / 1e6,
        "by_label_ms": {k: v / 1e6 for k, v in
                        sorted(by.items(), key=lambda kv: -kv[1])},
        "in_tick_ms_per_tick": in_tick / 1e6 / len(tops),
        "between_ticks_ms_per_tick": between / 1e6 / len(tops),
        "attributed_share_pct": (100.0 * (1 - unlabelled / total)
                                 if total else None),
    }


def programs_per_tick(cap) -> Optional[Dict[str, Any]]:
    """Programs the device ran per engine tick, between the first tick's
    start and the last one's end, and which they were."""
    tops = _named(cap, TICK)
    if not tops:
        return None
    lo, hi = tops[0][2], max(t[3] for t in tops)
    names: Dict[str, int] = {}
    for m in chip0(cap, tr.MODULES):
        if lo <= m[3] <= hi:
            name = tr.module_name(m[2])
            names[name] = names.get(name, 0) + 1
    n = len(tops)
    return {"ticks": n, "per_tick": sum(names.values()) / n,
            "by_program": {k: v / n for k, v in
                           sorted(names.items(), key=lambda kv: -kv[1])}}


def ragged_cost(cap) -> Optional[Dict[str, Any]]:
    """Device time of the traced ragged programs over the tokens their
    dispatch spans say they carried, and the same by (T, ctx, rows)."""
    progs = [p for p in programs(cap) if p["kind"] == "ragged"]
    if not progs:
        return None
    table: Dict[Tuple[int, int, int], List[Tuple[int, int]]] = {}
    for p in progs:
        a = p["args"]
        tokens = a["decode_rows"] + a["prefill_tokens"]
        table.setdefault((a["T"], a["ctx"], a["rows"]), []).append(
            (p["end"] - p["start"], tokens))
    ns = sum(d for rows in table.values() for d, _ in rows)
    tokens = sum(t for rows in table.values() for _, t in rows)
    return {
        "programs": len(progs), "tokens": tokens,
        "us_per_token": ns / 1e3 / tokens if tokens else None,
        "by_T_ctx_rows": [
            {"T": k[0], "ctx": k[1], "rows": k[2], "programs": len(v),
             "ms_median": statistics.median(d for d, _ in v) / 1e6,
             "tokens_mean": sum(t for _, t in v) / len(v)}
            for k, v in sorted(table.items())]}


# ---- operations, kernels and scopes ------------------------------------

def op_self_ns(cap) -> List[Tuple[str, str, int, int]]:
    """(name, scope, start, ns no operation nested inside it covers) for
    every operation on chip 0: a `while` is charged what its body's
    operations leave, as in `trace_reduce.op_self_seconds`."""
    out: List[Tuple[str, str, int, int]] = []
    stack: List[list] = []        # [name, scope, start, end, self_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][3] <= upto:
            name, scope, start, _, self_ns = stack.pop()
            out.append((name, scope, start, self_ns))

    for _, _, name, start, dur, scope, *_ in chip0(cap, tr.OPS):
        close(start)
        if stack:
            stack[-1][4] -= min(dur, stack[-1][3] - start)
        stack.append([name, scope, start, start + dur, dur])
    close(1 << 62)
    return out


def busy_ns(cap) -> int:
    names = tr.planes(cap["events"])
    if not names:
        return 0
    return sum(b - a for a, b in tr.busy_intervals(cap["events"], names[0]))


def scope_of(path: str) -> str:
    """The outermost of SCOPES on an operation's scope path, a path like
    `jit(run)/while/body/closed_call/attn/dot_general`; under
    differentiation a component reads `jvp(attn)` or
    `transpose(jvp(attn))`. "" where it names none."""
    for part in path.split("/"):
        inner = re.sub(r"^(?:\w+\()+|\)+$", "", part)
        if inner in SCOPES:
            return inner
    return ""


def is_kernel(name: str, *kernels: str) -> bool:
    """Is this operation one of `kernels`?
    `ragged_paged_attention.6[custom-call]` is kernel
    `ragged_paged_attention`; `paged_decode_mp.5[custom-call]` is kernel
    `paged_decode_mp` and not `paged_decode`."""
    return any(re.fullmatch(re.escape(k) + r"(\.\d+)?(\[.*\])?", name)
               for k in kernels)


def share_of_busy(cap, pick) -> Optional[float]:
    """100 x self time of the operations `pick(name, scope)` accepts
    over chip 0's busy time."""
    busy = busy_ns(cap)
    if not busy:
        return None
    return 100.0 * sum(ns for name, scope, _, ns in op_self_ns(cap)
                       if pick(name, scope)) / busy


def scope_shares(cap) -> Dict[str, float]:
    """% of busy time under each of SCOPES, and under none."""
    busy = busy_ns(cap) or 1
    by: Dict[str, int] = {}
    for _, scope, _, ns in op_self_ns(cap):
        key = scope_of(scope) or "(no scope)"
        by[key] = by.get(key, 0) + ns
    return {k: 100.0 * v / busy
            for k, v in sorted(by.items(), key=lambda kv: -kv[1])}


def kernel_shares(cap) -> Dict[str, float]:
    """% of busy time in each Pallas kernel that ran."""
    busy = busy_ns(cap) or 1
    by: Dict[str, int] = {}
    for name, _, _, ns in op_self_ns(cap):
        if name.endswith("[custom-call]"):
            key = re.sub(r"(\.\d+)?\[custom-call\]$", "", name)
            by[key] = by.get(key, 0) + ns
    return {k: 100.0 * v / busy
            for k, v in sorted(by.items(), key=lambda kv: -kv[1])}


def kernel_traffic(cap, kind: str, kernels: Sequence[str], min_bytes
                   ) -> Optional[Dict[str, float]]:
    """For the programs of `kind` that found their dispatch span: the
    time chip 0 spent in operations of `kernels`, and the least bytes
    `min_bytes(span arguments)` says that work needs."""
    progs = [p for p in programs(cap) if p["kind"] == kind]
    ops = [(name, start, ns) for name, _, start, ns in op_self_ns(cap)
           if is_kernel(name, *kernels)]
    starts = [o[1] for o in ops]
    ns = need = 0
    used = 0
    for p in progs:
        lo = bisect.bisect_left(starts, p["start"])
        hi = bisect.bisect_left(starts, p["end"])
        spent = sum(o[2] for o in ops[lo:hi])
        if spent:
            ns += spent
            need += min_bytes(p["args"])
            used += 1
    if not ns:
        return None
    return {"programs": used, "kernel_ms": ns / 1e6, "min_bytes": need,
            "bytes_per_s": need / (ns / 1e9)}


# ---- the tables a traced run leaves ------------------------------------

def tables(cap, run: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Everything above for one capture, as it is written beside the
    trace (`span_tables.json`) and said on an earlier line."""
    out = {"spans": len(cap["spans"]), "clock": clock_check(cap),
           "idle": idle_summary(cap),
           "programs_per_tick": programs_per_tick(cap),
           "ragged_cost": ragged_cost(cap),
           "kernel_share_pct": kernel_shares(cap),
           "scope_share_pct": scope_shares(cap)}
    if run is not None and run.get("config", {}).get("engine"):
        from . import kernel_costs
        cfg = run["config"]
        out["kernel_traffic"] = {
            "ragged": kernel_traffic(
                cap, "ragged", RAGGED_KERNELS,
                lambda a: kernel_costs.ragged_attention_min_bytes(cfg, a)),
            "decode": kernel_traffic(
                cap, "decode", DECODE_KERNELS,
                lambda a: kernel_costs.paged_decode_min_bytes(cfg, a))}
    return out


def report(cap, run: Optional[Dict[str, Any]] = None) -> None:
    """Write the tables beside the trace and say them."""
    from .harness import say
    out = tables(cap, run)
    cell_dir = cap["path"].split(os.sep + "trace" + os.sep)[0]
    with open(os.path.join(cell_dir, "span_tables.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)
    for key, value in out.items():
        say(f"[spans] {key}: {json.dumps(value, default=str)}")
