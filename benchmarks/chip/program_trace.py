"""The program's own names in a profiler trace: the step's stage scopes
and the serving loop's phases, and the per-stage numbers they give.

``trace_reduce`` reads what the benchmark records itself: device ops by
their HLO name and the benchmark's wrapper spans. This module reads what
the program records besides, on the same clock:

* each device op's stage — the chain of known stage scopes
  (``jax.named_scope`` in ``core/pipeline.py`` and ``core/collector.py``,
  ``STAGES``) in the op's ``op_name`` metadata. On a TPU the profiler
  keeps it as the ``tf_op`` stat of the op's event metadata
  (``jit(dfa_step)/reporter/ingest/gather:``), which
  ``jax.profiler.ProfileData`` does not show, so :func:`op_names` reads
  it from the ``.xplane.pb`` itself;
* the serving loop's ``serve/<phase>`` spans (``launch/serving.py``),
  each with the ``period`` it serves.

:func:`read_xplane` returns what ``trace_reduce.read_xplane`` returns,
with two keys more (``"scopes"``, one entry per device op, and
``"serve"``), so ``trace_reduce.reduce`` reads it unchanged.
:func:`reduce` returns what ``trace_reduce.reduce`` returns, with
per-stage device time, the time no stage scope holds, the serving loop's
phases per period, and the idle gaps labelled by the program's spans
where one overlaps them (else by the benchmark's, as before). On a trace
without the program's names it returns ``trace_reduce.reduce``'s numbers
and labels, and the new keys are empty.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Optional

import trace_reduce

# stage -> its child scopes, as the program names them
STAGES = {"reporter": ("ingest", "due", "reports"), "route": (),
          "exchange": (), "translate": (), "faults": (),
          "collector": ("validate", "place"), "enrich": ("infer",)}
# the stat of an XLA Ops event's metadata that holds the op's ``op_name``
OP_NAME_STAT = "tf_op"
SERVE = "serve/"
WRAPPED = re.compile(r"^[\w-]+\((.*)\)$")     # vmap(due) -> due


def stage_path(op_name: Optional[str]) -> Optional[str]:
    """``jit(dfa_step)/shard_map/collector/validate/sort`` ->
    ``collector/validate``: the known stage scopes in an op's name,
    outermost first; None when it holds none."""
    chain: List[str] = []
    for part in (op_name or "").split("/"):
        m = WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = WRAPPED.match(part)
        if not chain and part in STAGES:
            chain = [part]
        elif len(chain) == 1 and part in STAGES[chain[0]]:
            chain.append(part)
    return "/".join(chain) or None


def _fields(buf: bytes, pos: int, end: int):
    """The protobuf fields in ``buf[pos:end]``: ``(number, value)``, the
    value an int for a varint and a ``(start, end)`` span for a
    length-delimited field; fixed-width fields are skipped."""
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at {pos}")
        yield number, value


def _varint(buf: bytes, pos: int):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{device id: {XLA op event name: its op_name metadata}}`` from an
    ``.xplane.pb``: the ``OP_NAME_STAT`` stat of each device plane's event
    metadata (``XSpace.planes`` 1; ``XPlane.name`` 2, ``event_metadata``
    4, ``stat_metadata`` 5; ``XEventMetadata.name`` 2, ``stats`` 5;
    ``XStat.metadata_id`` 1, ``str_value`` 5; ``XStatMetadata.name``
    2). The lines, which hold most of the file, are skipped unread."""
    with open(path, "rb") as f:
        buf = f.read()

    def text(span):
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for n, v in _fields(buf, *plane):
            if n == 2:
                name = text(v)
            elif n == 4:
                events.append(v)
            elif n == 5:
                entry = dict(_fields(buf, *v))
                meta = dict(_fields(buf, *entry[2])) if 2 in entry else {}
                if 2 in meta:
                    stat_names[entry.get(1, 0)] = text(meta[2])
        m = trace_reduce.DEVICE_PLANE.match(name or "")
        if not m:
            continue
        want = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        ops = out.setdefault(m.group(1), {})
        for entry in events:
            entry = dict(_fields(buf, *entry))
            if 2 not in entry:
                continue
            ev_name, op_name = None, None
            for n, v in _fields(buf, *entry[2]):
                if n == 2:
                    ev_name = text(v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat.get(1) in want and 5 in stat:
                        op_name = text(stat[5])
            if ev_name is not None and op_name is not None:
                ops[ev_name] = op_name
    return out


def read_xplane(log_dir: str) -> Dict:
    """``trace_reduce.read_xplane``'s dict, plus ``"scopes"``
    (``{id: [stage or None, ...]}``, one per op of ``"devices"``) and
    ``"serve"`` (``[[name, start_ns, dur_ns, period], ...]``)."""
    import jax
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    names = op_names(paths[0])
    devices: Dict[str, List] = {}
    scopes: Dict[str, List] = {}
    host: List = []
    serve: List = []
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace_reduce.OPS_LINE:
                mine = names.get(m.group(1), {})
                for e in line.events:
                    devices.setdefault(m.group(1), []).append(
                        [trace_reduce.op_name(e.name), e.start_ns,
                         e.duration_ns])
                    scopes.setdefault(m.group(1), []).append(
                        stage_path(mine.get(e.name)))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name in trace_reduce.HOST_SPANS:
                        host.append([e.name, e.start_ns, e.duration_ns])
                    elif e.name.startswith(SERVE):
                        period = dict(e.stats).get("period")
                        serve.append([e.name, e.start_ns, e.duration_ns,
                                      None if period is None
                                      else int(period)])
    return {"devices": devices, "host": host, "scopes": scopes,
            "serve": serve}


def _overlap_label(g0, g1, spans) -> Optional[str]:
    best, label = 0, None
    for n, s, e in spans:
        ov = min(e, g1) - max(s, g0)
        if ov > best:
            best, label = ov, n
    return label


def reduce(trace: Dict, top: int = 10) -> Optional[Dict]:
    """``trace_reduce.reduce(trace)`` with, besides:

    ``stage_ns``     device time per chip of each stage path, the ops
                     clipped to the window (``{"collector/place": ns}``);
    ``unscoped_ns``  device time per chip of the ops in no stage scope;
    ``phase_ns``     per serving-loop phase, its spans' time in the window
                     over the periods they serve (``{"stage": ns}``);
    ``batch_wait_ns`` mean over the window's periods but its first (whose
                     batch has no step to wait for) of the time from the
                     end of a period's ``serve/stage`` to the start of the
                     ``serve/dispatch`` that consumes its batch;
    ``idle_gaps``    labelled by the ``serve/*`` span that overlaps each
                     gap most, else by the benchmark's span, else
                     ``none``.

    The new keys are empty (``batch_wait_ns`` None) on a trace without
    the program's scopes or spans. None where ``trace_reduce.reduce`` is.
    """
    red = trace_reduce.reduce(trace, top)
    if red is None:
        return None
    (window,) = [h for h in trace["host"] if h[0] == "window"]
    w0, w1 = window[1], window[1] + window[2]
    n_dev = red["n_devices"]
    scopes = trace.get("scopes", {})
    stage_ns: Dict[str, float] = {}
    unscoped = 0
    label = {}
    for dev, ops in trace["devices"].items():
        for (name, s, d), st in zip(ops, scopes.get(dev, [None] * len(ops))):
            ov = min(s + d, w1) - max(s, w0)
            if ov <= 0:
                continue
            if st is None:
                unscoped += ov
            else:
                stage_ns[st] = stage_ns.get(st, 0) + ov
                label.setdefault(name, st)
    if not stage_ns:
        unscoped = 0
    serve = [(n[len(SERVE):], s, s + d, p)
             for n, s, d, p in trace.get("serve", []) if w0 <= s < w1]
    phase_ns: Dict[str, float] = {}
    for phase in {p for p, _, _, _ in serve}:
        mine = [(s, e, k) for p, s, e, k in serve if p == phase]
        phase_ns[phase] = (sum(e - s for s, e, _ in mine)
                           / len({k for _, _, k in mine}))
    # the window's first batch has no step to wait for
    staged = {k: e for p, _, e, k in serve if p == "stage"}
    first = min(staged, default=None)
    waits = [s - staged[k] for p, s, _, k in serve
             if p == "dispatch" and k in staged and k != first]
    program = [(SERVE + p, s, e) for p, s, e, _ in serve]
    bench = [(n, s, s + d) for n, s, d in trace["host"] if n != "window"]
    gaps: Dict[str, float] = {}
    for ops in trace["devices"].values():
        iv = [[max(s, w0), min(s + d, w1)] for _, s, d in ops
              if min(s + d, w1) > max(s, w0)]
        merged = trace_reduce.union(iv)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                name = (_overlap_label(g0, g1, program)
                        or _overlap_label(g0, g1, bench) or "none")
                gaps[name] = gaps.get(name, 0) + (g1 - g0)
    red.update(
        stage_ns={k: v / n_dev for k, v in stage_ns.items()},
        unscoped_ns=unscoped / n_dev,
        phase_ns=phase_ns,
        batch_wait_ns=sum(waits) / len(waits) if waits else None,
        device_ops=[[f"{k} ({label[k]})" if k in label else k, v]
                    for k, v in red["device_ops"]],
        idle_gaps=sorted(([k, v / n_dev / 1e9] for k, v in gaps.items()),
                         key=lambda kv: -kv[1])[:top])
    return red


def stage_ns(red: Optional[Dict], *stages: str) -> Optional[float]:
    """Device time per chip in the given stages and their children;
    None where the trace holds no stage scope, or none of these."""
    if red is None or not red.get("stage_ns"):
        return None
    ns = sum(v for k, v in red["stage_ns"].items()
             if k.split("/")[0] in stages)
    return ns or None


def phase_ms(red: Optional[Dict], phase: str) -> Optional[float]:
    """A serving-loop phase's time per period, in ms; None where the
    trace holds none of its spans."""
    if red is None or phase not in red.get("phase_ns", {}):
        return None
    return red["phase_ns"][phase] / 1e6
