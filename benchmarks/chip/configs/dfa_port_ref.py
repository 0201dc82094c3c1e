"""Plain reference of one DFA port on one chip, in numpy.

The semantics of the paper's reporter, translator and collector (arXiv
2505.17573, §III-§IV, Table I, Figs 2 and 4) as this repository's
configuration states them, written out period by period with nothing of
the program imported: its own hash, log* tables, wire layout and feature
definitions. The benchmark runs it on the host after the measured window,
over the same event stream the window served, and compares.

One shard holds the whole flow space (``flow_home = "ingest"``), so flow
id = table slot and every report is homed where it was made.

Per period:

1. reporter ingest — FNV-1a slot of the five-tuple; first-come admission
   with stored-key collision counting (a colliding packet counts for the
   resident flow); inter-arrival time from the previous packet of the
   slot (0 for a flow's first packet); the seven Table-I sums, the
   powers through the log*/exp* tables, all mod 2^32;
2. due flows — active slots whose last report is a monitoring period or
   more behind ``now``, most overdue first, ties to the lower slot, at
   most ``report_capacity``;
3. reports — V2 wire: flow id, reporter id 0 | seq (a 16-bit field of the
   reporter's running count), the sums, the five-tuple;
4. translator — per-flow history counter mod ``history``, the 64 B
   payload with its rotate-xor checksum;
5. collector — checksum check, the §VI-B duplicate window (below),
   placement of the payload at (flow, history index), the loss count;
6. enrichment — the derived features of each reported flow from its ring
   rows, in float32.

The duplicate window follows the collector's rule as the program states
it: ``last_seq`` keeps the largest ``seq + 1`` seen per reporter, and a
seq up to ``seq_dup_window`` below it is a duplicate. Once a reporter's
16-bit seq wraps, ``last_seq`` stays at 65,536, so from the second lap on
the top 2,048 seqs of each lap count as duplicates, and the loss count
goes below zero mod 2^32. That is a fault of the rule, kept here on
purpose so that a run's verdict does not depend on how many periods fit
into its window; PERF.md lists it under Open questions.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

M32 = 0xFFFFFFFF
Q = 16                     # fractional bits of the log* values
EPS = 1e-6
PAD_FLOW_ID = 0xFFFFFFFF

# V2 wire (64 B payload): word 0 flow id, 1-7 stats, 8-12 five-tuple,
# 13 reporter(16) << 16 | seq(16), 14 checksum, 15 history index (8 bits)
SEQ_BITS = 16
N_REPORTERS = 1 << 16
HIST_MASK = 0xFF
CSUM_POSITIONS = tuple(range(14)) + (15,)


def u32(x) -> np.ndarray:
    return np.asarray(x, np.uint64) & M32


def fnv1a(five: np.ndarray) -> np.ndarray:
    h = np.full(five.shape[:-1], 0x811C9DC5, np.uint64)
    for i in range(5):
        h = ((h ^ five[..., i].astype(np.uint64)) * 0x01000193) & M32
    return h


def tables(bits: int):
    n = 1 << bits
    i = np.arange(n, dtype=np.float64)
    log_t = np.round((1 << Q) * np.log2(1.0 + i / n)).astype(np.int64)
    exp_t = np.round(n * (np.exp2(i / n) - 1.0)).astype(np.int64)
    return log_t, exp_t


def log_star(x: np.ndarray, bits: int, log_t) -> np.ndarray:
    """u32 -> Q16 log2 with a ``bits``-bit mantissa table; 0 -> 0."""
    x = x.astype(np.int64)
    nz = x > 0
    e = np.frexp(x.astype(np.float64))[1].astype(np.int64) - 1  # exact
    mask = (1 << bits) - 1
    frac = (x >> np.maximum(e - bits, 0)) & mask
    frac = (frac << np.maximum(bits - e, 0)) & mask
    return np.where(nz, (e << Q) + log_t[frac], 0)


def exp_star(l: np.ndarray, bits: int, exp_t) -> np.ndarray:
    """Q16 log2 -> u32 through the exp table, rounding on the way down,
    saturating at 2^32 - 1; 0 -> 1."""
    e = l >> Q
    frac = (l >> (Q - bits)) & ((1 << bits) - 1)
    mant = (1 << bits) + exp_t[frac]
    sh = np.clip(e - bits, -(bits + 32), 31)
    down = np.clip(-sh, 1, 31)
    val = np.where(sh >= 0, (mant << np.clip(sh, 0, 31)) & M32,
                   (mant + (np.int64(1) << (down - 1))) >> down)
    val = np.where(e >= 32, M32, val)
    return np.where(l == 0, 1, val)


def approx_pow(x: np.ndarray, n: int, bits: int, luts) -> np.ndarray:
    lx = log_star(x, bits, luts[0])
    ln = lx * n
    v = exp_star(ln, bits, luts[1])
    v = np.where((ln >> Q) >= 32, M32, v)
    return np.where(x == 0, 0, v)


def checksum(words: np.ndarray) -> np.ndarray:
    """Rotate each covered word left by its position, xor them."""
    c = np.zeros(words.shape[:-1], np.uint64)
    for p in CSUM_POSITIONS:
        w = words[..., p].astype(np.uint64)
        c ^= ((w << p) | (w >> ((32 - p) % 32))) & M32 if p else w
    return c


def derive(rows: np.ndarray, valid: np.ndarray, derived_dim: int,
           rnd=None) -> np.ndarray:
    """(R, H, 16) ring rows + (R, H) validity -> (R, derived_dim) f32.

    Per history entry, from its seven sums: count, IAT and size mean,
    variance, std, coefficient of variation and skew, volume, rate,
    packets per second, duration and three log1p terms (18). Per flow:
    the newest entry's 18 (newest = most packets), the window's mean and
    std of each, newest minus mean, the valid entry count and the
    largest history index; zero padded. ``rnd`` rounds after every
    operation (the lower-precision control); None keeps float32."""
    r = rnd or (lambda a: a)
    f32 = np.float32
    s = [r(rows[..., 1 + k].astype(f32)) for k in range(7)]
    n = np.maximum(s[0], f32(1.0))
    eps = f32(EPS)

    def moments(s1, s2, s3):
        mean = r(s1 / n)
        var = np.maximum(r(r(s2 / n) - r(mean * mean)), f32(0.0))
        std = r(np.sqrt(var))
        cov = r(std / np.maximum(mean, eps))
        m3 = r(r(r(s3 / n) - r(r(f32(3) * mean) * var))
               - r(r(mean * mean) * mean))
        skew = r(m3 / np.maximum(r(r(std * std) * std), eps))
        return [mean, var, std, cov, skew]

    duration = np.maximum(s[1], f32(1.0))
    volume = s[4]
    secs = r(r(duration / f32(1e6)) + eps)
    rate = r(r(volume * f32(8.0)) / secs)
    pps = r(n / secs)
    ent = np.stack([n, *moments(s[1], s[2], s[3]),
                    *moments(s[4], s[5], s[6]), volume, rate, pps,
                    duration, r(np.log1p(volume)), r(np.log1p(rate)),
                    r(np.log1p(n))], axis=-1)          # (R, H, 18)
    vm = valid.astype(f32)[..., None]
    ent = ent * vm
    nvalid = np.maximum(valid.sum(-1, keepdims=True), 1).astype(f32)
    count = np.where(valid, rows[..., 1], 0)
    newest = np.argmax(count, axis=-1)
    newest_f = np.take_along_axis(ent, newest[:, None, None], axis=1)[:, 0]
    mean_w = r(ent.sum(1, dtype=f32) / nvalid)
    dev = (ent - mean_w[:, None, :]) * vm
    std_w = r(np.sqrt(r(r(dev * dev).sum(1, dtype=f32) / nvalid)))
    delta = r(newest_f - mean_w)
    hist = (rows[..., 15] & HIST_MASK).astype(f32)
    maxhist = np.max(np.where(valid, hist, f32(0.0)), axis=-1,
                     keepdims=True)
    out = np.concatenate([newest_f, mean_w, std_w, delta, nvalid, maxhist],
                         axis=-1).astype(f32)
    pad = derived_dim - out.shape[-1]
    if pad > 0:
        out = np.pad(out, ((0, 0), (0, pad)))
    return out[:, :derived_dim]


# error bound per float32 operation, relative to its result: 4 units of
# the last place for + - * / sqrt (a chip's division and square root may
# be off by more than half a unit); 2^-12 for log1p, whose TPU v5 lite
# evaluation was measured off by 5.7e-5 (log1p(2) = 1.0986746549606323
# against 1.0986122886681096)
U = 4.0 * 2.0 ** -24
U_LOG = 2.0 ** -12


def _add(a, b, sign=1.0):
    v = a[0] + sign * b[0]
    return v, a[1] + b[1] + U * np.abs(v)


def _mul(a, b):
    v = a[0] * b[0]
    with np.errstate(invalid="ignore"):
        e = (np.abs(a[0]) * b[1] + np.abs(b[0]) * a[1] + a[1] * b[1]
             + U * np.abs(v))
    return v, np.where(np.isnan(e), np.inf, e)


def _div(a, b):
    """``b`` is positive (a max with 1 or EPS came before)."""
    v = a[0] / b[0]
    lo = b[0] - b[1]
    with np.errstate(divide="ignore", invalid="ignore"):
        e = np.where(lo > 0, (a[1] + np.abs(v) * b[1]) / lo, np.inf)
    return v, e + U * np.abs(v)


def _max(a, c):
    return np.maximum(a[0], c), a[1]


def _sqrt(a):
    v = np.sqrt(np.maximum(a[0], 0.0))
    with np.errstate(divide="ignore", invalid="ignore"):
        e = np.minimum(np.sqrt(a[1]), np.where(v > 0, a[1] / v, np.inf))
    return v, e + U * v


def _log1p(a):
    v = np.log1p(a[0])
    lo = 1.0 + a[0] - a[1]
    with np.errstate(divide="ignore", invalid="ignore"):
        e = np.where(lo > 0, a[1] / lo, np.inf)
    return v, e + U_LOG * np.abs(v)


def _sum(a, axis):
    """Sum in any order: the terms' bounds plus (n - 1) roundings of at
    most the sum of magnitudes."""
    n = a[0].shape[axis]
    return (a[0].sum(axis), a[1].sum(axis)
            + (n - 1) * U * np.abs(a[0]).sum(axis))


def derive_bound(rows: np.ndarray, valid: np.ndarray, derived_dim: int):
    """:func:`derive` in float64 with a bound on how far any float32
    evaluation of the same formulas may lie from it: ``(value, bound)``,
    each (R, derived_dim). Inputs are the float32 conversions of the ring
    words, as in the program, so an exact feature has bound 0."""
    s = [rows[..., 1 + k].astype(np.float32).astype(np.float64)
         for k in range(7)]
    s = [(x, np.zeros_like(x)) for x in s]
    n = _max(s[0], 1.0)
    eps = (np.float64(np.float32(EPS)), 0.0)

    def moments(s1, s2, s3):
        mean = _div(s1, n)
        var = _max(_add(_div(s2, n), _mul(mean, mean), -1.0), 0.0)
        std = _sqrt(var)
        cov = _div(std, _max(mean, eps[0]))
        m3 = _add(_add(_div(s3, n), _mul(_mul((3.0, 0.0), mean), var),
                       -1.0), _mul(_mul(mean, mean), mean), -1.0)
        skew = _div(m3, _max(_mul(_mul(std, std), std), eps[0]))
        return [mean, var, std, cov, skew]

    duration = _max(s[1], 1.0)
    volume = s[4]
    secs = _add(_div(duration, (1e6, 0.0)), eps)
    rate = _div(_mul(volume, (8.0, 0.0)), secs)
    pps = _div(n, secs)
    ent = [n, *moments(s[1], s[2], s[3]), *moments(s[4], s[5], s[6]),
           volume, rate, pps, duration, _log1p(volume), _log1p(rate),
           _log1p(n)]
    vm = valid[..., None]
    ent = tuple(np.where(vm, np.stack([np.broadcast_to(x[i], valid.shape)
                                       for x in ent], -1), 0.0)
                for i in (0, 1))
    nvalid = np.maximum(valid.sum(-1, keepdims=True), 1).astype(np.float64)
    nv = (nvalid, np.zeros_like(nvalid))
    count = np.where(valid, rows[..., 1], 0)
    newest = np.argmax(count, axis=-1)[:, None, None]
    newest_f = tuple(np.take_along_axis(x, newest, axis=1)[:, 0]
                     for x in ent)
    mean_w = _div(_sum(ent, 1), nv)
    dev = _add(ent, (mean_w[0][:, None], mean_w[1][:, None]), -1.0)
    dev = (np.where(vm, dev[0], 0.0), np.where(vm, dev[1], 0.0))
    std_w = _sqrt(_div(_sum(_mul(dev, dev), 1), nv))
    delta = _add(newest_f, mean_w, -1.0)
    hist = (rows[..., 15] & HIST_MASK).astype(np.float64)
    maxhist = np.max(np.where(valid, hist, 0.0), axis=-1, keepdims=True)
    zero = np.zeros_like(nvalid)
    parts = [newest_f, mean_w, std_w, delta, (nvalid, zero),
             (maxhist, zero)]
    v = np.concatenate([p[0] for p in parts], axis=-1)
    e = np.concatenate([p[1] for p in parts], axis=-1)
    pad = derived_dim - v.shape[-1]
    if pad > 0:
        v, e = np.pad(v, ((0, 0), (0, pad))), np.pad(e, ((0, 0), (0, pad)))
    return v[:, :derived_dim], e[:, :derived_dim]


class Reference:
    """One port's reporter, translator, collector and enrichment."""

    def __init__(self, dfa: Dict):
        self.F = F = dfa["flows_per_shard"]
        self.H = H = dfa["history"]
        self.R = dfa["report_capacity"]
        self.period_us = dfa["monitoring_period_us"]
        self.bits = dfa["logstar_bits"]
        self.derived_dim = dfa["derived_dim"]
        self.luts = tables(self.bits)
        self.dup_window = 1 << (SEQ_BITS - 5)
        self.regs = np.zeros((F, 7), np.uint64)
        self.last_ts = np.zeros(F, np.uint64)
        self.last_report = np.zeros(F, np.uint64)
        self.keys = np.zeros((F, 5), np.uint32)
        self.active = np.zeros(F, bool)
        self.seq = 0
        self.collisions = 0
        self.hist_counter = np.zeros(F, np.int64)
        self.memory = np.zeros((F, H, 16), np.uint32)
        self.entry_valid = np.zeros((F, H), bool)
        self.last_seq = np.zeros(N_REPORTERS, np.int64)
        self.bad_checksum = 0
        self.seq_anomalies = 0
        self.received = 0
        self.lost_reports = 0
        self.work = []            # per period: what the layers had to do

    # -- 1. reporter ingest ----------------------------------------------
    def ingest(self, ts, size, five, valid):
        F = self.F
        v = np.flatnonzero(valid)
        ts, size, five = u32(ts[v]), u32(size[v]), five[v]
        slot = (fnv1a(five) & (F - 1)).astype(np.int64)
        pre_active = self.active.copy()
        empty = ~pre_active[slot]
        match = (self.keys[slot] == five).all(1) & ~empty
        inst = np.flatnonzero(empty)
        uniq, first = np.unique(slot[inst], return_index=True)
        winner = np.zeros(len(v), bool)
        winner[inst[first]] = True
        self.keys[uniq] = five[inst[first]]
        self.active[uniq] = True
        same = (self.keys[slot] == five).all(1)
        collide = (~empty & ~match) | (empty & ~winner & ~same)
        self.collisions = (self.collisions + int(collide.sum())) & M32

        order = np.argsort(slot, kind="stable")
        ss, st, ps = slot[order], ts[order], size[order]
        prev_same = np.concatenate([[False], ss[1:] == ss[:-1]])
        prev_ts = np.where(prev_same,
                           np.concatenate([np.zeros(1, np.uint64), st[:-1]]),
                           self.last_ts[ss])
        first_pkt = ~prev_same & ~pre_active[ss]
        iat = np.where(first_pkt, 0, (st - prev_ts) & M32).astype(np.int64)
        ps = ps.astype(np.int64)
        cols = [np.ones_like(ps), iat,
                approx_pow(iat, 2, self.bits, self.luts),
                approx_pow(iat, 3, self.bits, self.luts), ps,
                approx_pow(ps, 2, self.bits, self.luts),
                approx_pow(ps, 3, self.bits, self.luts)]
        heads = np.flatnonzero(~prev_same)
        tails = np.concatenate([heads[1:] - 1, [len(ss) - 1]])
        sums = np.stack([np.add.reduceat(c.astype(np.uint64), heads)
                         for c in cols], axis=-1) if len(ss) else \
            np.zeros((0, 7), np.uint64)
        touched = ss[heads]
        self.regs[touched] = (self.regs[touched] + sums) & M32
        self.last_ts[ss[tails]] = st[tails]
        return len(v), len(touched)

    # -- 2./3. due flows and reports -------------------------------------
    def reports(self, now):
        F, R = self.F, self.R
        elapsed = (now - self.last_report) & M32
        due = self.active & (elapsed >= self.period_us)
        score = np.where(due, elapsed, 0).astype(np.uint64)
        k = min(R, F)
        # most overdue first, ties to the lower slot
        key = (score << 20) | (np.uint64((1 << 20) - 1)
                               - np.arange(F, dtype=np.uint64))
        top = np.argpartition(key, F - k)[F - k:]
        top = top[np.argsort(key[top])[::-1]]
        mask = due[top]
        slots = top[mask]                 # valid rows lead: score > 0
        n = len(slots)
        seqs = (self.seq + np.arange(n)) & ((1 << SEQ_BITS) - 1)
        self.last_report[slots] = now
        self.seq = (self.seq + n) & M32
        return slots, seqs

    # -- 4. translator ---------------------------------------------------
    def payloads(self, slots, seqs):
        H = self.H
        hist = (self.hist_counter[slots] & HIST_MASK) % H
        self.hist_counter[slots] = ((self.hist_counter[slots] + 1)
                                    & HIST_MASK) % H
        p = np.zeros((len(slots), 16), np.uint64)
        p[:, 0] = slots
        p[:, 1:8] = self.regs[slots]
        p[:, 8:13] = self.keys[slots]
        p[:, 13] = seqs                  # reporter id 0 in the top half
        p[:, 15] = hist
        p[:, 14] = checksum(p)
        return p.astype(np.uint32), hist

    # -- 5. collector ----------------------------------------------------
    def collect(self, pay):
        ok = checksum(pay) == pay[:, 14]
        self.bad_checksum = (self.bad_checksum + int((~ok).sum())) & M32
        flow = pay[:, 0].astype(np.int64)
        rep = (pay[:, 13] >> 16).astype(np.int64)
        seq = (pay[:, 13] & 0xFFFF).astype(np.int64)
        hist = (pay[:, 15] & HIST_MASK).astype(np.int64)
        mask = ok & (flow < self.F)
        prev = self.last_seq[rep]
        prev_seq = (prev - 1) & 0xFFFF
        dup = mask & (prev > 0) & (seq <= prev_seq) & (
            prev_seq - seq < self.dup_window)
        ident = rep * (1 << SEQ_BITS) + seq
        seen = np.zeros(len(pay), bool)
        _, first = np.unique(np.where(mask, ident, -1 - np.arange(len(pay))),
                             return_index=True)
        seen[first] = True
        dup |= mask & ~seen
        place = mask & ~dup
        self.memory[flow[place], hist[place]] = pay[place]
        self.entry_valid[flow[place], hist[place]] = True
        self.seq_anomalies = (self.seq_anomalies + int(dup.sum())) & M32
        old = int(self.last_seq.sum())
        np.maximum.at(self.last_seq, rep[place], seq[place] + 1)
        lost = (int(self.last_seq.sum()) - old - int(place.sum())) & M32
        self.received = (self.received + int(place.sum())) & M32
        self.lost_reports = (self.lost_reports + lost) & M32
        return int(place.sum()), int(dup.sum()), lost

    # -- one period ------------------------------------------------------
    def step(self, ts, size, five, valid, now: int, enrich: bool = True,
             rnd=None) -> Dict:
        """One period; with ``enrich`` also the features (float32, and
        float64 with their bound), and with ``rnd`` the features computed
        rounding after every operation (the control)."""
        coll0, bad0 = self.collisions, self.bad_checksum
        n_events, n_touched = self.ingest(ts, size, five, valid)
        slots, seqs = self.reports(int(now))
        pay, _ = self.payloads(slots, seqs)
        placed, dups, lost = self.collect(pay)
        n = len(slots)
        R = self.R
        flow_ids = np.full(R, PAD_FLOW_ID, np.uint32)
        flow_ids[:n] = slots
        mask = np.zeros(R, bool)
        mask[:n] = True
        out = {"flow_ids": flow_ids, "mask": mask, "metrics": {
            "reports_sent": n, "reports_recv": n, "bucket_drops": 0,
            "misroutes": 0,
            "collisions": (self.collisions - coll0) & M32,
            "bad_checksum": (self.bad_checksum - bad0) & M32,
            "seq_anomalies": dups, "lost_reports": lost}}
        if enrich:
            # the program gathers pad rows at flow 0 and zeroes them
            lf = np.zeros(R, np.int64)
            lf[:n] = slots
            rows, ev = self.memory[lf], self.entry_valid[lf]
            out["enriched"] = derive(rows, ev, self.derived_dim)
            out["exact"], out["bound"] = derive_bound(rows, ev,
                                                      self.derived_dim)
            if rnd is not None:
                out["control"] = derive(rows, ev, self.derived_dim, rnd)
            for k in ("enriched", "exact", "bound", "control"):
                if k in out:
                    out[k][n:] = 0.0
        self.work.append({"events": n_events, "slots_touched": n_touched,
                          "reports": n, "placed": placed})
        return out

    def state(self) -> Dict[str, np.ndarray]:
        """End state under the program's leaf names."""
        return {
            "reporter.regs": self.regs, "reporter.last_ts": self.last_ts,
            "reporter.last_report": self.last_report,
            "reporter.keys": self.keys, "reporter.active": self.active,
            "reporter.seq": np.asarray([self.seq]),
            "reporter.collisions": np.asarray([self.collisions]),
            "translator.hist_counter": self.hist_counter,
            "collector.memory": self.memory,
            "collector.entry_valid": self.entry_valid,
            "collector.last_seq": self.last_seq,
            "collector.bad_checksum": np.asarray([self.bad_checksum]),
            "collector.seq_anomalies": np.asarray([self.seq_anomalies]),
            "collector.received": np.asarray([self.received]),
            "collector.lost_reports": np.asarray([self.lost_reports]),
        }


class Replay:
    """The serving loop's event source as the configuration states it.

    The stream of valid events cycles. Each period offers ``per_period``
    events (0: one full batch, line rate); with no host queue, what does
    not fit the batch is dropped, newest first. The batch is padded with
    invalid rows, its timestamps are spread evenly over the period in
    arrival order, and ``now`` is the period's end."""

    def __init__(self, events: Dict, batch: int, per_period: int,
                 budget_us: int):
        valid = np.asarray(events["valid"]).reshape(-1)
        self.five = np.asarray(events["five_tuple"]).reshape(-1, 5)[valid]
        self.size = np.asarray(events["size"]).reshape(-1)[valid]
        self.N, self.per, self.budget = batch, per_period, budget_us
        self.eps = per_period * 1e6 / budget_us
        self.cursor, self.acc, self.period = 0, 0.0, 0
        self.offered = self.processed = self.dropped = 0

    def next_batch(self):
        N, L = self.N, len(self.size)
        if self.per == 0:
            n = N
        else:
            self.acc += self.eps * self.budget / 1e6
            n = int(self.acc)
            self.acc -= n
        idx = (self.cursor + np.arange(n)) % L
        self.cursor = (self.cursor + n) % L
        kept = idx[:N]
        k = len(kept)
        five = np.zeros((N, 5), np.uint32)
        size = np.zeros(N, np.uint32)
        valid = np.zeros(N, bool)
        five[:k], size[:k], valid[:k] = self.five[kept], self.size[kept], True
        t0 = (self.period * self.budget) & M32
        ts = ((t0 + (np.arange(N, dtype=np.uint64) * self.budget) // N)
              & M32).astype(np.uint32)
        self.period += 1
        now = (self.period * self.budget) & M32
        self.offered += n
        self.processed += k
        self.dropped += n - k
        return {"ts": ts, "size": size, "five_tuple": five,
                "valid": valid}, now
