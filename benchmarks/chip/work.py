"""The least bytes each kernel of the main path has to move in a period,
from what the period did (the reference's counts), and the least time
they take at the chip's peak bandwidth.

The counts charge the work, not an implementation: a row read once and
written once, whatever the kernel copies besides. All three kernels do
integer or light float work per byte, so their bound is bandwidth; no
operation count is charged against the matrix units' peaks.

Per period, with the reference's counts (``work`` entries):

``ingest_update``  every valid event is read once (timestamp 4 B, size
                   4 B, five-tuple 20 B, validity 1 B, slot 4 B); every
                   table slot it touches has its seven 4 B registers and
                   its last timestamp read and written, its 20 B key read
                   and its 1 B active flag read.
``ring_scatter``   every placed payload is read (64 B) and written into
                   the ring (64 B) with its 1 B validity; every routed
                   report's coordinates are read (flow 4 B, history
                   index 4 B, mask 1 B).
``gather_enrich``  every routed report reads its flow's ring rows
                   (history x 64 B) and their validity (history x 1 B)
                   and its 4 B flow id, and writes ``derived_dim`` f32
                   features.
"""
from __future__ import annotations

from typing import Dict, List


def ingest_update_bytes(w: Dict, cfg: Dict) -> float:
    return w["events"] * (4 + 4 + 20 + 1 + 4) + w["slots_touched"] * (
        2 * 7 * 4 + 2 * 4 + 20 + 1)


def ring_scatter_bytes(w: Dict, cfg: Dict) -> float:
    return w["placed"] * (64 + 64 + 1) + w["reports"] * (4 + 4 + 1)


def gather_enrich_bytes(w: Dict, cfg: Dict) -> float:
    H = cfg["history"]
    return w["reports"] * (H * 64 + H + 4 + 4 * cfg["derived_dim"])


BYTES = {"ingest_update": ingest_update_bytes,
         "ring_scatter": ring_scatter_bytes,
         "gather_enrich": gather_enrich_bytes}


def least_seconds(kernel: str, work: List[Dict], cfg: Dict,
                  peaks: Dict) -> float:
    """Least time of ``kernel``'s work over the given periods."""
    return sum(BYTES[kernel](w, cfg) for w in work) / peaks[
        "hbm_bytes_per_s"]
