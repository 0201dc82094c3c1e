"""The single registry of every ``REPRO_*`` environment override.

Before this module existed, each env var was parsed at its point of use
with its own ad-hoc semantics: the kernel dispatch layer validated its
three choice vars fail-loud, while ``REPRO_BENCH_TINY`` treated any
string but ``""``/``"0"`` as true (so ``REPRO_BENCH_TINY=false`` meant
*tiny*) and ``REPRO_REGEN_GOLDENS`` accepted anything truthy. Now every
override is declared here once, with one parsing rule per kind and one
fail-loud contract: a malformed value raises ``ValueError`` naming the
variable and what it accepts — it is never silently ignored, because a
typo'd override that loses quietly is indistinguishable from one that
worked.

Kinds:

``choice``
    One of a fixed set of strings. Unset, ``""`` and ``"auto"`` all mean
    "defer to the next stage of the precedence ladder" (see
    ``repro.kernels.dispatch``); anything else must be a registered
    choice.
``flag``
    Boolean. Unset/``""``/``"0"``/``"false"``/``"no"``/``"off"`` are
    false; ``"1"``/``"true"``/``"yes"``/``"on"`` are true (case
    insensitive). Anything else raises.
``str``
    A free-form string (a filesystem path, typically). Unset/``""`` ->
    None; the raw value otherwise — NOT lowercased, paths are
    case-sensitive. Validation of the content (does the file exist,
    does it parse) belongs to the consumer, which must still fail loud.

The full table (also rendered by :func:`env_table` for docs):

=======================  ======  =================  =========================
variable                 kind    values             consumed by
=======================  ======  =================  =========================
REPRO_KERNEL_BACKEND     choice  ref|pallas|        kernels.dispatch backend
                                 interpret          precedence (beats
                                                    DFAConfig.kernel_backend,
                                                    loses to explicit
                                                    ``backend=``)
REPRO_GATHER_VARIANT     choice  full|hbm           gather_enrich memory
                                                    strategy
REPRO_INGEST_VARIANT     choice  block|hbm          ingest_update event-
                                                    stream strategy
REPRO_BENCH_TINY         flag                       benchmarks/: shrink
                                                    problem sizes + iters
                                                    (set by run.py --tiny)
REPRO_REGEN_GOLDENS      flag                       tests/test_run_periods_
                                                    golden.py: refresh all
                                                    committed fingerprints
REPRO_WIRE_FORMAT        choice  v1|v2              core.wire active wire
                                                    schema (beats
                                                    DFAConfig.wire_format)
REPRO_TUNING_REGISTRY    str     path               kernels.tuning tuned-
                                                    config registry JSON
                                                    (beats DFAConfig.
                                                    tuning_registry)
=======================  ======  =================  =========================
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("", "0", "false", "no", "off")


@dataclass(frozen=True)
class EnvSpec:
    """One registered override: its name, kind, and legal values."""

    name: str
    kind: str                         # "choice" | "flag" | "str"
    choices: Tuple[str, ...] = ()     # kind == "choice" only
    description: str = ""
    consumer: str = ""                # module that reads it

    def __post_init__(self):
        if self.kind not in ("choice", "flag", "str"):
            raise ValueError(f"unknown env kind {self.kind!r}")
        if self.kind == "choice" and not self.choices:
            raise ValueError(f"{self.name}: choice spec needs choices")


_REGISTRY: Dict[str, EnvSpec] = {}


def register(spec: EnvSpec) -> EnvSpec:
    """Register (or re-register, for tests) one override."""
    _REGISTRY[spec.name] = spec
    return spec


def registered() -> Dict[str, EnvSpec]:
    return dict(_REGISTRY)


def spec(name: str) -> EnvSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unregistered env override {name!r}; registered: "
            f"{sorted(_REGISTRY)} (declare it in repro.configs.env)")
    return _REGISTRY[name]


def read_choice(name: str) -> Optional[str]:
    """The validated value of a choice var, or ``None`` when it defers.

    Unset / ``""`` / ``"auto"`` -> None (the precedence ladder moves on);
    a registered choice -> that choice; anything else raises listing the
    registered values — even when a stronger setting (an explicit
    ``backend=`` argument) would win, so a typo can never lose silently.
    """
    s = spec(name)
    if s.kind != "choice":
        raise ValueError(f"{name} is a {s.kind} var, not a choice")
    raw = os.environ.get(name, "").strip().lower()
    if raw in ("", "auto"):
        return None
    if raw not in s.choices:
        raise ValueError(
            f"unknown value {raw!r} from env var {name}; registered: "
            f"{list(s.choices)} (or 'auto')")
    return raw


def read_flag(name: str) -> bool:
    """The validated value of a flag var (unset -> False; junk raises)."""
    s = spec(name)
    if s.kind != "flag":
        raise ValueError(f"{name} is a {s.kind} var, not a flag")
    raw = os.environ.get(name, "").strip().lower()
    if raw in _FALSE:
        return False
    if raw in _TRUE:
        return True
    raise ValueError(
        f"unknown value {raw!r} from env var {name}; a flag accepts "
        f"{list(_TRUE)} / {list(_FALSE)}")


def read_str(name: str) -> Optional[str]:
    """The raw value of a string var, or ``None`` when unset/empty.

    No lowercasing (paths are case-sensitive) and no content validation
    here — the consumer validates what the string points at, fail-loud.
    """
    s = spec(name)
    if s.kind != "str":
        raise ValueError(f"{name} is a {s.kind} var, not a str")
    raw = os.environ.get(name, "").strip()
    return raw or None


def env_table() -> str:
    """Markdown table of every registered override (for README/docs)."""
    lines = ["| variable | kind | values | consumed by |",
             "|---|---|---|---|"]
    for name in sorted(_REGISTRY):
        s = _REGISTRY[name]
        vals = ("\\|".join(s.choices) if s.kind == "choice"
                else "0/1" if s.kind == "flag" else "path")
        lines.append(f"| `{name}` | {s.kind} | {vals} | {s.consumer}: "
                     f"{s.description} |")
    return "\n".join(lines)


# -- the in-tree overrides ---------------------------------------------------

KERNEL_BACKEND = register(EnvSpec(
    "REPRO_KERNEL_BACKEND", "choice", ("ref", "pallas", "interpret"),
    description="kernel backend (beats DFAConfig.kernel_backend, loses "
                "to an explicit backend= argument)",
    consumer="repro.kernels.dispatch"))

GATHER_VARIANT = register(EnvSpec(
    "REPRO_GATHER_VARIANT", "choice", ("full", "hbm"),
    description="gather_enrich memory strategy (ring pinned in VMEM vs "
                "HBM-resident ring, routed rows gathered by XLA)",
    consumer="repro.kernels.dispatch"))

INGEST_VARIANT = register(EnvSpec(
    "REPRO_INGEST_VARIANT", "choice", ("block", "hbm"),
    description="ingest_update event-stream strategy (BlockSpec-tiled "
                "VMEM vs HBM-resident double-buffered DMA)",
    consumer="repro.kernels.dispatch"))

BENCH_TINY = register(EnvSpec(
    "REPRO_BENCH_TINY", "flag",
    description="bench-smoke mode: tiny problem sizes, 2 timed iters "
                "(set by benchmarks/run.py --tiny)",
    consumer="benchmarks.common"))

REGEN_GOLDENS = register(EnvSpec(
    "REPRO_REGEN_GOLDENS", "flag",
    description="refresh every committed golden fingerprint in one run",
    consumer="tests.test_run_periods_golden"))

TUNING_REGISTRY = register(EnvSpec(
    "REPRO_TUNING_REGISTRY", "str",
    description="path to a tuned-config registry JSON "
                "(kernels.tuning; produced by the *_scaling.py sweeps' "
                "--tune flag; beats DFAConfig.tuning_registry)",
    consumer="repro.kernels.tuning"))

WIRE_FORMAT = register(EnvSpec(
    "REPRO_WIRE_FORMAT", "choice", ("v1", "v2"),
    description="active wire schema (v1 = the paper's 8-bit "
                "reporter_id/seq layout, v2 = the widened u16 layout; "
                "beats DFAConfig.wire_format)",
    consumer="repro.core.wire"))
