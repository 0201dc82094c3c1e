"""Unified kernel backend registry — the dispatch layer for the DFA hot path.

Every kernel family registers up to three implementations:

* ``ref``       — pure-jnp oracle (portable; bit-exact semantics contract)
* ``pallas``    — compiled Pallas TPU kernel
* ``interpret`` — the same Pallas kernel run by the Pallas interpreter
                  (works on CPU; CI uses it for equivalence vs ``ref``)

Families shipped here: ``flow_moments`` (reporter accumulate),
``ring_scatter`` (collector placement), ``derived_features`` (enrichment),
``gather_enrich`` (fused history-gather + enrichment) and
``flash_attention`` (model serving path).

``pallas`` runs only on a TPU: requesting it anywhere else raises (see
:func:`interpret_flag`); ``interpret`` is the CPU route.

Backend selection precedence (strongest first):

1. an explicit ``backend=`` argument at the call site (``"auto"`` defers)
2. the ``REPRO_KERNEL_BACKEND`` environment variable
3. ``DFAConfig.kernel_backend``
4. auto: ``pallas`` on TPU, ``ref`` everywhere else

An unrecognized value raises ValueError listing the registered backends no
matter where it sits in the precedence chain — a typo'd env var must fail
loudly even at call sites that pass an explicit ``backend=``, not silently
lose to the stronger setting.

``gather_enrich`` additionally carries a memory-strategy *variant*: the
``full`` kernel pins the shard's whole (F, H, 16) ring region in VMEM,
the ``hbm`` kernel keeps it HBM-resident and reads only the routed rows.
``resolve_gather_variant`` picks one by a VMEM-budget heuristic (full
while the ring region fits, hbm beyond), overridable via
``DFAConfig.gather_variant`` or ``REPRO_GATHER_VARIANT``. Fitting is not
speed: no chip run has yet compared the two variants.

``ingest_update`` (reporter-side fused sort-once / segment-reduce ingest)
mirrors that scheme on the *event* axis: the ``block`` kernel streams the
sorted event arrays through BlockSpec-tiled VMEM blocks, the ``hbm``
kernel keeps them HBM-resident (``pl.ANY``) and double-buffers DMA
slices of 8 ``event_tile`` rows with scalar-prefetched run-boundary
metadata, so events_per_shard can grow to 2^20 with VMEM = O(event_tile).
``resolve_ingest_variant`` picks block while the whole sorted stream fits
the VMEM budget, overridable via ``DFAConfig.ingest_variant`` or
``REPRO_INGEST_VARIANT``.

Both variant resolvers — and the ``resolve_event_tile`` /
``resolve_report_tile`` helpers the ops wrappers call — consult the
measurement-driven tuned-config registry (``repro.kernels.tuning``,
armed via ``REPRO_TUNING_REGISTRY`` / ``DFAConfig.tuning_registry``)
INSIDE their heuristic tier: a sweep-measured winner for the exact
(shape, backend) beats the VMEM model, while any explicit setting
(argument, env var, non-"auto" config attr) still beats the measurement.

Resolution happens at trace time: a step traced under one setting keeps it
until re-traced (jit caches are keyed on shapes, not on this env var).
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.configs import env as ENV

BACKENDS = ENV.KERNEL_BACKEND.choices
ENV_VAR = ENV.KERNEL_BACKEND.name

GATHER_VARIANTS = ENV.GATHER_VARIANT.choices
GATHER_ENV_VAR = ENV.GATHER_VARIANT.name
INGEST_VARIANTS = ENV.INGEST_VARIANT.choices
INGEST_ENV_VAR = ENV.INGEST_VARIANT.name
WORDS = 16               # collector entry words (64 B RoCEv2 payload)
EVENT_WORDS = 5          # sorted-event-stream words: slot/ts/ps/base_ts/first
VMEM_BYTES_PER_MB = 1 << 20
SUBLANES, LANES = 8, 128  # Mosaic's 32-bit VMEM tile

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
_BUILTIN_LOADED = False


def register(family: str, backend: str, fn: Optional[Callable] = None):
    """Register ``fn`` as ``family``'s ``backend`` implementation.

    Usable directly (``register("fam", "ref", impl)``) or as a decorator
    (``@register("fam", "ref")``). Re-registration overwrites.
    """
    if backend not in BACKENDS:
        raise ValueError(f"backend {backend!r} not in {BACKENDS}")

    def _set(f: Callable) -> Callable:
        _REGISTRY.setdefault(family, {})[backend] = f
        return f

    return _set(fn) if fn is not None else _set


def families() -> List[str]:
    _ensure_builtin()
    return sorted(_REGISTRY)


def implementations(family: str) -> List[str]:
    _ensure_builtin()
    return sorted(_REGISTRY.get(family, {}))


def negotiate_tile(size: int, preferred: int) -> int:
    """Largest tile <= ``preferred`` that divides ``size`` exactly (>= 1).

    Every Pallas family tiles its leading (flow/report) dimension; this is
    the single negotiation rule all ops.py wrappers share.
    """
    size, preferred = int(size), int(preferred)
    t = max(1, min(preferred, size))
    while size % t:
        t -= 1
    return t


def _check_choice(value: str, valid: Tuple[str, ...], source: str) -> None:
    if value not in valid:
        raise ValueError(
            f"unknown value {value!r} from {source}; registered: "
            f"{list(valid)} (or 'auto')")


def _resolve_choice(explicit: Optional[str], cfg, *, env_var: str,
                    choices: Tuple[str, ...], cfg_attr: str, heuristic,
                    arg_source: str) -> str:
    """The one selection-precedence ladder every knob shares: explicit
    argument > ``env_var`` > ``DFAConfig.<cfg_attr>`` > ``heuristic()``.

    The env var is read through the ``repro.configs.env`` registry, so a
    malformed value raises even when a stronger setting (explicit
    argument) would win: a typo'd env var silently losing the precedence
    fight is indistinguishable from it working.
    """
    env = ENV.read_choice(env_var)       # fail-loud registry validation
    if explicit in (None, "auto", ""):
        cfg_value = (getattr(cfg, cfg_attr, "auto")
                     if cfg is not None else "auto") or "auto"
        if env is not None:
            explicit = env
        elif cfg_value != "auto":
            _check_choice(cfg_value, choices, f"DFAConfig.{cfg_attr}")
            explicit = cfg_value
        else:
            explicit = heuristic()
    _check_choice(explicit, choices, arg_source)
    return explicit


def resolve_backend(backend: Optional[str] = None, cfg=None) -> str:
    """Apply the selection precedence; returns one of BACKENDS (auto:
    ``pallas`` on TPU, ``ref`` everywhere else)."""
    return _resolve_choice(
        backend, cfg, env_var=ENV_VAR, choices=BACKENDS,
        cfg_attr="kernel_backend",
        heuristic=lambda: ("pallas" if jax.default_backend() == "tpu"
                           else "ref"),
        arg_source="backend= argument")


# -- measurement-driven tuned-config registry -------------------------------

def _tuned_value(cfg, knob: str, key):
    """Consult the tuned-config registry (kernels.tuning), keyed by the
    RESOLVED backend — a winner measured under the interpreter says
    nothing about compiled pallas. Returns None when no registry is
    armed or no exact (knob, backend, key) measurement exists, letting
    the VMEM heuristic decide. Sits INSIDE the heuristic tier, so an
    explicit argument, env var or explicit DFAConfig attr still wins."""
    from repro.kernels import tuning  # lazy: dispatch stays import-light
    if tuning.resolve_path(cfg) is None:
        return None
    return tuning.lookup_value(cfg, knob, resolve_backend(None, cfg), key)


def _tuned_tile(cfg, knob: str, key, fallback: int) -> int:
    tuned = _tuned_value(cfg, knob, key)
    if tuned is None:
        return int(fallback)
    t = int(tuned)
    if t < 1:
        raise ValueError(
            f"tuned {knob} for key {tuple(key)} is {t}; tiles must be "
            ">= 1 — the registry file is corrupt")
    return t


def resolve_event_tile(cfg, events: int) -> int:
    """The ingest_update event tile: a tuned measurement for this event
    count beats the static ``DFAConfig.event_tile`` default (arming a
    registry is an explicit opt-in). Kernel-bound clamping stays with
    the caller (``clamp_tile``)."""
    return _tuned_tile(cfg, "ingest_update.event_tile", (int(events),),
                       int(getattr(cfg, "event_tile", 256)))


def resolve_report_tile(cfg, reports: int) -> int:
    """The gather_enrich report tile: a tuned measurement for this
    report count beats the static ``DFAConfig.flow_tile`` default."""
    return _tuned_tile(cfg, "gather_enrich.report_tile",
                       (int(reports),),
                       int(getattr(cfg, "flow_tile", 512)))


# -- gather_enrich memory-strategy variant ----------------------------------

def _pad(n: int, multiple: int) -> int:
    return -(-int(n) // multiple) * multiple


def ring_vmem_bytes(flows: int, history: int, words: int = WORDS) -> int:
    """VMEM the full gather_enrich kernel pins for the shard ring region:
    (F, words*H) u32 word-major rows + (F, H) i32 validity, each minor dim
    padded to whole 128-lane tiles, double-buffered by the pipeline."""
    return 2 * 4 * _pad(flows, SUBLANES) * (
        _pad(words * history, LANES) + _pad(history, LANES))


def gather_vmem_bytes(variant: str, flows: int, history: int,
                      report_tile: int, derived_dim: int,
                      words: int = WORDS) -> int:
    """Estimated peak VMEM working set of one gather_enrich variant.

    full: the pinned ring region, the (T, words*H) + (T, H) row scratch
          and the double-buffered (derived_dim, T) output block.
    hbm:  XLA gathers the R routed rows in HBM; the derive kernel streams
          double-buffered (words*H, T) entry, (H, T) validity and
          (derived_dim, T) output blocks — independent of F.
    """
    lanes = _pad(report_tile, LANES)
    out = 2 * 4 * _pad(derived_dim, SUBLANES) * lanes
    if variant == "full":
        scratch = 4 * _pad(report_tile, SUBLANES) * (
            _pad(words * history, LANES) + _pad(history, LANES))
        return ring_vmem_bytes(flows, history, words) + scratch + out
    if variant == "hbm":
        return 2 * 4 * lanes * (_pad(words * history, SUBLANES)
                                + _pad(history, SUBLANES)) + out
    raise ValueError(f"unknown gather variant {variant!r}; "
                     f"registered: {list(GATHER_VARIANTS)}")


def resolve_gather_variant(variant: Optional[str], cfg, flows: int,
                           history: int, report_tile: int,
                           derived_dim: int) -> str:
    """full-block while its working set fits the VMEM budget, hbm beyond.

    Same precedence (and same fail-loud env validation) as backends:
    explicit ``variant=`` argument > ``REPRO_GATHER_VARIANT`` >
    ``DFAConfig.gather_variant`` > tuned-config registry (an exact
    measurement for this shape, when one is armed) > the budget
    heuristic against ``DFAConfig.vmem_budget_mb``.
    """
    def heuristic():
        tuned = _tuned_value(cfg, "gather_enrich.variant",
                             (flows, history, report_tile, derived_dim))
        if tuned is not None:
            _check_choice(str(tuned), GATHER_VARIANTS, "tuning registry")
            return str(tuned)
        budget = int(getattr(cfg, "vmem_budget_mb", 16)
                     ) * VMEM_BYTES_PER_MB
        need = gather_vmem_bytes(
            "full", flows, history, report_tile, derived_dim,
            words=int(getattr(cfg, "payload_words", WORDS)))
        return "full" if need <= budget else "hbm"

    return _resolve_choice(
        variant, cfg, env_var=GATHER_ENV_VAR, choices=GATHER_VARIANTS,
        cfg_attr="gather_variant", heuristic=heuristic,
        arg_source="variant= argument")


# -- ingest_update event-stream variant -------------------------------------

def ingest_vmem_bytes(variant: str, events: int, event_tile: int) -> int:
    """Estimated peak VMEM working set of one ingest_update variant.

    Both kernels share the per-tile working set: the five sorted-stream
    input words, the (event_tile, event_tile) segment mask the MXU
    reduction contracts against, and the u16-half / output tiles.

    block: the whole padded sorted stream is staged through VMEM blocks
           by the Pallas pipeline (conservatively modeled as resident).
    hbm:   two double-buffered event-tile scratch slots — independent of
           E (the sorted stream stays in HBM), which is what lets one
           shard ingest the 2^20-events-per-period blocks.
    """
    tile_ws = (event_tile * EVENT_WORDS * 4          # input tile words
               + event_tile * event_tile * 4         # segment mask (f32)
               + 3 * event_tile * 8 * 4)             # lo/hi halves + out
    if variant == "block":
        return events * EVENT_WORDS * 4 + tile_ws
    if variant == "hbm":
        return 2 * event_tile * EVENT_WORDS * 4 + tile_ws
    raise ValueError(f"unknown ingest variant {variant!r}; "
                     f"registered: {list(INGEST_VARIANTS)}")


def resolve_ingest_variant(variant: Optional[str], cfg, events: int,
                           event_tile: int) -> str:
    """block while the sorted event stream fits the VMEM budget, hbm
    beyond. Same precedence (and same fail-loud env validation) as the
    gather variant: explicit ``variant=`` argument >
    ``REPRO_INGEST_VARIANT`` > ``DFAConfig.ingest_variant`` >
    tuned-config registry (an exact measurement for this event count,
    when one is armed) > the budget heuristic against
    ``DFAConfig.vmem_budget_mb``."""
    def heuristic():
        tuned = _tuned_value(cfg, "ingest_update.variant",
                             (events,))
        if tuned is not None:
            _check_choice(str(tuned), INGEST_VARIANTS, "tuning registry")
            return str(tuned)
        budget = int(getattr(cfg, "vmem_budget_mb", 16)
                     ) * VMEM_BYTES_PER_MB
        need = ingest_vmem_bytes("block", events, event_tile)
        return "block" if need <= budget else "hbm"

    return _resolve_choice(
        variant, cfg, env_var=INGEST_ENV_VAR, choices=INGEST_VARIANTS,
        cfg_attr="ingest_variant", heuristic=heuristic,
        arg_source="variant= argument")


def interpret_flag(backend: str) -> bool:
    """Whether a Pallas impl runs in the interpreter: exactly for the
    ``interpret`` backend. A ``pallas`` request off the TPU raises rather
    than falling back to the interpreter, so a run meant for the chip
    that lands on the CPU stops instead of carrying on; ``interpret`` is
    the CPU route for the same kernels."""
    if backend == "interpret":
        return True
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            f"kernel backend 'pallas' requested on "
            f"{jax.default_backend()!r}: compiled Pallas kernels need a "
            "TPU. Use backend 'interpret' (REPRO_KERNEL_BACKEND=interpret"
            " or DFAConfig.kernel_backend='interpret') to run them in the "
            "Pallas interpreter on this device")
    return False


def tpu_kernels(hlo_text: str) -> List[str]:
    """Names of the Pallas TPU kernels (``tpu_custom_call`` instructions)
    in a compiled program's HLO text — e.g. ``ingest_update_block``,
    ``ring_scatter``, ``gather_enrich_hbm`` (each ``pallas_call`` here
    passes its ``name``; XLA appends a ``.N`` suffix, dropped here)."""
    names = re.findall(
        r'%([\w-]+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"',
        hlo_text)
    return sorted(set(names))


def lookup(family: str, backend: Optional[str] = None,
           cfg=None) -> Tuple[str, Callable]:
    """Resolve (backend_name, implementation) for one call site."""
    _ensure_builtin()
    if family not in _REGISTRY:
        raise KeyError(f"unknown kernel family {family!r}; "
                       f"known: {sorted(_REGISTRY)}")
    b = resolve_backend(backend, cfg)
    impls = _REGISTRY[family]
    if b not in impls:
        raise KeyError(f"family {family!r} has no {b!r} implementation "
                       f"(has: {sorted(impls)})")
    return b, impls[b]


def _ensure_builtin() -> None:
    """Lazy-register the in-tree families (import cycle-free: kernel/ref
    modules never import ops.py or this module)."""
    global _BUILTIN_LOADED
    if _BUILTIN_LOADED:
        return
    from repro.kernels.derived_features import kernel as df_k
    from repro.kernels.derived_features import ref as df_r
    from repro.kernels.flash_attention import kernel as fa_k
    from repro.kernels.flash_attention import ref as fa_r
    from repro.kernels.flow_moments import kernel as fm_k
    from repro.kernels.flow_moments import ref as fm_r
    from repro.kernels.gather_enrich import kernel as ge_k
    from repro.kernels.gather_enrich import ref as ge_r
    from repro.kernels.ingest_update import kernel as iu_k
    from repro.kernels.ingest_update import ref as iu_r
    from repro.kernels.ring_scatter import kernel as rs_k
    from repro.kernels.ring_scatter import ref as rs_r

    register("flow_moments", "ref", fm_r.flow_moments_ref)
    register("flow_moments", "pallas", fm_k.flow_moments_pallas)
    register("flow_moments", "interpret", fm_k.flow_moments_pallas)

    register("ring_scatter", "ref", rs_r.ring_scatter_ref)
    register("ring_scatter", "pallas", rs_k.ring_scatter_pallas)
    register("ring_scatter", "interpret", rs_k.ring_scatter_pallas)

    register("derived_features", "ref", df_r.derived_features_ref)
    register("derived_features", "pallas", df_k.derived_features_pallas)
    register("derived_features", "interpret", df_k.derived_features_pallas)

    register("gather_enrich", "ref", ge_r.gather_enrich_ref)
    register("gather_enrich", "pallas", ge_k.gather_enrich_pallas)
    register("gather_enrich", "interpret", ge_k.gather_enrich_pallas)

    # HBM-resident memory-strategy variant (same semantics, ring region
    # stays in HBM; selected by resolve_gather_variant)
    register("gather_enrich_hbm", "ref", ge_r.gather_enrich_ref)
    register("gather_enrich_hbm", "pallas", ge_k.gather_enrich_hbm_pallas)
    register("gather_enrich_hbm", "interpret",
             ge_k.gather_enrich_hbm_pallas)

    # reporter-side fused ingest (sort-once, segment-reduce); the ref
    # backend keeps the pre-fusion multipass shape as the bitwise oracle
    register("ingest_update", "ref", iu_r.ingest_update_ref)
    register("ingest_update", "pallas", iu_k.ingest_update_pallas)
    register("ingest_update", "interpret", iu_k.ingest_update_pallas)

    # HBM-resident event-stream variant (same semantics, sorted stream
    # stays in HBM; selected by resolve_ingest_variant)
    register("ingest_update_hbm", "ref", iu_r.ingest_update_ref)
    register("ingest_update_hbm", "pallas", iu_k.ingest_update_hbm_pallas)
    register("ingest_update_hbm", "interpret",
             iu_k.ingest_update_hbm_pallas)

    register("flash_attention", "ref", fa_r.flash_attention_ref)
    register("flash_attention", "pallas", fa_k.flash_attention_pallas)
    register("flash_attention", "interpret", fa_k.flash_attention_pallas)

    # only after every family registered: a failed import above stays
    # retryable instead of leaving a partial registry behind
    _BUILTIN_LOADED = True
