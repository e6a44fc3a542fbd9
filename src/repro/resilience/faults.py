"""Deterministic fault injection and resilience configuration (DESIGN.md §17).

The DICE pipeline already tolerates *outdated* activations: the staleness
cache, the residual-codec base, and the paging pool are all sources of
slightly-old-but-valid data.  The resilience subsystem wires those up as
graceful-degradation paths — a failed paging fetch, a corrupted wire
payload, or an overloaded admission queue is absorbed as "one more stale
step" instead of an engine crash.

Two frozen, hashable configs ride inside ``DiceConfig`` (like
``CompressConfig`` / ``PagingSpec``), so plans, jit signatures, and the
plan-variant count are untouched:

* ``FaultConfig`` — seeded injection rates.  Off (``None`` / all-zero)
  means byte-identical graphs and bit-identical outputs.
* ``ResilienceConfig`` — the degradation ladder: wire guards, paging
  retry/fallback policy, demotion thresholds, admission bounds,
  quarantine.

``FaultPlan`` is the host-side roll engine: every decision is a pure
function of ``(seed, site, *coordinates)`` via crc32, so a chaos run is
reproducible from its seed alone (no RNG state, no wall clock).  In-graph
corruption masks are drawn from the traced step key folded with the seed,
so they are reproducible per (step, layer, site) and add no static args.
"""
import dataclasses
import hashlib
from typing import List, Optional

import jax
import jax.numpy as jnp

# indices into the (NUM_FAULT_EVENTS,) fault-event vector accumulated
# in-graph by moe_forward and summed over layers/shards by dit_forward
FE_CORRUPT_COMBINE = 0   # combine-direction pair rows corrupted (injected)
FE_GUARDED_COMBINE = 1   # combine-direction pair rows caught by the guard
FE_CORRUPT_DISPATCH = 2  # dispatch-direction token rows corrupted (injected)
FE_GUARDED_DISPATCH = 3  # dispatch-direction token rows caught by the guard
NUM_FAULT_EVENTS = 4


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Seeded fault-injection rates.  Plan-static and hashable; all-zero
    (or ``None`` on the ``ResilienceConfig``) injects nothing."""

    seed: int = 0
    # host-side paging faults, rolled per (layer, dev, fetch-seq, attempt)
    paging_error_rate: float = 0.0
    paging_delay_rate: float = 0.0
    paging_delay_s: float = 0.0
    # in-graph NaN corruption of wire payloads, drawn from the traced key
    corrupt_combine_rate: float = 0.0
    corrupt_dispatch_rate: float = 0.0
    # host-side slow ring hop: sleep injected into the engine tick while a
    # ring engine is live (the watchdog observes the walltime breach)
    hop_delay_rate: float = 0.0
    hop_delay_s: float = 0.0
    # one-shot slot poisoning at this engine tick (-1 = never): models
    # corruption that escaped the wire guards and exercises quarantine
    poison_tick: int = -1
    # checkpoint chunk truncation, rolled per (leaf, chunk)
    checkpoint_truncate_rate: float = 0.0
    # arrival bursts: benches group arrivals into simultaneous bursts of
    # this size (0 = smooth arrivals)
    burst_size: int = 0

    @property
    def enabled(self) -> bool:
        return (self.paging_error_rate > 0 or self.paging_delay_rate > 0
                or self.corrupt_combine_rate > 0
                or self.corrupt_dispatch_rate > 0
                or self.hop_delay_rate > 0 or self.poison_tick >= 0
                or self.checkpoint_truncate_rate > 0 or self.burst_size > 0)


@dataclasses.dataclass(frozen=True)
class ResilienceConfig:
    """Degradation-ladder policy (DESIGN.md §17).  Hashable and carried on
    ``DiceConfig.resilience``; ``None`` there means the serving stack runs
    exactly the pre-resilience graphs (byte-identical)."""

    faults: Optional[FaultConfig] = None
    # rung 2: NaN/Inf wire guards — corrupted combine payloads fall back to
    # h_cache (the cond-comm masked-pair path), dispatch payloads to c_base
    guards: bool = True
    # rung 1: paging fetch retry-with-backoff under a deadline, then serve
    # the still-resident stale shard instead of crashing the engine
    paging_retries: int = 2
    paging_backoff_s: float = 5e-4
    paging_deadline_s: float = 0.25
    stale_fallback: bool = True
    # rung 3: variant demotion after this many consecutive anomalies
    demote_after: int = 3
    step_deadline_factor: float = 8.0   # watchdog: deadline = factor x baseline
    step_deadline_s: float = 0.0        # absolute deadline floor (0 = factor only)
    codec_error_limit: float = 0.0      # mean CODEC_ERR above this = codec anomaly
    # rung 4/5: quarantine + bounded admission
    quarantine: bool = True
    max_requeues: int = 2
    max_queue_depth: int = 0            # 0 = unbounded (legacy behavior)
    admission_deadline_steps: int = 0   # 0 = no admission deadline


def resilience_of(dcfg) -> Optional[ResilienceConfig]:
    """The resilience policy stamped on a DiceConfig, or None.  Reads via
    getattr so pre-resilience configs (and plain test doubles) pass."""
    return getattr(dcfg, "resilience", None)


def normalize_resilience(
        res: Optional[ResilienceConfig]) -> Optional[ResilienceConfig]:
    """Strip inert configs so "resilience off" is structurally ``None``
    (byte-identical graphs); ``repro.core.plan.resolve`` applies it."""
    if res is None:
        return None
    if res.faults is not None and not res.faults.enabled:
        res = dataclasses.replace(res, faults=None)
    inert = (res.faults is None and not res.guards and not res.quarantine
             and res.max_queue_depth <= 0
             and res.admission_deadline_steps <= 0
             and res.codec_error_limit <= 0 and res.step_deadline_s <= 0)
    return None if inert else res


# ---------------------------------------------------------------------------
# host-side deterministic rolls
# ---------------------------------------------------------------------------
def _roll(seed: int, *parts) -> float:
    """Uniform [0, 1) as a pure function of (seed, *parts) — hash-based so
    chaos runs replay exactly from the seed (no RNG state, no clock).
    sha256, not crc32: crc's GF(2)-linearity makes rolls at adjacent
    coordinates (e.g. retry attempts 0 and 1) perfectly correlated, which
    would make retries useless against injected fetch errors."""
    h = hashlib.sha256(
        repr(("dice-fault", int(seed)) + parts).encode("utf-8")).digest()
    return int.from_bytes(h[:8], "big") / 2.0 ** 64


class FaultPlan:
    """Host-side decision engine for a seeded :class:`FaultConfig`.

    Every method is deterministic in its arguments; the same seed and the
    same sequence of coordinates reproduce the same fault schedule."""

    def __init__(self, cfg: FaultConfig):
        self.cfg = cfg

    def roll(self, *parts) -> float:
        return _roll(self.cfg.seed, *parts)

    def paging_error(self, layer: int, dev: int, seq: int,
                     attempt: int) -> bool:
        r = self.cfg.paging_error_rate
        return r > 0 and self.roll("paging_err", layer, dev, seq, attempt) < r

    def paging_delay(self, layer: int, dev: int, seq: int,
                     attempt: int) -> bool:
        r = self.cfg.paging_delay_rate
        return r > 0 and self.roll("paging_delay", layer, dev, seq,
                                   attempt) < r

    def hop_delay(self, tick: int) -> bool:
        r = self.cfg.hop_delay_rate
        return r > 0 and self.roll("hop_delay", tick) < r

    def poison(self, tick: int) -> bool:
        return self.cfg.poison_tick >= 0 and tick == self.cfg.poison_tick

    def truncate_chunk(self, leaf: int, chunk: int, payload: bytes) -> bytes:
        """Checkpoint read-truncation injection: deterministically drop the
        tail of a chunk payload (at least one byte) when the roll hits."""
        r = self.cfg.checkpoint_truncate_rate
        if r <= 0 or self.roll("ckpt_trunc", leaf, chunk) >= r:
            return payload
        keep = int(len(payload) * self.roll("ckpt_keep", leaf, chunk))
        return payload[:min(keep, max(len(payload) - 1, 0))]


# ---------------------------------------------------------------------------
# in-graph corruption masks
# ---------------------------------------------------------------------------
def corruption_mask(key: Optional[jax.Array], seed: int, salt: int,
                    site: int, rate: float, shape) -> jax.Array:
    """Bernoulli(rate) mask drawn from the traced step key folded with the
    fault seed, a per-layer salt, and the injection site.  ``rate`` is a
    Python float (closure constant), so traces stay static; the key is the
    per-tick folded step key, so injection is reproducible per step."""
    if key is None:
        key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    ck = jax.random.fold_in(key, (seed * 7 + site) & 0x7FFFFFFF)
    ck = jax.random.fold_in(ck, salt & 0x7FFFFFFF)
    return jax.random.bernoulli(ck, rate, shape)


def corrupt_rows(payload: jax.Array, mask: jax.Array) -> jax.Array:
    """NaN-poison the rows of ``payload`` selected by ``mask`` (one bool per
    leading-row; broadcast over the trailing feature axis)."""
    bad = jnp.asarray(jnp.nan, payload.dtype)
    return jnp.where(mask[..., None], bad, payload)


# ---------------------------------------------------------------------------
# arrival bursts + CLI spec parsing
# ---------------------------------------------------------------------------
def bursty_arrivals(n: int, rate: float, burst_size: int,
                    start: float = 0.0) -> List[float]:
    """Arrival ticks where requests land in simultaneous bursts of
    ``burst_size``, spaced so the long-run rate still matches ``rate``
    requests/step.  ``burst_size <= 1`` degrades to smooth 1/rate spacing."""
    b = max(int(burst_size), 1)
    gap = (b if b > 1 else 1) / max(rate, 1e-9)
    if b == 1:
        return [start + i * gap for i in range(n)]
    return [start + (i // b) * gap for i in range(n)]


_FAULT_KEYS = {
    "seed": ("seed", int),
    "paging_err": ("paging_error_rate", float),
    "corrupt": ("corrupt_combine_rate", float),
    "corrupt_dispatch": ("corrupt_dispatch_rate", float),
    "poison_tick": ("poison_tick", int),
    "ckpt_trunc": ("checkpoint_truncate_rate", float),
    "burst": ("burst_size", int),
}
_RES_KEYS = {
    "guards": ("guards", lambda v: bool(int(v))),
    "quarantine": ("quarantine", lambda v: bool(int(v))),
    "stale_fallback": ("stale_fallback", lambda v: bool(int(v))),
    "retries": ("paging_retries", int),
    "backoff": ("paging_backoff_s", float),
    "fetch_deadline": ("paging_deadline_s", float),
    "demote_after": ("demote_after", int),
    "step_deadline_factor": ("step_deadline_factor", float),
    "step_deadline": ("step_deadline_s", float),
    "codec_err_limit": ("codec_error_limit", float),
    "queue": ("max_queue_depth", int),
    "admit_deadline": ("admission_deadline_steps", int),
    "requeues": ("max_requeues", int),
}


def parse_resilience(spec: Optional[str]) -> Optional[ResilienceConfig]:
    """Parse a ``--faults`` CLI spec into a :class:`ResilienceConfig`.

    Comma-separated ``key=value`` pairs, e.g.::

        seed=7,corrupt=0.05,paging_err=0.3,hop_delay=0.5:0.01,queue=16

    ``hop_delay`` / ``paging_delay`` take ``rate:seconds``.  ``off`` /
    empty returns None (resilience entirely disabled)."""
    if spec is None or spec.strip() in ("", "off", "none"):
        return None
    faults: dict = {}
    res: dict = {}
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            raise ValueError(f"--faults item {item!r} is not key=value")
        k, v = item.split("=", 1)
        k = k.strip()
        v = v.strip()
        if k == "hop_delay" or k == "paging_delay":
            rate, _, secs = v.partition(":")
            faults[f"{k}_rate"] = float(rate)
            if secs:
                faults[f"{k}_s"] = float(secs)
        elif k in _FAULT_KEYS:
            field, conv = _FAULT_KEYS[k]
            faults[field] = conv(v)
        elif k in _RES_KEYS:
            field, conv = _RES_KEYS[k]
            res[field] = conv(v)
        else:
            raise ValueError(
                f"unknown --faults key {k!r} (known: "
                f"{sorted(_FAULT_KEYS) + sorted(_RES_KEYS) + ['hop_delay', 'paging_delay']})")
    fcfg = FaultConfig(**faults) if faults else None
    if fcfg is not None and not fcfg.enabled:
        fcfg = None
    return ResilienceConfig(faults=fcfg, **res)
