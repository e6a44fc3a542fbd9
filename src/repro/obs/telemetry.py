"""In-graph staleness telemetry (DESIGN.md Sec. 16).

The ROADMAP's closed-loop controller needs the signal the codec already
reconstructs: how far the current payload has drifted from the staleness
cache it is transmitted against.  This module defines the fixed-shape
per-layer telemetry block that carries exactly that out of the traced
step — ``ObsConfig``-gated so that ``obs=off`` traces are byte-identical
to a build without the subsystem, and shape-static (one (NUM_FIELDS,)
f32 vector per MoE layer, whatever the plan variant) so turning it on
never adds jit-cache entries beyond the plan-variant count.

Field semantics (per layer, per step):

  ``staleness_age``             the action's consumption staleness in
                                steps (sync 0, interweaved/staggered 1,
                                displaced 2) — static, stamped by
                                :func:`repro.core.staleness.apply_layer_action`
  ``residual_energy_dispatch``  ``‖x − c_base‖² / ‖x‖²`` — the relative
                                energy of the dispatch residual the wire
                                codec compresses.  0 on steps that
                                transmit losslessly (no residual is on
                                the wire, by definition).
  ``residual_energy_combine``   ``‖h_fresh − h_cache‖² / ‖h_fresh‖²``
                                over pairs transmitted fresh AND kept —
                                the realized drift between the expert
                                outputs arriving now and the cached
                                values stale pairs consume.  Measured on
                                steps that actually lean on the cache
                                (a cond-comm mask or a combine codec);
                                0 on lossless refresh/sync steps.
  ``mask_rate``                 fraction of (token, rank) pairs
                                transmitted fresh (1.0 when no
                                conditional-communication mask).
  ``dropped_frac``              capacity-drop fraction over dispatched
                                pairs (same value as ``aux.dropped_frac``).
  ``codec_error``               relative quantization error actually
                                injected by the wire codec this step
                                (dispatch + combine reconstructions);
                                exactly 0 on lossless steps.

All ratios are computed on the local token shard; the mesh path pmean's
them over the token-sharding axes alongside the other aux reductions, so
the reported block is the shard-mean and replicated.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

TELEMETRY_FIELDS = (
    "staleness_age",
    "residual_energy_dispatch",
    "residual_energy_combine",
    "mask_rate",
    "dropped_frac",
    "codec_error",
)
NUM_FIELDS = len(TELEMETRY_FIELDS)
AGE, RES_DISPATCH, RES_COMBINE, MASK_RATE, DROP_FRAC, CODEC_ERR = range(
    NUM_FIELDS)

_EPS = 1e-12


@dataclass(frozen=True)
class ObsConfig:
    """Observability gate, threaded as a closure constant (never a traced
    or jit-static *argument*, so it cannot multiply jit-cache entries).

    ``enabled=False`` (the default everywhere) keeps every traced graph
    byte-identical to a build without the subsystem.  The device-side
    names of the layer parts (``jax.named_scope``) do not depend on it:
    the model carries them always."""
    enabled: bool = False


def _rel_energy(diff, ref):
    """``‖diff‖² / ‖ref‖²`` in f32 with a zero-safe denominator."""
    num = jnp.sum(jnp.square(diff.astype(jnp.float32)))
    den = jnp.sum(jnp.square(ref.astype(jnp.float32)))
    return num / jnp.maximum(den, _EPS)


def layer_telemetry(*, x, x_wire, dispatch_base, codec,
                    pair_vals, recon, pair_keep,
                    fresh_mask, h_cache, dropped_frac) -> jnp.ndarray:
    """The (NUM_FIELDS,) f32 telemetry vector of one MoE layer forward.

    Called from :func:`repro.core.moe.moe_forward` with the quantities it
    already computes; ``pair_vals`` are the PRE-reconstruction combined
    pair values (fresh pairs carry the raw wire value) and ``recon`` the
    codec's combine-path reconstruction (None when lossless).  The
    ``staleness_age`` slot is left 0 here — it is an action-level
    property the executor stamps afterwards."""
    zero = jnp.float32(0.0)
    # ---- dispatch path: residual vs the codec base, and its quantization
    # error.  Lossless steps put no residual on the wire -> both are 0.
    if codec is not None:
        base = jnp.zeros_like(x) if dispatch_base is None else dispatch_base
        den = jnp.maximum(
            jnp.sum(jnp.square(x.astype(jnp.float32))), _EPS)
        res_d = jnp.sum(jnp.square(
            x.astype(jnp.float32) - base.astype(jnp.float32))) / den
        err_d = jnp.sum(jnp.square(
            x_wire.astype(jnp.float32) - x.astype(jnp.float32))) / den
    else:
        res_d = err_d = zero
    # ---- combine path: drift of freshly arriving expert outputs vs the
    # conditional-communication cache, measured over fresh-AND-kept pairs
    # (the only pairs where both sides exist).  Gated on steps that lean
    # on the cache: a cond-comm mask or a combine codec.
    res_c = err_c = zero
    if h_cache is not None and (fresh_mask is not None or codec is not None):
        fk = pair_keep if fresh_mask is None else (pair_keep & fresh_mask)
        w = fk[..., None].astype(jnp.float32)
        pv = pair_vals.astype(jnp.float32) * w
        den_c = jnp.maximum(jnp.sum(jnp.square(pv)), _EPS)
        res_c = jnp.sum(jnp.square(
            pv - h_cache.astype(jnp.float32) * w)) / den_c
        if recon is not None:
            err_c = jnp.sum(jnp.square(
                recon.astype(jnp.float32) * w - pv)) / den_c
    mask_rate = (jnp.mean(fresh_mask.astype(jnp.float32))
                 if fresh_mask is not None else jnp.float32(1.0))
    return jnp.stack([zero, jnp.float32(res_d), jnp.float32(res_c),
                      jnp.float32(mask_rate),
                      dropped_frac.astype(jnp.float32),
                      jnp.float32(err_d + err_c)])


def stamp_age(aux, action, obs: Optional[ObsConfig]):
    """Write the action's static staleness age into an aux telemetry
    block (no-op when telemetry is off)."""
    if obs is None or not obs.enabled or aux.telemetry is None:
        return aux
    return aux._replace(telemetry=aux.telemetry.at[AGE].set(
        jnp.float32(action.staleness)))


def merge_staggered(t0, t1):
    """Telemetry of staggered mode's two half-batch calls: the ratio and
    rate fields average (equal-sized halves)."""
    if t0 is None or t1 is None:
        return None
    return (t0 + t1) * 0.5
