"""Token-level Conditional Communication (paper Sec. 4.3, Alg. 4).

MoE output is the router-score-weighted sum y_i = sum_e s_i^e h_i^e, so a
staleness perturbation on h propagates with magnitude proportional to the
score (paper Eq. 1).  Therefore: the top-1 (token, expert) pair is always
transmitted fresh; lower-ranked pairs reuse their cached expert output and
refresh only every ``stride`` steps.  Training-free.

In the serving engine this is realised with two compiled step variants:
"refresh" steps dispatch all K ranks (full capacity), "light" steps
dispatch only rank-0 pairs into a K-times-smaller buffer — the all-to-all
payload genuinely shrinks (visible in the lowered HLO), unlike a masked
send of a fixed-size buffer.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def is_refresh_step(step: int, stride: int) -> bool:
    return stride <= 1 or (step % stride == 0)


def policy_mask(policy: str, num_tokens: int, k: int,
                key: Optional[jax.Array] = None) -> jnp.ndarray:
    """(T, K) bool: which (token, rank) pairs are transmitted on a light
    (non-refresh) step under ``policy``.

    policy "low"  — deprioritise low-score (non-top-1) pairs   [paper's choice]
    policy "high" — deprioritise the top-1 pair                 [ablation]
    policy "random" — deprioritise a random half of pairs       [ablation]
    """
    ranks = jnp.arange(k)[None, :].repeat(num_tokens, axis=0)
    if policy == "low":
        return ranks == 0
    if policy == "high":
        return ranks != 0
    if policy == "random":
        assert key is not None
        return jax.random.bernoulli(key, 0.5, (num_tokens, k))
    raise ValueError(f"unknown cond_policy: {policy}")


def policy_effective_k(policy: str, k: int) -> int:
    """Ranks dispatched on a light step (sizes the dispatch buffer)."""
    if policy == "low":
        return 1
    if policy == "high":
        return k - 1
    if policy == "random":
        return max(1, k // 2)      # expect half
    raise ValueError(f"unknown cond_policy: {policy}")


def fresh_mask(step: int, num_tokens: int, k: int, *, stride: int,
               policy: str = "low",
               key: Optional[jax.Array] = None) -> Optional[jnp.ndarray]:
    """Step-indexed form of :func:`policy_mask`; ``None`` on refresh steps
    (everything fresh)."""
    if is_refresh_step(step, stride):
        return None
    return policy_mask(policy, num_tokens, k, key=key)


def effective_k(step: int, k: int, *, stride: int, policy: str = "low") -> int:
    """Ranks actually dispatched this step (sizes the dispatch buffer)."""
    if is_refresh_step(step, stride):
        return k
    return policy_effective_k(policy, k)


def comm_volume_fraction(k: int, stride: int, policy: str = "low", *,
                         light_scale: float = 1.0) -> float:
    """Long-run mean all-to-all volume relative to full dispatch.

    ``light_scale`` (<= 1) scales the light steps' per-rank volume — the
    wire codec's compression ratio (``CodecSpec.wire_ratio``) when light
    payloads are transmitted as quantized residuals while refresh steps
    stay lossless (DESIGN.md Sec. 11)."""
    if stride <= 1:
        return 1.0
    kf = {"low": 1, "high": k - 1, "random": k / 2}[policy]
    # refresh step sends k ranks fresh; the other (stride-1) steps send kf
    # ranks, each at the codec's light-step wire ratio
    return (k + (stride - 1) * kf * light_scale) / (stride * k)


def expected_dispatch_fraction(k: int, stride: int, policy: str,
                               capacity_of) -> float:
    """:func:`comm_volume_fraction` in *buffer slots*: the long-run mean
    per-device all-to-all payload relative to full dispatch given the
    actual (floor-aligned) capacities the plan allocates.

    ``capacity_of(k) -> int`` maps an effective rank count to the
    per-device dispatch capacity (``LayerAction.dispatch_capacity`` /
    ``moe.default_capacity``).  When capacity rounding is exact this
    equals :func:`comm_volume_fraction`; the 8-slot floor alignment makes
    it the quantity ``aux.dispatch_bytes`` actually measures on the wire.
    """
    if stride <= 1:
        return 1.0
    c_full = capacity_of(k)
    c_light = capacity_of(policy_effective_k(policy, k))
    return (c_full + (stride - 1) * c_light) / (stride * c_full)


@jax.named_scope("stale_select")
def update_cache(h_cache: Optional[jnp.ndarray],
                 pair_vals: jnp.ndarray,
                 mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Keep fresh pair outputs, retain cached values for stale pairs."""
    if mask is None or h_cache is None:
        return pair_vals
    return jnp.where(mask[..., None], pair_vals, h_cache)
