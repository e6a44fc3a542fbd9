"""Operations a DiT-MoE forward requires, from the configuration's shapes.

"Required" counts what the model's equations ask for: every token through
attention (projections and scores), its shared experts and exactly its
top-k routed experts (no capacity padding, no dropped pairs), the router,
adaLN and the embeddings.  A multiply-add counts 2.  Staleness does not
change the count: a reused expert output is work the step did not have to
do again, so a schedule that reuses more shows a lower required rate for
the same output, never a higher one.
"""
from __future__ import annotations


def per_token_layer(m: dict) -> int:
    """Required FLOPs of one token through one block, adaLN excluded."""
    d, T = m["d_model"], m["patch_tokens"]
    hd = m["num_heads"] * m["head_dim"]
    f, E, k = m["moe_d_ff"], m["num_experts"], m["experts_per_token"]
    attn = 2 * d * hd * 4 + 2 * T * hd * 2        # q, k, v, o; scores, mix
    routed = k * 3 * 2 * d * f
    shared = 3 * 2 * d * m["shared_d_ff"] if m["num_shared_experts"] else 0
    router = 2 * d * E
    return attn + routed + shared + router


def per_image_forward(m: dict) -> int:
    """Required FLOPs of one image (all its tokens) through one forward."""
    d, T, L, c = m["d_model"], m["patch_tokens"], m["num_layers"], \
        m["in_channels"]
    tokens = T * L * per_token_layer(m)
    adaln = L * 2 * d * 6 * d
    embed = T * 2 * c * d + 2 * 256 * d + 2 * d * d
    final = 2 * d * 2 * d + T * 2 * d * c
    return tokens + adaln + embed + final


def per_lane_step(m: dict, guided: bool = True) -> int:
    """One request's share of one engine tick: one Euler step, two
    forwards when guidance is on."""
    return per_image_forward(m) * (2 if guided else 1)
