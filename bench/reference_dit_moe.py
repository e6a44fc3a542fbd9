"""Plain reference of DiT-MoE served under the DICE schedule, and the
benchmark's own weights.

Written from the model's equations, in ``jax.numpy`` and float32 at
"highest" matmul precision; it imports nothing of the system under test.
It does two jobs:

* :func:`make_weights` draws the weight tree the benchmark hands to the
  server: one jitted program from the seed, in the served dtype (bf16),
  in the tree layout the serving entry point takes.  The adaLN and output
  projections are drawn like every other dense layer (zero-initialised,
  the velocity would be 0 and any check would pass on an identity).
* :func:`replay` recomputes what a continuous-batching run served, tick
  by tick, from the admissions and ticks that run logged: every lane of
  the batch (free lanes included, since their tokens take expert
  capacity), the staleness state of each MoE layer, the guided Euler
  update.  It returns the final latent of each request asked for.

The layer equations (arXiv:2407.11633, DiT with MoE FFNs, as this repo
serves it):

  h0  = x W_patch + pos;   c = MLP(sinusoid(t)) + class_embed[y]
  per layer: (s1, a1, g1, s2, a2, g2) = silu(c) W_ada
    h += g1 * Attn(mod(rms(h), s1, a1))        16 heads, RoPE, no mask
    h += g2 * MoE(mod(rms(h), s2, a2))
  MoE(u) = sum_k p_k(u) E_{i_k}(u) + S(u)      top-2 of softmax(u W_r),
           scores not renormalised; E, S gated SiLU FFNs, S of width
           shared_d_ff (DiT-MoE: num_shared_experts x d_model)
  v = mod(rms(h), fs, fa) W_out;  guided v = v_u + g (v_c - v_u)
  x <- x + v / num_steps

Departures from the published model, as served here: RoPE inside
attention beside the learned position table, and expert capacity (tokens
past ``capacity`` per expert, in token order, are dropped; capacity is
counted per group of lanes that share a chip).

DICE (the schedule this benchmark serves): the deepest half of the layers
run synchronously; the others consume the MoE output computed one step
earlier (interweaved), and on every other step send only each token's
top-1 pair, reusing the last expert output kept for the second pair.
A lane's first ``warmup_steps`` steps run synchronously.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def _weight_shapes(m: dict):
    """(path, shape, kind) for every leaf, in a fixed order; kind is
    "dense" (normal / sqrt(fan-in)), "embed" (0.02 normal), "norm"
    (zeros, f32) or "router" (dense, f32)."""
    d, c_in, f, E = m["d_model"], m["in_channels"], m["moe_d_ff"], \
        m["num_experts"]
    hd = m["num_heads"] * m["head_dim"]
    fs = m["shared_d_ff"] if m["num_shared_experts"] else 0
    out = [(("patch_embed",), (c_in, d), "dense"),
           (("pos_embed",), (m["patch_tokens"], d), "embed"),
           (("t_mlp1",), (256, d), "dense"),
           (("t_mlp2",), (d, d), "dense"),
           (("class_embed",), (m["num_classes"] + 1, d), "embed"),
           (("final_mod",), (d, 2 * d), "dense"),
           (("final_out",), (d, c_in), "dense"),
           (("final_norm", "scale"), (d,), "norm")]
    for i in range(m["num_layers"]):
        b = ("blocks", i)
        out += [(b + ("ln1", "scale"), (d,), "norm"),
                (b + ("ln2", "scale"), (d,), "norm"),
                (b + ("attn", "wq"), (d, hd), "dense"),
                (b + ("attn", "wk"), (d, hd), "dense"),
                (b + ("attn", "wv"), (d, hd), "dense"),
                (b + ("attn", "wo"), (hd, d), "dense"),
                (b + ("moe", "router"), (d, E), "router"),
                (b + ("moe", "experts_gate"), (E, d, f), "dense"),
                (b + ("moe", "experts_up"), (E, d, f), "dense"),
                (b + ("moe", "experts_down"), (E, f, d), "dense"),
                (b + ("adaln",), (d, 6 * d), "dense")]
        if fs:
            out += [(b + ("moe", "shared_gate"), (d, fs), "dense"),
                    (b + ("moe", "shared_up"), (d, fs), "dense"),
                    (b + ("moe", "shared_down"), (fs, d), "dense")]
    return out


def _set(tree, path, value):
    node = tree
    for k in path[:-1]:
        if k == "blocks":
            node = node.setdefault("blocks", [])
        elif isinstance(k, int):
            while len(node) <= k:
                node.append({})
            node = node[k]
        else:
            node = node.setdefault(k, {})
    node[path[-1]] = value


def make_weights(m: dict, seed: int, out_shardings=None):
    """The whole weight tree from ``seed``, made on the device by one
    jitted program, in ``m["dtype"]`` (norm scales and routers f32)."""
    dtype = jnp.dtype(m["dtype"])
    leaves = _weight_shapes(m)

    def build(key):
        tree: dict = {}
        for j, (path, shape, kind) in enumerate(leaves):
            k = jax.random.fold_in(key, j)
            if kind == "norm":
                val = jnp.zeros(shape, F32)
            elif kind == "embed":
                val = (0.02 * jax.random.normal(k, shape, F32)).astype(dtype)
            else:
                fan_in = shape[-2]
                val = jax.random.normal(k, shape, F32) / math.sqrt(fan_in)
                val = val.astype(F32 if kind == "router" else dtype)
            _set(tree, path, val)
        return tree

    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    if out_shardings is None:
        return jax.jit(build)(key)
    return jax.jit(build, out_shardings=out_shardings)(key)


def abstract_weights(m: dict):
    """``ShapeDtypeStruct`` tree of :func:`make_weights`, with no compute."""
    def build():
        dtype = jnp.dtype(m["dtype"])
        tree: dict = {}
        for path, shape, kind in _weight_shapes(m):
            dt = F32 if kind in ("norm", "router") else dtype
            _set(tree, path, jnp.zeros(shape, dt))
        return tree
    return jax.eval_shape(build)


def request_noise(noise_key, rid: int, m: dict):
    """A request's starting latent: keyed by its id alone, so it does not
    depend on the lane it is served in."""
    return jax.random.normal(jax.random.fold_in(noise_key, rid),
                             (m["patch_tokens"], m["in_channels"]))


# ---------------------------------------------------------------------------
# arithmetic: float32 at "highest", or the control's fp8
# ---------------------------------------------------------------------------
def _q8(a):
    """Round to float8 e4m3 with one absmax scale per tensor."""
    a = a.astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(FP8).astype(F32) * s


def _ein(spec, a, b, fp8: bool):
    a, b = a.astype(F32), b.astype(F32)
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _mm(a, b, fp8):
    return _ein("...i,ij->...j", a, b, fp8)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _mod(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _rope(x, theta):
    """x: (B, T, H, Dh), positions 0..T-1; halves rotated."""
    T, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _ffn(u, wg, wu, wd, fp8, spec_in, spec_out):
    g = _ein(spec_in, u, wg, fp8)
    up = _ein(spec_in, u, wu, fp8)
    return _ein(spec_out, jax.nn.silu(g) * up, wd, fp8)


def capacity_of(tokens: int, k_eff: int, m: dict) -> int:
    """Per-expert slots of one capacity group, rounded up to 8."""
    c = math.ceil(tokens * k_eff * m["capacity_factor"] / m["num_experts"])
    return max(8, -(-c // 8) * 8)


@partial(jax.jit, static_argnames=("m_items", "fp8"))
def _embed(p, x, t, y, *, m_items, fp8):
    m = dict(m_items)
    h = _mm(x, p["patch_embed"], fp8) + p["pos_embed"].astype(F32)[None]
    half = 128
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=F32) / half)
    ang = t[:, None] * 1000.0 * freqs[None]
    temb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)
    temb = _mm(jax.nn.silu(_mm(temb, p["t_mlp1"], fp8)), p["t_mlp2"], fp8)
    c = temb + p["class_embed"].astype(F32)[y]
    return h, c


@partial(jax.jit, static_argnames=("m_items", "fp8"))
def _final(p, h, c, *, m_items, fp8):
    m = dict(m_items)
    fs, fa = jnp.split(_mm(jax.nn.silu(c), p["final_mod"], fp8), 2, axis=-1)
    h = _mod(_rms(h, p["final_norm"]["scale"], m["norm_eps"]), fs, fa)
    return _mm(h, p["final_out"], fp8)


@partial(jax.jit, static_argnames=("m_items", "fp8", "groups", "capacity",
                                   "sync"))
def _layer(blk, h, c, y_buf, h_cache, take_fresh, fresh, *, m_items, fp8,
           groups, capacity, sync):
    """One DiT-MoE block over every lane.

    h: (B, T, d); c: (B, d); y_buf (B*T, d) and h_cache (B*T, K, d): this
    layer's staleness state (unused when ``sync``); take_fresh (B*T,):
    tokens that consume this step's MoE output (the rest consume y_buf);
    fresh (B*T, K): pairs sent to their expert this step (the rest reuse
    h_cache).  Capacity is counted per group of B / groups lanes.
    Returns (h, y_new, h_cache_new)."""
    m = dict(m_items)
    B, T, d = h.shape
    H, Dh, E, K = m["num_heads"], m["head_dim"], m["num_experts"], \
        m["experts_per_token"]
    eps = m["norm_eps"]
    s1, a1, g1, s2, a2, g2 = jnp.split(
        _mm(jax.nn.silu(c), blk["adaln"], fp8), 6, axis=-1)

    # attention
    u = _mod(_rms(h, blk["ln1"]["scale"], eps), s1, a1)
    at = blk["attn"]
    q = _rope(_mm(u, at["wq"], fp8).reshape(B, T, H, Dh), m["rope_theta"])
    k = _rope(_mm(u, at["wk"], fp8).reshape(B, T, H, Dh), m["rope_theta"])
    v = _mm(u, at["wv"], fp8).reshape(B, T, H, Dh)
    s = _ein("bqhd,bkhd->bhqk", q, k, fp8) / math.sqrt(Dh)
    o = _ein("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, fp8)
    h = h + g1[:, None, :] * _mm(o.reshape(B, T, H * Dh), at["wo"], fp8)

    # MoE: route, pack per group and expert in token order, drop overflow
    u = _mod(_rms(h, blk["ln2"]["scale"], eps), s2, a2).reshape(B * T, d)
    mo = blk["moe"]
    probs = jax.nn.softmax(_mm(u, mo["router"], fp8), axis=-1)
    scores, idx = jax.lax.top_k(probs, K)                       # (N, K)
    N = B * T
    ng = N // groups
    hot = (idx[..., None] == jnp.arange(E)) & fresh[..., None]  # (N, K, E)
    hot = hot.reshape(groups, ng * K, E).astype(jnp.int32)
    pos = (jnp.cumsum(hot, axis=1) - 1) * hot
    pos = pos.sum(-1).reshape(N, K)
    keep = fresh & (pos < capacity)
    grp = jnp.repeat(jnp.arange(groups), ng * K).reshape(N, K)
    tok = jnp.repeat(jnp.arange(N), K).reshape(N, K)
    slot = jnp.where(keep, pos, capacity)                       # C: dropped
    buf = jnp.zeros((groups, E, capacity, d), F32)
    buf = buf.at[grp, idx, slot].set(u[tok], mode="drop")
    out = _ffn(buf, mo["experts_gate"], mo["experts_up"],
               mo["experts_down"], fp8, "gecd,edf->gecf", "gecf,efd->gecd")
    pv = out.at[grp, idx, slot].get(mode="fill", fill_value=0.0)  # (N, K, d)
    pv = jnp.where(keep[..., None], pv, 0.0)
    if not sync:
        pv = jnp.where(fresh[..., None], pv, h_cache)
    y = jnp.einsum("nk,nkd->nd", scores, pv)
    if "shared_gate" in mo:
        y = y + _ffn(u, mo["shared_gate"], mo["shared_up"],
                     mo["shared_down"], fp8, "ni,if->nf", "nf,fd->nd")
    if sync:
        consumed, new_cache = y, h_cache
    else:
        consumed = jnp.where(take_fresh[:, None], y, y_buf)
        new_cache = jnp.where((fresh & keep)[..., None], pv, h_cache)
    h = h + g2[:, None, :] * consumed.reshape(B, T, d)
    return h, y, new_cache


# ---------------------------------------------------------------------------
# the served run, replayed
# ---------------------------------------------------------------------------
@dataclass
class Served:
    """What a continuous-batching call did, as its spans logged it.

    ``ticks``: (tick, slotted) of every executed tick, in order.
    ``admissions``: tick -> [(lane, rid, class_id)] admitted at that tick.
    """
    ticks: List[Tuple[int, bool]]
    admissions: Dict[int, List[Tuple[int, int, int]]]


@dataclass
class Schedule:
    """DICE's knobs, as the configuration states them."""
    name: str = "dice"            # "dice" or "sync"
    warmup_steps: int = 2
    sync_fraction: float = 0.5    # deepest share of layers kept synchronous
    cond_stride: int = 2          # second pairs refresh every n steps
    capacity_groups: int = 1      # lanes are split into this many groups

    def sync_layers(self, num_layers: int) -> np.ndarray:
        mask = np.zeros(num_layers, bool)
        if self.name == "sync":
            mask[:] = True
            return mask
        k = int(round(num_layers * self.sync_fraction))
        mask[num_layers - k:] = True
        return mask


@dataclass
class _Lanes:
    x: np.ndarray
    cls: np.ndarray
    step: np.ndarray
    active: np.ndarray
    rid: np.ndarray
    state: Dict[Tuple[int, int], Tuple[jnp.ndarray, jnp.ndarray]] = \
        field(default_factory=dict)


class ReplayMismatch(Exception):
    """The logged run does not follow the engine's admission rules."""


def replay(params, m: dict, sched: Schedule, served: Served, *, key,
           num_steps: int, guidance: float, max_batch: int,
           want: Sequence[int], start_tick: Optional[int] = None,
           fp8: bool = False, sharding=None) -> Dict[int, np.ndarray]:
    """Final latents of the requests in ``want``, recomputed.

    ``start_tick``: replay from this logged tick on; every lane must be
    admitted at it (a whole cohort), so nothing before it matters.
    ``None`` replays from the call's first tick, with every lane empty.
    ``sharding``: where the activations and state live (default: the
    first device).  On a mesh give the replicated sharding: the weights
    stay where they are and the compiler splits the expert matmuls
    along the experts' sharding."""
    dev = sharding if sharding is not None else jax.devices()[0]
    B, T, C = max_batch, m["patch_tokens"], m["in_channels"]
    K, L = m["experts_per_token"], m["num_layers"]
    d = m["d_model"]
    null = m["num_classes"]
    sync_mask = sched.sync_layers(L)
    m_items = tuple(sorted(m.items()))
    noise_key = jax.random.split(key)[0]
    want = set(int(r) for r in want)
    lanes = _Lanes(x=np.zeros((B, T, C), np.float32),
                   cls=np.full((B,), null, np.int32),
                   step=np.zeros((B,), np.int64),
                   active=np.zeros((B,), bool),
                   rid=np.full((B,), -1, np.int64))
    zeros_y = jnp.zeros((B * T, d), F32, device=dev)
    zeros_c = jnp.zeros((B * T, K, d), F32, device=dev)
    for pas in (0, 1):
        for i in range(L):
            if not sync_mask[i]:
                lanes.state[(pas, i)] = (zeros_y, zeros_c)
    blocks = params["blocks"]
    top = {k: v for k, v in params.items() if k != "blocks"}
    ticks = served.ticks
    if start_tick is not None:
        ticks = [tk for tk in ticks if tk[0] >= start_tick]
        lanes_in = {a[0] for a in served.admissions.get(start_tick, [])}
        if lanes_in != set(range(B)):
            raise ReplayMismatch(f"tick {start_tick} admits lanes "
                                 f"{sorted(lanes_in)}, not all {B}")
    out: Dict[int, np.ndarray] = {}
    dt = 1.0 / num_steps
    for tick, slotted_logged in ticks:
        if want <= set(out):
            break
        adm = served.admissions.get(tick, [])
        if adm:
            reset = np.zeros((B,), bool)
            for lane, rid, cls in adm:
                if lanes.active[lane]:
                    raise ReplayMismatch(f"tick {tick}: lane {lane} busy")
                lanes.x[lane] = np.asarray(request_noise(noise_key, rid, m))
                lanes.cls[lane] = cls
                lanes.step[lane] = 0
                lanes.active[lane] = True
                lanes.rid[lane] = rid
                reset[lane] = True
            rt = jnp.asarray(np.repeat(reset, T), device=dev)
            lanes.state = {
                k: (jnp.where(rt[:, None], 0.0, yb),
                    jnp.where(rt[:, None, None], 0.0, hc))
                for k, (yb, hc) in lanes.state.items()}
        act = lanes.active
        if not act.any():
            raise ReplayMismatch(f"tick {tick} ran with no lane active")
        warming = act & (lanes.step < sched.warmup_steps)
        slotted = bool(warming.any())
        if slotted != bool(slotted_logged):
            raise ReplayMismatch(f"tick {tick}: slotted {slotted_logged} "
                                 f"logged, {slotted} by the rules")
        rank0 = np.zeros((B * T, K), bool)
        rank0[:, 0] = True
        if slotted:
            # lanes in warm-up (and free ones) run synchronously; the rest
            # follow their own phase, at full capacity
            fresh_lane = warming | ~act
            light = tick % sched.cond_stride != 0
            est = np.ones((B * T, K), bool) if not light else rank0
            fresh = np.where(np.repeat(fresh_lane, T)[:, None], True, est)
            take = np.repeat(fresh_lane, T)
            k_eff = K
        else:
            idx = int(min(lanes.step[act].min(), num_steps - 1))
            light = idx % sched.cond_stride != 0
            fresh = rank0 if light else np.ones((B * T, K), bool)
            take = np.zeros((B * T,), bool)
            k_eff = 1 if light else K
        cap = capacity_of(B * T // sched.capacity_groups, k_eff, m)
        t = np.where(act, lanes.step * dt, 0.0).astype(np.float32)
        fresh_d = jnp.asarray(fresh, device=dev)
        take_d = jnp.asarray(take, device=dev)
        all_fresh = jnp.ones((B * T, K), bool, device=dev)
        vs = []
        x_d = jnp.asarray(lanes.x, device=dev)
        t_d = jnp.asarray(t, device=dev)
        for pas, cls in ((0, lanes.cls), (1, np.full((B,), null, np.int32))):
            h, c = _embed(top, x_d, t_d, jnp.asarray(cls, device=dev),
                          m_items=m_items, fp8=fp8)
            for i in range(L):
                blk = blocks[i]
                if sync_mask[i]:
                    h, _, _ = _layer(blk, h, c, zeros_y, zeros_c, take_d,
                                     all_fresh, m_items=m_items, fp8=fp8,
                                     groups=sched.capacity_groups,
                                     capacity=capacity_of(
                                         B * T // sched.capacity_groups, K,
                                         m),
                                     sync=True)
                else:
                    yb, hc = lanes.state[(pas, i)]
                    h, y_new, hc = _layer(blk, h, c, yb, hc, take_d,
                                          fresh_d, m_items=m_items, fp8=fp8,
                                          groups=sched.capacity_groups,
                                          capacity=cap, sync=False)
                    lanes.state[(pas, i)] = (y_new, hc)
            vs.append(_final(top, h, c, m_items=m_items, fp8=fp8))
        v = vs[1] + guidance * (vs[0] - vs[1])
        lanes.x = np.array(x_d + dt * v)
        for lane in np.flatnonzero(act):
            lanes.step[lane] += 1
            if lanes.step[lane] >= num_steps:
                rid = int(lanes.rid[lane])
                if rid in want:
                    out[rid] = lanes.x[lane].copy()
                lanes.active[lane] = False
                lanes.cls[lane] = null
                lanes.rid[lane] = -1
    return out
