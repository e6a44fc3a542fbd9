"""Expert paging: host-RAM expert pool + planned async prefetch.

DESIGN.md Sec. 15.  Today's mesh path chains model size to mesh size:
every routed expert lives in device memory and ``moe_forward`` requires
``E % n_dev == 0``.  Paging breaks the chain — the full expert set lives
in a host-RAM :class:`ExpertPool` and each device holds only a bounded
working set of per-layer expert shards, fetched *inside* the traced step
via ``io_callback`` one MoE layer ahead of use (the plan's ``prefetch``
field), so the transfer has no data dependency on the current layer and
XLA overlaps it with the ring hops / expert GEMMs already in flight —
the same hide-behind-the-wire trick the ring engine plays with chunk
transfers (Sec. 12).

Because the pool pads the expert dim to the next multiple of the ep-axis
size (``E_pad``) with zero-weight *phantom experts* that the router can
never select (router logits only cover the real ``E``), paging also
lifts the ``E % n_dev == 0`` restriction: any expert count serves on any
mesh.  With ``E_pad == E`` the padded wire layout is exactly the
fully-resident layout, so paged runs stay bit-identical to resident runs
(the conformance suite's acceptance bar).

Residency accounting is a slot-allocator ledger: each device owns a
window of ``depth + 1`` per-layer slots (the layer being computed plus
the ``depth`` prefetched ahead); every fetch appends to the window and
evicts the oldest beyond it, and the pool tracks the realized
``peak_resident_bytes`` per device from the actual fetch sequence —
the quantity the ``--expert-hbm-budget`` contract bounds.  On a real
accelerator the same ledger drives ``jax.device_put`` onto the window's
donated slots; the CPU simulation realizes each fetch through the
callback result buffer and keeps the ledger as the budget model.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

# the pooled (paged) leaves of one MoE layer's param dict, in fetch order
EXPERT_LEAF_NAMES = ("experts_gate", "experts_up", "experts_down")


class PagingFetchError(RuntimeError):
    """A paging fetch failed (injected or real) past every retry, with the
    stale-shard fallback disabled.  Carrying a dedicated type lets the
    serving loop and tests distinguish a fetch fault from engine bugs."""


@dataclass(frozen=True)
class PagingSpec:
    """Planned paging shape of a run.  Hashable and StepPlan-static: it is
    stamped onto every :class:`repro.core.plan.LayerAction` (like
    ``codec`` / ``placement`` / ``overlap``) so the jit cache stays at
    the plan-variant count.

    budget_bytes
        per-device HBM budget for resident routed-expert shards; the
        pool validates every planned residency window against it and the
        realized peak must stay <= it.  ``None`` is unbounded (paging
        for the ``E % n_dev`` decoupling alone); ``0`` is the "auto"
        sentinel entry points resolve to the tightest feasible budget.
    depth
        prefetch distance in MoE layers: layer ``i`` issues the fetch of
        layer ``i + depth`` before its own compute, and each device keeps
        ``depth + 1`` layer-shard slots resident.
    """
    budget_bytes: Optional[int] = None
    depth: int = 1

    def __post_init__(self):
        if self.depth < 1:
            raise ValueError(f"paging depth must be >= 1, got {self.depth}")
        if self.budget_bytes is not None and self.budget_bytes < 0:
            raise ValueError(
                f"expert HBM budget must be >= 0, got {self.budget_bytes}")


def _pad_expert_dim(arr: np.ndarray, e_pad: int) -> np.ndarray:
    if arr.shape[0] == e_pad:
        return np.ascontiguousarray(arr)
    pad = np.zeros((e_pad - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.ascontiguousarray(np.concatenate([arr, pad], axis=0))


class ExpertPool:
    """Host-RAM owner of every routed-expert stack, serving per-device
    per-layer shards to the traced step.

    ``layers`` maps MoE layer index -> ``{"experts_gate": (E, d, f),
    "experts_up": (E, d, f), "experts_down": (E, f, d)}`` host numpy
    arrays (the FULL expert set); the pool pads each to ``E_pad`` (next
    multiple of ``n_dev``) with zero-weight phantom experts so device
    ``j`` always owns the contiguous shard ``[j * e_loc, (j+1) * e_loc)``
    of a cleanly divisible stack.
    """

    def __init__(self, layers: Dict[int, Dict[str, np.ndarray]],
                 *, n_dev: int):
        if n_dev < 1:
            raise ValueError(f"n_dev must be >= 1, got {n_dev}")
        if not layers:
            raise ValueError("ExpertPool needs at least one MoE layer")
        self.n_dev = n_dev
        first = layers[min(layers)]["experts_gate"]
        self.num_experts = int(first.shape[0])
        self.e_pad = -(-self.num_experts // n_dev) * n_dev
        self.e_loc = self.e_pad // n_dev
        self._layers: Dict[int, Dict[str, np.ndarray]] = {}
        for i, leaves in layers.items():
            got = {k: np.asarray(v) for k, v in leaves.items()}
            missing = [k for k in EXPERT_LEAF_NAMES if k not in got]
            if missing:
                raise ValueError(f"MoE layer {i} is missing expert leaves "
                                 f"{missing}")
            if got["experts_gate"].shape[0] != self.num_experts:
                raise ValueError(
                    f"MoE layer {i} has {got['experts_gate'].shape[0]} "
                    f"experts, layer {min(layers)} has {self.num_experts}; "
                    f"the pool requires a uniform expert count")
            self._layers[i] = {k: _pad_expert_dim(got[k], self.e_pad)
                               for k in EXPERT_LEAF_NAMES}
        # -- transfer + residency ledger (guarded: callbacks run on the
        # runtime's per-device threads) ---------------------------------
        self._lock = threading.Lock()
        self.transfers = 0
        self.bytes_transferred = 0
        self._resident: Dict[int, list] = {}      # dev -> [layer, ...] window
        self._resident_window = 2                  # depth + 1, set per run
        self._peak_resident = 0
        # optional repro.obs.trace.StepTracer: each io_callback fetch
        # emits a span from the runtime thread it runs on (DESIGN.md
        # Sec. 16); None keeps the fetch path free of any obs work
        self.tracer = None
        # -- resilience (DESIGN.md Sec. 17): retry/backoff/deadline policy
        # + seeded fault injection, set per run via set_resilience() -----
        self.resilience = None            # Optional[ResilienceConfig]
        self.fault_plan = None            # Optional[FaultPlan]
        self.fetch_errors = 0             # failed fetch attempts (incl. retried)
        self.fetch_retries = 0            # re-attempts issued
        self.stale_fallbacks = 0          # fetches served from the stale shard
        self._fetch_seq: Dict[Tuple[int, int], int] = {}  # (dev, layer) -> n

    # ------------------------------------------------------------------
    # geometry
    # ------------------------------------------------------------------
    @property
    def num_layers(self) -> int:
        return len(self._layers)

    @property
    def layer_indices(self) -> Tuple[int, ...]:
        return tuple(sorted(self._layers))

    @property
    def num_wire_experts(self) -> int:
        """Padded expert count — the wire/dispatch-buffer expert space."""
        return self.e_pad

    def shard_shape_dtypes(self, layer: int):
        """(shape, dtype) of the three per-device shards of ``layer``, in
        :data:`EXPERT_LEAF_NAMES` order — the callback result spec."""
        lv = self._layers[layer]
        return tuple(((self.e_loc,) + lv[k].shape[1:], lv[k].dtype)
                     for k in EXPERT_LEAF_NAMES)

    def layer_shard_bytes(self, layer: int) -> int:
        """Per-device HBM bytes one resident layer-shard occupies."""
        return sum(int(np.prod(shape)) * np.dtype(dt).itemsize
                   for shape, dt in self.shard_shape_dtypes(layer))

    def window_bytes(self, layers) -> int:
        """Per-device bytes of a residency window (a set of layer indices
        simultaneously resident)."""
        return sum(self.layer_shard_bytes(i) for i in layers)

    def min_budget_bytes(self, depth: int = 1) -> int:
        """The tightest feasible per-device budget for ``depth``-ahead
        prefetch: the largest (depth+1)-layer sliding window."""
        idx = self.layer_indices
        win = depth + 1
        return max(self.window_bytes(idx[i:i + win])
                   for i in range(len(idx)))

    def total_host_bytes(self) -> int:
        return sum(v.nbytes for lv in self._layers.values()
                   for v in lv.values())

    # ------------------------------------------------------------------
    # plan validation
    # ------------------------------------------------------------------
    def validate_actions(self, actions) -> None:
        """Check every planned residency window fits the budget (raises
        before compile rather than overflowing HBM mid-run), and size the
        ledger's eviction window from the plan's depth."""
        for a in actions:
            spec = getattr(a, "paging", None)
            if spec is None:
                continue
            self._resident_window = max(self._resident_window,
                                        spec.depth + 1)
            resident = getattr(a, "resident", None)
            if spec.budget_bytes and resident:
                need = self.window_bytes(resident)
                if need > spec.budget_bytes:
                    raise ValueError(
                        f"expert HBM budget {spec.budget_bytes} cannot hold "
                        f"the planned residency window {tuple(resident)} "
                        f"({need} bytes/device); the tightest feasible "
                        f"budget at depth {spec.depth} is "
                        f"{self.min_budget_bytes(spec.depth)} bytes")

    def validate_plan(self, splan) -> None:
        for variant in splan.variants:
            self.validate_actions(variant.actions)

    # ------------------------------------------------------------------
    # resilience policy (DESIGN.md Sec. 17)
    # ------------------------------------------------------------------
    def set_resilience(self, res) -> None:
        """Install (or clear, with None) the run's ResilienceConfig; a
        FaultPlan is derived from its seeded FaultConfig when present."""
        from repro.resilience.faults import FaultPlan
        with self._lock:
            self.resilience = res
            self.fault_plan = (FaultPlan(res.faults) if res is not None
                               and res.faults is not None else None)

    # ------------------------------------------------------------------
    # host-side fetch (the io_callback target)
    # ------------------------------------------------------------------
    def _slice_shards(self, layer: int, j: int):
        """The pure copy: this device's contiguous shard of each expert
        leaf.  No ledger side effects — callers reserve/commit around it."""
        lo = j * self.e_loc
        hi = lo + self.e_loc
        return tuple(np.ascontiguousarray(self._layers[layer][k][lo:hi])
                     for k in EXPERT_LEAF_NAMES)

    def _reserve(self, j: int, layer: int) -> bool:
        """Claim a residency-window slot for ``layer`` on device ``j``
        BEFORE the fallible copy (the budget model: the HBM slot is held
        for the duration of the fetch).  Returns whether the layer was
        already resident, so a failed fetch can release exactly what it
        claimed."""
        with self._lock:
            window = self._resident.setdefault(j, [])
            was_resident = layer in window
            if was_resident:
                window.remove(layer)        # re-fetch refreshes residency
            window.append(layer)
            while len(window) > self._resident_window:
                window.pop(0)
            return was_resident

    def _release(self, j: int, layer: int, was_resident: bool) -> None:
        """Undo a reservation after a failed fetch: the claimed slot must
        not leak into the budget ledger across retries (a fetch that never
        delivered bytes never occupied its slot)."""
        with self._lock:
            window = self._resident.get(j, [])
            if not was_resident and layer in window:
                window.remove(layer)

    def _commit(self, j: int, nbytes: int) -> None:
        """Record a delivered fetch: transfer counters plus the realized
        residency peak (measured at commit, when the bytes truly land)."""
        with self._lock:
            self.transfers += 1
            self.bytes_transferred += nbytes
            live = self.window_bytes(self._resident.get(j, ()))
            if live > self._peak_resident:
                self._peak_resident = live

    def _fetch_host(self, layer: int, dev: np.ndarray):
        """Reserve -> (fallible copy, with injection/retry/backoff under a
        deadline) -> commit.  On exhaustion the reservation is released
        and, when the resilience policy allows it, the still-resident
        stale shard is served instead of crashing the engine — expert
        weights are static, so the fallback is numerically identical and
        only *recorded* as extra staleness (DESIGN.md Sec. 17 rung 1)."""
        tracer = self.tracer
        t_fetch = tracer.now() if tracer is not None else 0.0
        j = int(dev)
        res = self.resilience
        fplan = self.fault_plan
        with self._lock:
            seq = self._fetch_seq[(j, layer)] = \
                self._fetch_seq.get((j, layer), 0) + 1
        was_resident = self._reserve(j, layer)
        retries = res.paging_retries if res is not None else 0
        deadline = res.paging_deadline_s if res is not None else 0.0
        t_start = time.perf_counter()
        err = None
        for attempt in range(retries + 1):
            try:
                if fplan is not None:
                    if fplan.paging_delay(layer, j, seq, attempt):
                        time.sleep(fplan.cfg.paging_delay_s)
                    if fplan.paging_error(layer, j, seq, attempt):
                        raise PagingFetchError(
                            f"injected paging fetch fault (layer {layer}, "
                            f"dev {j}, seq {seq}, attempt {attempt})")
                shards = self._slice_shards(layer, j)
                nbytes = sum(s.nbytes for s in shards)
                self._commit(j, nbytes)
                if tracer is not None:
                    tracer.complete("paged_fetch", t_fetch, cat="paging",
                                    args={"layer": layer, "dev": j,
                                          "bytes": nbytes,
                                          "attempt": attempt})
                return shards
            except PagingFetchError as e:
                err = e
                with self._lock:
                    self.fetch_errors += 1
                if attempt < retries:
                    backoff = (res.paging_backoff_s * (2 ** attempt)
                               if res is not None else 0.0)
                    if deadline > 0 and (time.perf_counter() - t_start
                                         + backoff) > deadline:
                        break               # retrying would bust the deadline
                    with self._lock:
                        self.fetch_retries += 1
                    if backoff > 0:
                        time.sleep(backoff)
        # every retry failed (or the deadline cut them short): release the
        # reservation so the claimed window slot cannot leak budget
        self._release(j, layer, was_resident)
        if res is not None and res.stale_fallback:
            with self._lock:
                self.stale_fallbacks += 1
            if tracer is not None:
                tracer.complete("paged_fetch_fallback", t_fetch,
                                cat="paging",
                                args={"layer": layer, "dev": j})
            # the weights are static host arrays, so the "stale" shard is
            # bit-identical data; no transfer is counted (nothing new
            # crossed the wire) and the degradation surfaces only in the
            # stale_fallbacks counter / obs
            return self._slice_shards(layer, j)
        raise err

    @property
    def peak_resident_bytes(self) -> int:
        """Realized per-device peak of the residency ledger — max over
        devices of the bytes simultaneously held in layer-shard slots."""
        with self._lock:
            return self._peak_resident

    def reset_stats(self) -> None:
        with self._lock:
            self.transfers = 0
            self.bytes_transferred = 0
            self._resident = {}
            self._peak_resident = 0
            self.fetch_errors = 0
            self.fetch_retries = 0
            self.stale_fallbacks = 0
            self._fetch_seq = {}

    # ------------------------------------------------------------------
    # traced fetch
    # ------------------------------------------------------------------
    def device_fetch(self, layer: int, *, ep_axis: str):
        """Fetch this device's shard of ``layer`` from inside the traced
        step (call within shard_map).  Returns the three expert leaves as
        a dict.  The callback result depends only on the static layer
        index and the device's axis position — no data dependency on the
        surrounding computation — so issuing it ``depth`` layers ahead
        lets XLA overlap the host->device transfer with the intervening
        layers' collectives and GEMMs (ordered=False: fetches may
        reorder/overlap freely; every fetch is idempotent).
        """
        import jax
        from jax.experimental import io_callback

        j = jax.lax.axis_index(ep_axis)
        result_shapes = tuple(
            jax.ShapeDtypeStruct(shape, dt)
            for shape, dt in self.shard_shape_dtypes(layer))
        shards = io_callback(functools.partial(self._fetch_host, layer),
                             result_shapes, j, ordered=False)
        return dict(zip(EXPERT_LEAF_NAMES, shards))


# ---------------------------------------------------------------------------
# params <-> pool plumbing
# ---------------------------------------------------------------------------
def has_expert_leaves(params) -> bool:
    blocks = params.get("blocks", ())
    return any(any(k in blk.get("moe", {}) for k in EXPERT_LEAF_NAMES)
               for blk in blocks)


def pool_from_params(params, *, n_dev: int) -> ExpertPool:
    """Build the pool from a full DiT-MoE param tree (host copies of every
    ``experts_*`` stack; ``params`` itself is not mutated)."""
    import jax
    layers = {}
    for i, blk in enumerate(params["blocks"]):
        moe = blk["moe"]
        layers[i] = {k: np.asarray(jax.device_get(moe[k]))
                     for k in EXPERT_LEAF_NAMES if k in moe}
    return ExpertPool(layers, n_dev=n_dev)


def strip_expert_params(params):
    """The device-resident remainder: ``params`` minus the pooled routed-
    expert stacks (router, shared experts, attention, embeddings stay).
    Shallow-copies containers; leaf arrays are shared, not copied."""
    out = dict(params)
    out["blocks"] = [
        dict(blk, moe={k: v for k, v in blk["moe"].items()
                       if k not in EXPERT_LEAF_NAMES})
        for blk in params["blocks"]
    ]
    return out


def split_params(params, expert_pool: Optional[ExpertPool], n_ep: int):
    """The paging split on an ``n_ep``-way ep axis: ``(device-resident
    params, pool)``.  The pool is built from the routed-expert stacks of
    ``params`` unless one is given, and must be sharded for ``n_ep``."""
    if expert_pool is None and has_expert_leaves(params):
        expert_pool = pool_from_params(params, n_dev=n_ep)
    if expert_pool is None:
        raise ValueError("paging is configured but params carry no expert "
                         "leaves and no expert_pool was provided")
    if expert_pool.n_dev != n_ep:
        raise ValueError(
            f"expert pool is sharded for {expert_pool.n_dev} devices but "
            f"the serving mesh has a {n_ep}-way ep axis")
    return strip_expert_params(params), expert_pool


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


def load_pooled_checkpoint(path: str, like, n_dev: int):
    """Streamed checkpoint restore straight into the paging split.

    Walks the checkpoint's leaves one at a time (validated against
    ``like`` — treedef / leaf count / dtypes / shapes — before the first
    buffer is read) and routes each as it arrives: routed-expert stacks
    into the host pool, everything else into the device-resident param
    tree.  Peak host memory is ONE leaf plus the pool built so far — the
    whole tree is never materialized (the restore-only streaming
    pattern; DESIGN.md Sec. 15).  Returns ``(stripped_params, pool)``.
    """
    import jax
    import jax.numpy as jnp
    from repro.checkpoint.io import load_checkpoint_leaves

    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    pool_layers: Dict[int, Dict[str, np.ndarray]] = {}
    out_leaves = []
    for (leaf_path, _), arr in zip(flat,
                                   load_checkpoint_leaves(path, like)):
        name = _leaf_name(leaf_path)
        names = [str(getattr(k, "key", getattr(k, "idx", "")))
                 for k in leaf_path]
        if name in EXPERT_LEAF_NAMES and "blocks" in names:
            layer = next(int(getattr(k, "idx"))
                         for k in leaf_path if hasattr(k, "idx"))
            pool_layers.setdefault(layer, {})[name] = arr
            out_leaves.append(None)        # placeholder, stripped below
        else:
            out_leaves.append(jnp.asarray(arr))
    restored = jax.tree_util.tree_unflatten(treedef, out_leaves)
    stripped = strip_expert_params(restored)
    return stripped, ExpertPool(pool_layers, n_dev=n_dev)


# ---------------------------------------------------------------------------
# config plumbing (mirrors plan.overlap_of; plan.resolve strips paging
# off an n>1 ep axis)
# ---------------------------------------------------------------------------
def paging_of(dcfg) -> Optional[PagingSpec]:
    """The planned paging spec of ``dcfg``, or None.  ``getattr`` so
    pre-paging config objects (and test doubles) keep planning
    unchanged."""
    return getattr(dcfg, "paging", None)


def resolve_budget(dcfg, pool: ExpertPool):
    """Resolve the ``budget_bytes == 0`` "auto" sentinel to the tightest
    feasible per-device budget for the pool's geometry — the largest
    (depth+1)-layer residency window.  A no-op on explicit budgets and
    unbounded (None) specs.  Must run BEFORE plans are compiled: the
    resolved spec is stamped into every LayerAction."""
    spec = paging_of(dcfg)
    if spec is None or spec.budget_bytes != 0:
        return dcfg
    return dataclasses.replace(
        dcfg, paging=dataclasses.replace(
            spec, budget_bytes=pool.min_budget_bytes(spec.depth)))
