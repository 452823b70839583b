"""MoE offloading engine — the paper's system, end to end.

Runs decode for an MoE decoder (Mixtral family: GQA/MLA attention +
MoE FFN) with experts offloaded to an ``ExpertStore`` and streamed
through per-layer ``ExpertCache``s under a pluggable policy, with
optional speculative (gate-ahead) or Markov pre-fetching. Every step is
traced; simulated wall time comes from the ``CostModel`` (trace-level
behaviour is real, transfer latency is modeled — DESIGN.md §9).

Control plane = host Python (policy decisions, routing readback at
batch≤8 decode, prefetch scheduling); data plane = jitted JAX (attention,
expert GEMMs, slot updates).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_policies import CachePolicy, make_policy
from repro.core.costmodel import CostModel, HardwareProfile, ModelBytes
from repro.core.expert_cache import ExpertCache
from repro.core.expert_store import ExpertStore
from repro.core.faults import as_injector
from repro.core.prefetch import (LearnedPredictor, MarkovPredictor,
                                 SpeculativePrefetcher)
from repro.core.trace import TraceRecorder
from repro.core.transfer_engine import TransferEngine
from repro.kernels import ops
from repro.models import moe as moe_lib
from repro.models import transformer as tf
from repro.models.layers import rms_norm, sinusoidal_positions, swiglu


def _layer_slice(tree, i):
    return jax.tree.map(lambda x: x[i], tree)


def _without_experts(params):
    """The param tree minus the stacked routed experts (they live in an
    ``ExpertStore``, never on the device with the rest)."""
    moe = {k: v for k, v in params["layers"]["moe"].items()
           if k != "experts"}
    return {**params, "layers": {**params["layers"], "moe": moe}}


def init_offloaded_params(cfg, key, *, quant: str = "none"):
    """Seeded MoE model built for offloading: ``(params, store)``.

    ``params`` is ``tf.init_params(cfg, key)`` without the routed
    experts (jitted, so the dropped expert init is dead code the
    compiler removes); each ``(layer, expert)`` is drawn on the device
    alone, from ``key`` folded with its layer and expert ids (same
    distribution as ``init_moe``'s stacked experts), and moved
    straight into the host ``store``. No more than one expert is ever
    on the device, so full-width models whose experts exceed device
    memory can be built. Pass both to ``OffloadEngine(params, cfg,
    store=store, ...)``."""
    from repro.models.layers import dense_init
    params = jax.jit(lambda k: _without_experts(tf.init_params(cfg, k)))(key)
    d, ff = cfg.d_model, cfg.expert_d_ff
    dtype = jnp.dtype(cfg.dtype)
    res_scale = 1.0 / math.sqrt(2 * cfg.num_layers)

    @jax.jit
    def one_expert(k):
        k1, k3, k2 = jax.random.split(k, 3)
        return {"w1": dense_init(k1, (d, ff), d, dtype=dtype),
                "w3": dense_init(k3, (d, ff), d, dtype=dtype),
                "w2": dense_init(k2, (ff, d), ff, scale=res_scale,
                                 dtype=dtype)}

    k_exp = jax.random.fold_in(key, 2**31 - 1)  # apart from init_params'
    store = ExpertStore(quant=quant)
    for l in filter(cfg.has_moe, range(cfg.num_layers)):
        k_l = jax.random.fold_in(k_exp, l)
        for e in range(cfg.num_experts):
            store.put((l, e), one_expert(jax.random.fold_in(k_l, e)))
    return params, store


@functools.partial(jax.jit, static_argnames=("impl",))
def _grouped_ffn(xf, w1, w3, w2, comb, *, impl: str = "xla"):
    """xf [B,d]; w* [U,d,ff]/[U,ff,d]; comb [B,U] -> y [B,d].

    The resident-expert FFN goes through the grouped SwiGLU kernel
    (``ops.moe_ffn``: Pallas on TPU, batched-dot XLA or the einsum
    oracle elsewhere — ``impl`` selects). Capacity dispatch is the
    full decode batch: x broadcasts to [U,B,d] (decode batches are
    <= 8 rows, so every expert computing every row is cheaper than a
    gather), and the combine matrix mixes each row's top-k outputs.
    """
    x_e = jnp.broadcast_to(xf[None], (w1.shape[0],) + xf.shape)
    out = ops.moe_ffn(x_e, w1, w3, w2, impl=impl)
    return jnp.einsum("ubd,bu->bd", out, comb)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(ln2, mlp, h, *, eps: float):
    """A dense layer's FFN with its norm and residual, one program."""
    return h + swiglu(mlp, rms_norm(h, ln2, eps))


@jax.jit
def _shared_ffn(shared, x, h):
    """The shared experts (one SwiGLU of their summed width) on the
    layer's normed input, added to the residual, one program."""
    return h + swiglu(shared, x)


def _batch_union(ids: np.ndarray, probs: np.ndarray,
                 active: Sequence[bool], num_experts: int
                 ) -> Tuple[List[int], np.ndarray]:
    """Union of the ACTIVE rows' experts, most-weighted first.

    Returns ``(union, w)`` where ``w`` [E] float64 holds each expert's
    summed gate weight. Pure-numpy replacement for the PR 1 Python
    loops, bit-identical with them (regression-tested): weights
    accumulate in float64 in row-major (b, j) order — the loop's
    ``weight_by_e[e] += float(probs[b, j])`` — and weight ties break
    by FIRST OCCURRENCE in that scan order, which is exactly the
    stable-sort-over-dict-insertion-order the loop relied on.
    """
    act = np.asarray(active, bool)
    flat = ids[act].ravel()
    w = np.zeros(num_experts, np.float64)
    np.add.at(w, flat, probs[act].ravel().astype(np.float64))
    first = np.full(num_experts, flat.size, np.int64)
    np.minimum.at(first, flat, np.arange(flat.size))
    present = np.flatnonzero(first < flat.size)
    order = np.lexsort((first[present], -w[present]))
    return [int(e) for e in present[order]], w


def _combine_matrix(chunk: Sequence[int], ids: np.ndarray, probs: np.ndarray,
                    active: Sequence[bool], num_experts: int) -> np.ndarray:
    """[B, len(chunk)] float32 combine weights: row b mixes chunk
    column j with the gate prob of that expert if row b routed to it
    (0 otherwise; inactive rows are all-zero). Numpy scatter in the
    same row-major order as the PR 1 loop — bit-identical."""
    B = ids.shape[0]
    act = np.asarray(active, bool)
    col = np.full(num_experts, -1, np.int64)
    col[np.asarray(chunk, np.int64)] = np.arange(len(chunk))
    cols = col[ids]                                   # [B, k]
    m = (cols >= 0) & act[:, None]
    rows = np.broadcast_to(np.arange(B)[:, None], cols.shape)
    comb = np.zeros((B, len(chunk)), np.float32)
    np.add.at(comb, (rows[m], cols[m]), probs[m])
    return comb


class OffloadEngine:
    def __init__(self, params, cfg, *,
                 cache_slots,  # int, or per-layer Sequence[int]
                 policy: str = "lru",
                 policy_kw: Optional[dict] = None,
                 policy_factory: Optional[Callable[[int], CachePolicy]] = None,
                 quant: str = "none",
                 prefetch: Optional[str] = None,  # None|"spec"|"markov"|"learned"
                 learned_model=None,   # repro.core.learned.LearnedModel
                 hw: Optional[HardwareProfile] = None,
                 overlap: bool = False,
                 ffn_impl: str = "xla",  # "xla"|"ref"|"pallas"|"pallas_interpret"
                 trace: Optional[TraceRecorder] = None,
                 tiers=None,   # repro.core.memory_tiers.TieredMemoryManager
                 faults=None,  # FaultPlan | FaultInjector | None
                 store: Optional[ExpertStore] = None,
                 seed: int = 0):
        """``params`` either holds the stacked experts, which move to a
        new host ``ExpertStore``, or comes with the ``store`` that
        already holds them (``init_offloaded_params``). Either way the
        device keeps only the non-expert parameters.

        The experts this engine computes are the ones the store holds:
        the router ranks all ``num_experts``, and a row's choices that
        the store lacks (experts held on other chips of an
        expert-parallel deployment) are left out of its result. Every
        MoE layer holds the same ids. Layers below
        ``first_k_dense_replace`` are dense (``params["dense_layers"]``)
        and have no cache (``caches[l] is None``) and no trace record."""
        assert cfg.is_moe, "offloading targets MoE experts"
        if prefetch not in (None, "spec", "markov", "learned"):
            raise ValueError(
                f"unknown prefetch={prefetch!r}: expected one of "
                f"None, 'spec', 'markov', 'learned'")
        if ffn_impl not in ("xla", "ref", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown ffn_impl={ffn_impl!r}: expected one of "
                f"'xla', 'ref', 'pallas', 'pallas_interpret'")
        if store is not None and store.quant != quant:
            raise ValueError(f"quant={quant!r} but the given store holds "
                             f"quant={store.quant!r} experts")
        self.cfg = cfg
        if isinstance(cache_slots, int):
            if cache_slots < 1:
                raise ValueError(
                    f"cache_slots must be >= 1, got {cache_slots}")
            slots = [cache_slots] * cfg.num_layers
        else:
            slots = list(cache_slots)
            assert len(slots) == cfg.num_layers
            if any(s < 1 for s in slots):
                raise ValueError(
                    f"per-layer cache_slots must all be >= 1, got {slots}")
        self.prefetch_mode = prefetch
        self.trace = trace if trace is not None else TraceRecorder()
        # one injector shared by caches, transfer engine and tier
        # arbiter, so fault-event indices are globally consistent;
        # None (the default) keeps every path bit-identical to pre-fault
        self.faults = as_injector(faults, trace=self.trace)
        self.store = (store if store is not None
                      else ExpertStore.from_params(params, cfg, quant=quant))
        self.params = _without_experts(params)
        self.dtype = self.params["embed"].dtype
        self.n_dense = (jax.tree.leaves(params["dense_layers"])[0].shape[0]
                        if "dense_layers" in params else 0)
        held = {l: [] for l in range(self.n_dense, cfg.num_layers)}
        for l, e in self.store.keys():
            held[l].append(e)
        sets = {tuple(sorted(v)) for v in held.values()}
        if len(sets) != 1 or () in sets:
            raise ValueError("every MoE layer must hold the same non-empty "
                             f"set of experts; the store holds {held}")
        self.held = sets.pop()
        self._held_mask = np.zeros(cfg.num_experts, bool)
        self._held_mask[list(self.held)] = True
        # per-layer budgets (beyond paper: skewed layers need fewer slots)
        self.slots = [max(1, min(s, len(self.held))) for s in slots]
        self.cache_slots = (sum(self.slots[self.n_dense:])
                            / (cfg.num_layers - self.n_dense))

        d, ff = cfg.d_model, cfg.expert_d_ff
        shapes = {"w1": (d, ff), "w3": (d, ff), "w2": (ff, d)}
        pkw = dict(policy_kw or {})
        if policy == "learned" and learned_model is not None:
            pkw.setdefault("model", learned_model)
        self.caches: List[Optional[ExpertCache]] = [None] * self.n_dense
        for l in range(self.n_dense, cfg.num_layers):
            pol = (policy_factory(l) if policy_factory is not None
                   else make_policy(policy, self.slots[l], **pkw))
            self.caches.append(ExpertCache(l, self.slots[l], pol,
                                           self.store, shapes,
                                           faults=self.faults))

        mb = ModelBytes.from_config(cfg)
        eb = self.store.expert_nbytes(self.store.keys()[0])
        mb = ModelBytes(**{**mb.__dict__, "expert_bytes": eb})
        self.cost = CostModel(hw or HardwareProfile.a6000_pcie4(), mb,
                              overlap=overlap)
        self.overlap = overlap
        self.ffn_impl = ffn_impl
        # host->device expert copy engine (the executed overlap
        # pipeline's clock; idle when overlap=False — the synchronous
        # path keeps the analytic step_latency accounting exactly)
        self.xfer = TransferEngine(lanes=2, faults=self.faults)
        self._clock = 0.0                 # per-step pipeline clock
        self.transfer_busy_s = 0.0        # DMA seconds issued
        self.exposed_transfer_s = 0.0     # DMA seconds the clock saw
        self.sim_time = 0.0
        self.tokens_done = 0
        self._steps_done = 0
        self.degraded_tokens = 0          # tokens decoded w/ dropped experts
        self._step_fault_stall_s = 0.0    # sync-path fault extras this step
        self.spec = SpeculativePrefetcher(cfg) if prefetch == "spec" else None
        self.markov = (MarkovPredictor(cfg.num_layers, cfg.num_experts,
                                       cfg.num_experts_per_tok)
                       if prefetch == "markov" else None)
        self.learned = (LearnedPredictor(cfg.num_layers, cfg.num_experts,
                                         cfg.num_experts_per_tok,
                                         model=learned_model)
                        if prefetch == "learned" else None)
        self._prompt_id = 0
        self._rng = np.random.default_rng(seed)
        self._prev_acts: Dict[int, Tuple[int, ...]] = {}
        self.tiers = None
        if tiers is not None:
            self.attach_tiers(tiers)

    def attach_tiers(self, tiers) -> None:
        """Wire a ``TieredMemoryManager`` in: register every expert's
        master copy (real store bytes) and point the per-layer caches
        at the arbiter. Call once, before any decoding."""
        assert self.tiers is None, "tiers already attached"
        self.tiers = tiers
        if tiers.trace is None:
            tiers.trace = self.trace
        for key in self.store.keys():
            tiers.register_expert(key, self.store.expert_nbytes(key))
        for c in self.moe_caches:
            c.tiers = tiers
        if self.faults is not None and getattr(tiers, "queue", None) is not None:
            # KV parks / disk spills ride the same injector (their
            # chains never abandon — a parked snapshot is the only copy)
            tiers.queue.faults = self.faults

    @property
    def moe_caches(self) -> List[ExpertCache]:
        """The caches of the layers with routed experts."""
        return [c for c in self.caches if c is not None]

    def _block(self, l: int):
        """Layer ``l``'s device parameters (the dense stack first)."""
        if l < self.n_dense:
            return _layer_slice(self.params["dense_layers"], l)
        return _layer_slice(self.params["layers"], l - self.n_dense)

    # ------------------------------------------------------------------
    def init_state(self, batch: int, cache_len: int):
        state = tf.init_decode_state(self.params, self.cfg, batch, cache_len,
                                     dtype=self.dtype)
        # unstack attention caches into a python list for per-layer updates
        layers = [
            _layer_slice(state["layers"], l) for l in range(self.cfg.num_layers)
        ]
        return {"layers": layers}

    def new_prompt(self, *, reset_context: bool = True) -> int:
        """Allocate a fresh prompt (request) id.

        ``reset_context=False`` keeps the Markov-prefetch context — the
        continuous server admits requests while others are mid-decode,
        and the layer-to-layer activation stream it predicts from is a
        shared-batch property, not a per-request one.
        """
        self._prompt_id += 1
        if reset_context:
            self.reset_prefetch_context()
        return self._prompt_id

    def reset_prefetch_context(self) -> None:
        """Forget the previous step's activations (Markov predictor
        input). The continuous server calls this when it goes idle so a
        request admitted to an empty batch sees the same prefetch state
        as a fresh ``generate`` call."""
        self._prev_acts = {}

    def _prefetch_ahead(self, layer: int, guess: Sequence[int],
                        pending: Dict) -> int:
        """Prefetch a guess for ``layer`` into its cache, record it in
        ``pending`` and, under overlap, issue its copies. Ids the store
        does not hold (experts of other chips) are dropped from the
        guess. Returns the number of experts moved."""
        guess = tuple(e for e in guess if self._held_mask[e])
        cache = self.caches[layer]
        moved = cache.prefetch(guess)
        pending[layer] = (guess, tuple(moved),
                          dict(cache.last_prefetch_outcomes))
        if self.overlap:
            self._issue_transfers(layer, moved, demand=False,
                                  outcomes=cache.last_prefetch_outcomes
                                  or None)
        return len(moved)

    # ------------------------------------------------------------------
    def _route(self, p_l, x) -> Tuple[np.ndarray, np.ndarray]:
        """x [B,1,d] -> (ids [B,k], weights [B,k]) on host, by the
        model's router (``moe.route``) over all ``num_experts``."""
        logits = np.asarray((x.astype(jnp.float32) @ p_l["moe"]["router"])[:, 0, :])
        probs, ids = moe_lib.route(logits, self.cfg, xp=np)
        return ids, probs

    def _issue_transfers(self, layer: int, eids: Sequence[int], *,
                         demand: bool, outcomes=None) -> None:
        """Submit host->device expert copies to the copy engine at the
        current pipeline clock (overlap mode only). Demand copies may
        displace queued prefetches; prefetches queue behind the lane
        tails. Keyed ``(layer, expert)`` so the consuming layer can ask
        when its working set is actually resident. ``outcomes`` maps
        expert id -> pre-planned ``FetchOutcome`` (fault injection): a
        retrying chain holds its lane longer, an abandoned one ends at
        the give-up time — the consumer discovers the failure then."""
        dur = self.cost.expert_transfer_time()
        nb = self.cost.mb.expert_bytes
        for e in eids:
            t = self.xfer.submit(self._clock, dur, key=(layer, int(e)),
                                 kind="expert", nbytes=nb, demand=demand,
                                 outcome=(outcomes or {}).get(int(e)))
            self.transfer_busy_s += t.duration

    def _moe_offloaded(self, p_l, layer: int, h,
                       pending_guess: Tuple[int, ...],
                       pending_moved: Tuple[int, ...],
                       pending_outcomes: Dict[int, object],
                       prompt_ids: Sequence[int],
                       token_indices: Sequence[int],
                       active: Sequence[bool]):
        """Batch-union MoE FFN over the shared per-layer cache.

        Inactive rows (free serving slots) route but contribute nothing:
        their experts never join the union and their combine weights are
        exactly zero, so active rows' outputs are independent of batch
        composition. The trace records the union access plus per-request
        attribution for each active row.

        With ``overlap=True`` this is one stage of the executed
        software pipeline: demand misses are ISSUED to the copy engine
        at the layer's start, compute proceeds immediately on the
        (functionally already-installed) union, and the clock stalls
        only for transfers still in flight when the FLOPs finish —
        ``stall = max(0, dma_done - compute_done)``, recorded per layer
        in the trace. The synchronous path exposes the full transfer
        time, exactly as ``CostModel.step_latency`` prices it.

        Under fault injection the layer's demand fetches are PRE-PLANNED
        (``ExpertCache.plan_fetches``): a fetch whose retry chain is
        abandoned drops its expert from this step's compute, and every
        affected row's combine weights are RENORMALIZED over the experts
        that did arrive (drop-missing-expert fallback — decode proceeds,
        degraded, never stalls forever). The dropped set and per-row
        degradation flags land in the trace for quality attribution.
        """
        cfg = self.cfg
        E = cfg.num_experts
        x = rms_norm(h, p_l["ln2"], cfg.norm_eps)
        with jax.profiler.TraceAnnotation("engine.route", layer=layer) as sp:
            ids, probs = self._route(p_l, x)   # [B,k]
            act = np.asarray(active, bool)
            here = self._held_mask[ids] & act[:, None]
            sp.set_metadata(held=int(here.sum()),
                            routed=int(act.sum()) * ids.shape[1])
        B = ids.shape[0]
        # choices held on other chips go to a column E that no chunk
        # holds: out of the union, zero in every combine
        cols = np.where(self._held_mask[ids], ids, E)

        # union of needed experts over ACTIVE rows, most-weighted first
        # (deterministic; first-occurrence order breaks weight ties)
        union, weight_of = _batch_union(cols, probs, active, E + 1)
        union = [e for e in union if e != E]

        cache = self.caches[layer]
        cache_before = cache.cached_ids()

        # fault injection: decide each demand fetch's fate BEFORE
        # compute, so the dropped set (abandoned chains) is known when
        # the combine weights are built ({} without an injector)
        fates = cache.plan_fetches(union)
        failed = {e for e, o in fates.items() if not o.success}
        scale = None
        if failed:
            # drop-missing-expert fallback: renormalize each affected
            # row's gate weights over the experts that did arrive; a
            # row that lost ALL its experts contributes zero MoE output
            avail = ~np.isin(ids, sorted(failed))          # [B,k]
            denom = (probs * avail).sum(axis=-1)           # [B]
            safe = np.where(denom > 0.0, denom, 1.0)
            scale = np.where(denom > 0.0, 1.0 / safe, 0.0)

        # working set may exceed the cache: stream it in chunks ≤ capacity
        hits: List[int] = []
        misses: List[int] = []
        evicted: List[int] = []
        miss_tiers: List[str] = []
        y = jnp.zeros((B, self.cfg.d_model), jnp.float32)
        cap = cache.n_slots
        for c0 in range(0, len(union), cap):
            chunk = union[c0:c0 + cap]
            # fault-free path keeps the pre-fault call shape (tests
            # monkeypatch ``access`` to drive Belady's cursor)
            h_, m_, e_ = (cache.access(chunk, outcomes=fates) if fates
                          else cache.access(chunk))
            hits += h_
            misses += m_
            evicted += e_
            miss_tiers += list(cache.last_miss_tiers)
            comp = ([e for e in chunk if e not in failed] if failed
                    else chunk)
            if not comp:
                continue
            with jax.profiler.TraceAnnotation(
                    "engine.ffn", layer=layer, experts=len(comp), rows=B):
                w = cache.gather(comp)
                comb = _combine_matrix(comp, cols, probs, active, E + 1)
                if scale is not None:
                    comb = (comb * scale[:, None]).astype(np.float32)
                y = y + _grouped_ffn(x[:, 0, :], w["w1"], w["w3"], w["w2"],
                                     jnp.asarray(comb), impl=self.ffn_impl)
        h = h + y[:, None, :].astype(h.dtype)

        # --- simulated pipeline clock for this layer ------------------
        n_active = sum(1 for a in active if a)
        t_comp = self.cost.layer_compute_time(n_active)
        if self.overlap:
            # demand misses hit the copy engine's priority class at the
            # layer's start (routing readback); already-issued
            # prefetches for this layer may still be in flight — both
            # only cost what outlives the layer's compute. Fault chains
            # ride the same lanes: retries hold them longer, an
            # abandoned chain ends at its give-up time.
            self._issue_transfers(layer, misses, demand=True,
                                  outcomes=fates or None)
            compute_done = self._clock + t_comp
            keys = [(layer, e) for e in union]
            stall_s, blockers = self.xfer.stall_until(keys, compute_done)
            self._clock = compute_done + stall_s
            inflight = tuple(sorted(int(k[1]) for k in blockers))
        else:
            # synchronous: every transfer of this layer is exposed on
            # the clock (the analytic step_latency accounting, sliced
            # per layer; the step's sim_time advance stays the exact
            # step_latency formula — byte-identical with pre-PR 9)
            stall_s = ((len(misses) + len(pending_moved))
                       * self.cost.expert_transfer_time())
            self.transfer_busy_s += stall_s
            inflight = ()
            if fates or pending_outcomes:
                # fault extras BEYOND the one-transfer-per-miss the
                # formula above prices: retries + backoff, plus whole
                # abandoned chains (their misses moved bytes too)
                base = self.cost.expert_transfer_time()
                extra = sum(o.extra_s(base, self.faults.plan)
                            for o in fates.values())
                extra += sum(o.extra_s(base, self.faults.plan)
                             for o in pending_outcomes.values())
                stall_s += extra
                self._step_fault_stall_s += extra
        self.exposed_transfer_s += stall_s
        if "shared" in p_l["moe"]:
            with jax.profiler.TraceAnnotation("engine.shared", layer=layer,
                                              rows=B):
                h = _shared_ffn(p_l["moe"]["shared"], x, h)

        # per-request attribution (slice of the union)
        req_ids = tuple(int(prompt_ids[b]) for b in range(B) if active[b])
        req_tok = tuple(int(token_indices[b]) for b in range(B) if active[b])
        req_act = tuple(tuple(sorted(int(e) for e in ids[b][here[b]]))
                        for b in range(B) if active[b])
        # per-row degradation flags (aligned with req_ids): a row is
        # degraded at this layer iff one of ITS routed experts dropped
        req_deg = (tuple(bool(not avail[b].all()) for b in range(B)
                         if active[b]) if failed else ())
        # legacy single-stream fields: exact when the step serves one
        # request (or several rows of one), sentinel otherwise
        pid = req_ids[0] if len(set(req_ids)) == 1 else -1
        tok = req_tok[0] if len(set(req_tok)) == 1 else self._steps_done

        acts = tuple(int(e) for e in union)
        self.trace.record(
            prompt_id=pid, token_idx=tok, layer=layer,
            activated=acts,
            gate_weights=tuple(float(weight_of[e]) for e in union),
            cache_before=cache_before, cache_after=cache.cached_ids(),
            hits=tuple(hits), misses=tuple(misses), evicted=tuple(evicted),
            spec_guess=tuple(pending_guess), prefetched=tuple(pending_moved),
            request_ids=req_ids, request_token_idx=req_tok,
            request_activated=req_act, engine_step=self._steps_done,
            # tier attribution only when an arbiter is attached, so
            # pre-tiering traces stay byte-identical
            miss_tiers=(tuple(miss_tiers) if self.tiers is not None else ()),
            stall_s=stall_s, inflight=inflight,
            # fault-free steps keep both empty so trace JSON stays
            # byte-identical with pre-fault output
            dropped=tuple(sorted(failed)), request_degraded=req_deg)
        return h, acts, len(misses), req_deg

    # ------------------------------------------------------------------
    def decode_token(self, state, token, pos: int, token_idx: int):
        """token [B,1] int32, all rows at the same position (the paper's
        single-stream setting). Returns (logits [B,V], state)."""
        B = token.shape[0]
        return self.decode_tokens(state, token,
                                  positions=[int(pos)] * B,
                                  token_indices=[int(token_idx)] * B)

    def decode_tokens(self, state, tokens, positions: Sequence[int],
                      token_indices: Optional[Sequence[int]] = None, *,
                      prompt_ids: Optional[Sequence[int]] = None,
                      active: Optional[Sequence[bool]] = None,
                      block_tables=None):
        """True B>1 decode over the shared per-layer expert caches.

        tokens [B,1] int32; ``positions[b]`` is row b's sequence position
        (rows may be staggered — continuous batching), ``token_indices[b]``
        its token index within its request — defaults to ``positions``,
        from which it only diverges once KV slots stop starting at
        position 0 (paged KV) — ``prompt_ids[b]`` its request
        id for trace attribution. ``active[b]=False`` marks a free
        serving slot: the row is decoded (static shapes) but routed
        nowhere, attends only to its own slot's KV rows, and is excluded
        from the union access, the trace, and the simulated clock.

        ``block_tables`` [B, T] int32 switches the KV path to a PAGED
        pool: ``state["layers"][l]`` must then be a per-layer block pool
        (see ``repro.core.paged_kv.PagedKVCache``) and row b's KV lives
        at the physical blocks ``block_tables[b]`` instead of slot b of
        a dense [B, cache_len] allocation. The paged path is bit-exact
        with the dense one, so everything downstream (routing, caches,
        trace, clock) is unchanged.
        Returns (logits [B,V], state).

        The call, and each layer's attention, MoE, routing, expert
        installs and FFN chunks, and the logits, are host spans
        (``engine.*``, ``expert_cache.install``) that a JAX profiler
        trace records (docs/traces.md, "Host spans").
        """
        cfg = self.cfg
        params = self.params
        B = tokens.shape[0]
        if token_indices is None:
            token_indices = positions
        if prompt_ids is None:
            prompt_ids = [self._prompt_id] * B
        if active is None:
            active = [True] * B
        n_active = sum(1 for a in active if a)
        assert n_active >= 1, "decode step with no active rows"
        with jax.profiler.TraceAnnotation("engine.decode", rows=n_active):
            pos_vec = jnp.asarray(list(positions), jnp.int32)

            h = params["embed"][tokens]
            if cfg.pos_emb == "sinusoidal":
                h = h + sinusoidal_positions(pos_vec[:, None],
                                             cfg.d_model).astype(h.dtype)

            # guesses issued at layer l are consumed at layer l+1 of the SAME
            # token pass (the prefetch travels ahead of the compute wavefront);
            # each entry is (guess, moved, fault outcomes of the moved ids)
            pending: Dict[int, Tuple[Tuple[int, ...], Tuple[int, ...], Dict]] = {}
            step_misses = 0
            step_prefetch = 0
            act_rows = np.asarray([b for b in range(B) if active[b]], np.int32)
            # the executed pipeline clock starts where the last step ended;
            # per-layer stages advance it by compute + exposed stall
            self._clock = self.sim_time
            self._step_fault_stall_s = 0.0
            step_degraded = [False] * n_active
            if self.faults is not None:
                self.faults.now = self.sim_time

            for l in range(cfg.num_layers):
                p_l = self._block(l)
                with jax.profiler.TraceAnnotation("engine.attention", layer=l):
                    if block_tables is None:
                        h, state["layers"][l] = tf._attn_decode_multipos(
                            p_l, cfg, h, state["layers"][l], pos_vec)
                    else:
                        h, state["layers"][l] = tf._attn_decode_paged(
                            p_l, cfg, h, state["layers"][l], pos_vec,
                            block_tables)

                # --- speculative guess for layer l+1 (paper §3.2) ---------
                if (self.spec is not None and l + 1 < cfg.num_layers
                        and self.caches[l + 1] is not None):
                    p_next = self._block(l + 1)
                    guess = self.spec.guess(h[act_rows], p_next["ln2"],
                                            p_next["moe"]["router"])
                    # issued before layer l's MoE computes: under overlap
                    # the copy has layer l's compute window to hide under
                    step_prefetch += self._prefetch_ahead(l + 1, guess,
                                                          pending)

                if self.caches[l] is None:
                    with jax.profiler.TraceAnnotation("engine.dense_ffn",
                                                      layer=l):
                        h = _dense_ffn(p_l["ln2"], p_l["mlp"], h,
                                       eps=cfg.norm_eps)
                    continue
                pg, pm, po = pending.get(l, ((), (), {}))
                with jax.profiler.TraceAnnotation("engine.moe", layer=l):
                    h, acts, misses, req_deg = self._moe_offloaded(
                        p_l, l, h, pg, pm, po, prompt_ids, token_indices,
                        active)
                step_misses += misses
                for i, d in enumerate(req_deg):
                    step_degraded[i] |= d
                predictor = self.markov if self.markov is not None else self.learned
                if predictor is not None:
                    if self.learned is not None:
                        # keep the learned feature walk aligned with training
                        self.learned.observe(l, acts)
                    if l > self.n_dense:
                        predictor.update(l - 1, self._prev_acts.get(l - 1, ()),
                                         acts)
                    if l + 1 < cfg.num_layers:
                        # predict l+1 from THIS token's layer-l set — the
                        # same-token l -> l+1 transition the table is
                        # trained on. (Guessing from self._prev_acts[l]
                        # here fed predict the PREVIOUS token's layer-l
                        # set: train/predict skew that wasted the learned
                        # transitions whenever consecutive tokens routed
                        # differently — regression-tested.)
                        # predicted AFTER layer l's MoE (the clock has
                        # advanced past it): under overlap the copy hides
                        # under layer l+1's attention + FFN compute
                        step_prefetch += self._prefetch_ahead(
                            l + 1, predictor.predict(l, acts), pending)
                self._prev_acts[l] = acts

            with jax.profiler.TraceAnnotation("engine.logits"):
                logits = tf.logits_from_hidden(params, cfg, h)[:, 0]

            # simulated clock: one step serves n_active tokens; misses are
            # already batch-union counts (amortization is emergent)
            if self.overlap:
                # executed pipeline: per-layer stages already advanced the
                # clock by compute + exposed stall; transfers that finished
                # under compute cost nothing (the analytic step_latency
                # formula is only the synchronous upper bound — validated
                # against this timeline in tests and bench_overlap)
                self.sim_time = self._clock
                self.xfer.advance(self.sim_time)
            else:
                self.sim_time += self.cost.step_latency(
                    step_misses / cfg.num_layers,
                    prefetch_per_layer=step_prefetch / cfg.num_layers,
                    batch=n_active)
                if self._step_fault_stall_s:
                    # retries/backoff/abandoned chains land ON TOP of the
                    # analytic formula (which prices one transfer per miss)
                    self.sim_time += self._step_fault_stall_s
            if self.faults is not None:
                self.faults.now = self.sim_time
                self.degraded_tokens += sum(1 for d in step_degraded if d)
            if self.tiers is not None:
                # tier stalls (disk-resident demand fetches, in-flight
                # demotion waits) land on top of the host-link pricing
                # above; then the arbiter's clock catches up so background
                # swaps complete
                self.sim_time += self.tiers.drain_stall()
                self.tiers.advance(self.sim_time)
            self.tokens_done += n_active
            self._steps_done += 1
            return logits, state

    # ------------------------------------------------------------------
    def prefill_tokens(self, state, tokens, positions: Sequence[int], *,
                       token_indices: Optional[Sequence[int]] = None,
                       prompt_ids: Optional[Sequence[int]] = None,
                       active: Optional[Sequence[bool]] = None,
                       block_tables=None):
        """Push N KNOWN tokens through ONE engine step (chunked prefill).

        ``tokens`` is a flat [N] (or [N,1]) int32 vector of *virtual
        rows*: row j is one known token at sequence position
        ``positions[j]``. Rows belonging to the same request (equal
        ``prompt_ids`` entries, consecutive positions, identical
        ``block_tables`` rows) form a chunk. Two properties make a
        chunk bit-exact with feeding the same tokens one step at a
        time (test-enforced, including after preemption replay):

        * the paged attention kernels scatter EVERY row's new K/V into
          the pool before any row gathers, and mask with
          ``idx <= pos`` — so within a step, later positions of a
          chunk see earlier ones' K/V and nothing of the future, and
        * a row's numerics are independent of the batch it is embedded
          in (the batched kernels are row-wise; empirically bitwise
          stable on this backend), so the virtual-row batch runs the
          *literally same* per-row computation as the one-token path.

        The MoE side is one batched union access per chunk: all rows'
        expert sets union into a single cache access per layer, so a
        chunk's misses are paid once, and the simulated clock prices
        one step serving N tokens — that amortization is the prefill
        throughput win.

        Requires paged KV (``block_tables`` [N, T]; replicate a
        request's block-table row across its chunk): dense layouts
        address KV by batch row, which virtual rows break. Returns
        (logits [N, V], state); callers sample from the LAST row of a
        request's final chunk and discard the rest.
        """
        assert block_tables is not None, \
            "chunked prefill requires a paged KV pool (block_tables)"
        toks = jnp.asarray(tokens, jnp.int32).reshape(-1, 1)
        return self.decode_tokens(state, toks, list(positions),
                                  token_indices=token_indices,
                                  prompt_ids=prompt_ids, active=active,
                                  block_tables=block_tables)

    # ------------------------------------------------------------------
    def generate(self, prompt: Sequence[int], n_new: int, *,
                 temperature: float = 0.0, seed: int = 0,
                 cache_len: Optional[int] = None) -> List[int]:
        """Single-sequence generation (the paper's batch-1 setting)."""
        cfg = self.cfg
        self.new_prompt()
        total = len(prompt) + n_new
        cache_len = cache_len or total
        state = self.init_state(1, cache_len)
        key = jax.random.PRNGKey(seed)
        out: List[int] = list(prompt)
        logits = None
        for i, t in enumerate(prompt):
            tok = jnp.asarray([[t]], jnp.int32)
            logits, state = self.decode_token(state, tok, i, i)
        for j in range(n_new):
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = int(jax.random.categorical(sub, logits / temperature, axis=-1)[0])
            else:
                nxt = int(jnp.argmax(logits, axis=-1)[0])
            out.append(nxt)
            pos = len(out) - 1
            tok = jnp.asarray([[nxt]], jnp.int32)
            logits, state = self.decode_token(state, tok, pos, pos)
        return out

    # ------------------------------------------------------------------
    def stats(self, *, kv_tokens: float = 0.0) -> Dict[str, float]:
        """Aggregate counters. ``kv_tokens`` is the peak number of KV
        token-slots resident alongside the experts (a serving layer
        passes its paged pool's peak block occupancy * block_size);
        the bare engine's dense per-call state is transient and priced
        at 0 by default."""
        caches = self.moe_caches
        hits = sum(c.hits for c in caches)
        misses = sum(c.misses for c in caches)
        pre = sum(c.prefetches for c in caches)
        prec, rec = self.trace.cache_precision_recall()
        sp, sr = self.trace.spec_precision_recall()
        s = {
            "hits": hits, "misses": misses, "prefetches": pre,
            "hit_rate": hits / max(hits + misses, 1),
            "cache_precision": prec, "cache_recall": rec,
            "spec_precision": sp, "spec_recall": sr,
            "bytes_transferred": sum(c.bytes_transferred for c in caches),
            "decode_steps": self._steps_done,
            # overlap pipeline accounting: DMA seconds issued vs the
            # fraction the simulated clock actually saw (== 1.0 on the
            # synchronous path, < 1.0 once transfers hide under compute)
            "transfer_busy_s": self.transfer_busy_s,
            "exposed_transfer_s": self.exposed_transfer_s,
            "exposed_transfer_frac": (self.exposed_transfer_s
                                      / self.transfer_busy_s
                                      if self.transfer_busy_s else 0.0),
            "dma_preempted": self.xfer.preempted,
            "sim_time_s": self.sim_time,
            "sim_tokens_per_s": self.tokens_done / self.sim_time
            if self.sim_time else 0.0,
            "peak_memory_bytes": self.cost.peak_memory_bytes(
                self.cfg.num_experts - self.cache_slots,
                kv_tokens=kv_tokens),
        }
        if self.tiers is not None:
            s.update(self.tiers.stats())
        if self.faults is not None:
            # health/degradation summary (keys absent without an
            # injector so pre-fault stats stay unchanged)
            s.update(self.faults.stats())
            s["fetch_failures"] = sum(c.fetch_failures for c in caches)
            s["corrupt_refetches"] = sum(c.corrupt_refetches
                                         for c in caches)
            s["degraded_tokens"] = self.degraded_tokens
            s["degraded_token_frac"] = (self.degraded_tokens
                                        / max(self.tokens_done, 1))
            s["dma_retries"] = self.xfer.retries
            s["dma_abandoned"] = self.xfer.abandoned
        return s
