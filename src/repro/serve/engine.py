"""Batched serving engine: continuous batching over a fixed-slot KV cache.

The engine keeps `slots` concurrent sequences. Each scheduler tick:
  1. admit queued requests into free slots (prompt tokens are injected
     through the decode path token-by-token — teacher-forced prefill — so
     one compiled decode_step serves both phases, for every family);
  2. run one batched decode_step for all active slots;
  3. retire sequences that hit max tokens or EOS.

Greedy or temperature sampling. This is the serving analogue the paper's
"job" maps onto for decode shapes, and the engine the serve_demo example
drives.

Telemetry (`telemetry=`, default the process registry): each tick is one
root span `engine.tick` (attributes `slots` stepped and output `tokens`
appended) with the children `engine.admit` (admission and slot resets),
`engine.dispatch` (the batch and the jitted step's async dispatch),
`engine.fetch` (the logits to the host: where the host waits on the
device) and `engine.sample` (the per-slot loop). The step is jitted as
`decode_step`, so profiler traces name its program `jit_decode_step`.
For a MoE model the step also returns, and `engine.fetch` copies beside
the logits, the pairs each MoE layer routed to each expert this model
holds (int32, MoE layers x held experts); the tick's span then carries
`held_routes` (pairs routed to held experts, summed over the layers) and
`experts_hit` (held experts with at least one pair, summed over the
layers). Other families fetch the logits alone.

Weights: the engine holds only the serving form of the tree it is given
(`repro.serve.prepare`): every leaf the step reads only through a
convert to the compute dtype is converted once, on the device, so no
step converts the stack again; every other leaf is the caller's array.
The engine keeps no reference to the tree it was given, so the caller's
float32 weights are freed once the caller lets go of them. The form is
derived at construction and on every assignment of `params`, each time
in a root span `engine.prepare` (attributes `cast_leaves`,
`kept_leaves`, `cast_bytes` of the serving copy; its `wall_s` is the
time the derivation took).

`AllocationEndpoint` is the allocator's request surface
(repro.allocator.service): dict-in/dict-out allocation requests. It is
independent of `ServeEngine`, which is a job the allocator sizes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.allocator.service import (AllocationRequest, AllocationResponse,
                                     AllocationService)
from repro.models.model import Model
from repro.serve.prepare import serving_params
from repro.telemetry import MetricsRegistry, default_registry, span_if


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    submitted_at: float = field(default_factory=time.monotonic)
    finished_at: Optional[float] = None


class ServeEngine:
    def __init__(self, model: Model, params, slots: int, max_len: int,
                 eos_id: Optional[int] = None, seed: int = 0,
                 telemetry: Optional[MetricsRegistry] = None):
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.key = jax.random.PRNGKey(seed)
        self.caches = model.init_caches(slots, max_len)
        self.active: List[Optional[Request]] = [None] * slots
        self.pending: List[Request] = []
        self.finished: List[Request] = []
        self._feed: List[List[int]] = [[] for _ in range(slots)]
        self._last_token = np.zeros((slots,), np.int32)
        self.telemetry = telemetry if telemetry is not None \
            else default_registry()

        self._routed = model.cfg.family == "moe"

        def decode_step(params, batch, caches):
            return model.decode_step(params, batch, caches, None,
                                     routed=self._routed)

        self._step = jax.jit(decode_step)
        self.params = params

    @property
    def params(self):
        """The weights the step reads: the serving form of the tree last
        assigned, derived again on every assignment."""
        return self._served

    @params.setter
    def params(self, params):
        on = self.telemetry.enabled
        with span_if(on, "engine.prepare") as sp:
            served, mask = serving_params(
                self._step, params, self._batch(), self.caches,
                dtype=self.model.compute_dtype)
            served = jax.block_until_ready(served)
            if sp is not None:
                cast = [a for a, m in zip(jax.tree.leaves(served), mask)
                        if m]
                sp.attrs.update(
                    cast_leaves=len(cast), kept_leaves=len(mask) - len(cast),
                    cast_bytes=sum(a.nbytes for a in cast))
        self._served = served

    # -- public ------------------------------------------------------------
    def submit(self, req: Request):
        self.pending.append(req)

    def run(self, max_ticks: int = 10000) -> List[Request]:
        ticks = 0
        while (self.pending or any(self.active)) and ticks < max_ticks:
            self.tick()
            ticks += 1
        return self.finished

    # -- internals ----------------------------------------------------------
    def tick(self):
        on = self.telemetry.enabled
        with span_if(on, "engine.tick") as sp:
            with span_if(on, "engine.admit"):
                self._admit()
            slots = sum(r is not None for r in self.active)
            tokens, routed = self._step_slots(on) if slots else (0, None)
            if sp is not None:
                sp.attrs["slots"] = slots
                sp.attrs["tokens"] = tokens
                if routed is not None:
                    sp.attrs["held_routes"] = int(routed.sum())
                    sp.attrs["experts_hit"] = int((routed > 0).sum())

    def _step_slots(self, on: bool):
        """One batched step over the active slots; returns the output
        tokens appended and, for a MoE model, the pairs routed to each held
        expert by layer (else None)."""
        with span_if(on, "engine.dispatch"):
            out = self._step(self.params, self._batch(), self.caches)
            logits, self.caches = out[0], out[1]
        routed = None
        with span_if(on, "engine.fetch"):
            logits = np.asarray(logits[:, 0])       # (slots, V)
            if self._routed:
                routed = np.asarray(out[2])
        tokens = 0
        with span_if(on, "engine.sample"):
            for i, req in enumerate(self.active):
                if req is None:
                    continue
                if self._feed[i]:
                    # still teacher-forcing the prompt
                    self._last_token[i] = self._feed[i].pop(0)
                    continue
                tok = self._sample(logits[i], req.temperature)
                req.out_tokens.append(int(tok))
                tokens += 1
                self._last_token[i] = tok
                if (len(req.out_tokens) >= req.max_new_tokens or
                        (self.eos_id is not None and tok == self.eos_id)):
                    req.done = True
                    req.finished_at = time.monotonic()
                    self.finished.append(req)
                    self.active[i] = None
        return tokens, routed

    def _admit(self):
        for i in range(self.slots):
            if self.active[i] is None and self.pending:
                req = self.pending.pop(0)
                self.active[i] = req
                self.caches = _reset_slot(self.caches, i)
                self._feed[i] = list(req.prompt[1:])
                self._last_token[i] = req.prompt[0]

    def _batch(self) -> Dict:
        """The step's batch: each slot's last token, and the family's
        extra inputs."""
        cfg = self.model.cfg
        batch = {"tokens": jnp.asarray(self._last_token)[:, None]}
        if cfg.family == "vlm":
            batch["media"] = jnp.zeros(
                (self.slots, cfg.cross_attn.n_media_tokens, cfg.d_model),
                jnp.float32)
        if cfg.family == "audio":
            batch["enc_out"] = jnp.zeros(
                (self.slots, cfg.encdec.enc_len, cfg.d_model), jnp.float32)
        return batch

    def _sample(self, logits: np.ndarray, temperature: float) -> int:
        if temperature <= 0.0:
            return int(np.argmax(logits))
        self.key, sub = jax.random.split(self.key)
        return int(jax.random.categorical(sub, jnp.asarray(logits) /
                                          temperature))


# the cache leaves a new sequence must not inherit, by base rank (batch
# axis = ndim - base rank): the per-row position and the recurrent states.
# Attention keys and values (k, v, ckv, kr and their int8 scales) keep
# their stale rows: every attention masks the positions past `pos`.
_RESET_RANK = {"pos": 1, "h": 4, "conv": 3, "wkv": 4, "tm_last": 2,
               "cm_last": 2}


class AllocationEndpoint:
    """Request endpoint over an AllocationService: wire-friendly dicts in,
    dicts out, with the service's batching/caching behind it. `submit`
    returns the service future for async callers; `handle` blocks (pass
    `include_trace=True` for per-stage walls + acquisition-tier counts);
    `stats` reports service counters plus adaptive-profiling/budget state
    for monitoring dashboards; `metrics` is the full telemetry snapshot
    (histogram percentiles included).

    Tracing: `handle` runs inside an `endpoint.request` span (when the
    service's telemetry is enabled, or always when the caller passes its
    own `trace=` propagation token to join an upstream trace), so the
    worker-side `service.*` spans, the pipeline stages, and any daemon
    round-trips all land under ONE trace id — returned on the wire as
    `trace_id` (None when untraced) for correlation with
    `stitch_fleet_traces` output and histogram exemplars."""

    def __init__(self, service: AllocationService):
        self.service = service

    def submit(self, *, job: str, profile_at, full_size: float,
               anchor: Optional[float] = None,
               sizes: Optional[List[float]] = None,
               signature: Optional[str] = None,
               leeway: Optional[float] = None,
               adaptive: Optional[bool] = None,
               placement: Optional[str] = None,
               tags: Optional[List[str]] = None,
               objective: str = "cheapest_fit"):
        return self.service.submit(AllocationRequest(
            job, profile_at, full_size, anchor=anchor, sizes=sizes,
            signature=signature, leeway=leeway, adaptive=adaptive,
            placement=placement, tags=tags, objective=objective))

    def handle(self, timeout: Optional[float] = None,
               include_trace: bool = False,
               trace: Optional[Dict] = None, **payload) -> Dict:
        # the span must wrap submit(): the service captures the caller's
        # trace context at submit time to hand it across the worker-
        # thread boundary. `trace=` is an upstream propagation token
        # ({"trace_id", "span_id"}) for callers that are themselves part
        # of a larger trace.
        tel = self.service.telemetry
        with span_if(tel.enabled or trace is not None, "endpoint.request",
                     parent=trace, job=payload.get("job")) as sp:
            resp = self.submit(**payload).result(timeout)
            wire = self.to_wire(resp)
            # which shared-state backend served this answer ("memory" /
            # "file" / "daemon", None for a process-local service), and
            # for a daemon, over which transport ("unix" | "tcp")
            wire["backend"] = self.service.backend_kind
            wire["backend_transport"] = self.service.backend_transport
            shards = self.service.backend_shards
            if shards is not None:
                # only present over a sharded backend: single-backend
                # wire answers keep their exact historical shape
                wire["backend_shards"] = [s["name"] for s in shards]
            wire["trace_id"] = sp.trace_id if sp is not None else None
            if include_trace:
                # opt-in ONLY: the rest of the wire answer stays stable
                lru_hits = max(0, resp.cache_hits - resp.store_hits)
                wire["trace"] = {
                    "stage_walls": dict(resp.stage_walls or {}),
                    "acquisition": {"fresh": resp.profiled,
                                    "lru_hits": lru_hits,
                                    "store_hits": resp.store_hits}}
        return wire

    def metrics(self) -> Dict:
        """Full telemetry snapshot (counters / gauges / histograms with
        p50/p95/p99) of the attached service — the wire form of
        `AllocationService.metrics()`, plus backend identity and the
        budget envelope when one is configured."""
        out = {"backend": self.service.backend_kind,
               "backend_transport": self.service.backend_transport,
               "backend_address": self.service.backend_address,
               "backend_shards": self.service.backend_shards,
               "metrics": self.service.metrics()}
        if self.service.budget is not None:
            out["budget"] = self.service.budget.snapshot()
        return out

    def stats(self) -> Dict:
        """Service counters + shared-state backend kind + profiling budget
        snapshot (including shared-envelope state), wire-friendly."""
        s = self.service.stats
        out = {"backend": self.service.backend_kind,
               "backend_transport": self.service.backend_transport,
               "backend_address": self.service.backend_address,
               "backend_shards": self.service.backend_shards,
               "requests": s.requests, "batches": s.batches,
               "profile_calls": s.profile_calls,
               "cache_hits": s.cache_hits, "store_hits": s.store_hits,
               "registry_hits": s.registry_hits,
               "plan_cache_hits": s.plan_cache_hits,
               "zoo_fits": s.zoo_fits, "zoo_confident": s.zoo_confident,
               "classifier_fallbacks": s.classifier_fallbacks,
               "baseline_fallbacks": s.baseline_fallbacks,
               "profile_hit_rate": s.profile_hit_rate,
               "adaptive_plans": s.adaptive_plans,
               "early_stops": s.early_stops,
               "escalations": s.escalations,
               "points_saved": s.points_saved,
               "budget_denied": s.budget_denied,
               "runtime_fits": s.runtime_fits,
               "runtime_confident": s.runtime_confident,
               "cost_objective_requests": s.cost_objective_requests,
               "objective_fallbacks": s.objective_fallbacks}
        if self.service.budget is not None:
            out["budget"] = self.service.budget.snapshot()
        return out

    @staticmethod
    def to_wire(resp: AllocationResponse) -> Dict:
        sel = resp.selection
        return {"job": resp.job, "signature": resp.signature,
                "source": resp.source, "candidate": resp.candidate,
                "neighbor": resp.neighbor,
                "requirement_gib": resp.requirement_gib,
                "config": sel.config.name,
                "usd_per_hour": sel.config.usd_per_hour,
                "method": sel.method, "fell_back": sel.fell_back,
                "profiled": resp.profiled, "cache_hits": resp.cache_hits,
                "wall_s": resp.wall_s, "early_stop": resp.early_stop,
                "escalated": resp.escalated,
                "budget_exhausted": resp.budget_exhausted,
                "placement": resp.placement,
                "objective": resp.objective,
                "objective_fell_back": sel.objective_fell_back,
                "predicted_runtime_s": sel.predicted_runtime_s,
                "predicted_cost_usd": sel.predicted_cost_usd,
                "runtime_candidate": resp.runtime_candidate}


def _reset_slot(caches, slot: int):
    """Zero one slot's state across all (stacked) cache leaves: per-row
    `pos` goes to 0 so stale KV beyond it is never attended; recurrent
    states are cleared explicitly. The KV leaves are left as they are, so
    an admission copies no cache."""
    def one(path, leaf):
        name = ""
        for p in reversed(path):
            k = getattr(p, "key", None)
            if isinstance(k, str):
                name = k
                break
        rank = _RESET_RANK.get(name)
        if rank is None or leaf.ndim < rank:
            return leaf
        axis = leaf.ndim - rank
        idx = (slice(None),) * axis + (slot,)
        return leaf.at[idx].set(0)

    return jax.tree_util.tree_map_with_path(one, caches)
