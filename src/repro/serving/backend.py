"""Backend abstraction: how the data plane *executes* a serving plan.

The control plane evolves policies; the data plane applies the resulting
plans to a backend and feeds what actually happened back into the
evolution loop:

  * :class:`SimBackend` — closes the loop against the roofline simulator
    (exactly the pre-backend accounting; ``IntervalMetrics.measured`` is
    False so nothing is blended into fitness).
  * :class:`JaxBackend` — a real multi-replica :class:`EnginePool` over the
    JAX engines.  ``apply_plan`` measures actual rebuild wall-clock;
    ``serve_interval`` runs real requests and measures TTFT/TPOT/tok/s.
  * :class:`repro.serving.shadow.ShadowBackend` — a deterministic,
    virtually-clocked EnginePool of roofline-costed shadow engines; the
    vehicle for the evaluation ladder's shadow-replay rung and for
    reproducible canary tests.

All satisfy the same protocol, so DataPlane.step is agnostic.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import List, Optional, Protocol, Sequence, Tuple, runtime_checkable

import jax

from repro.configs.base import ModelConfig
from repro.core.execution_model import IntervalMetrics
from repro.core.plan import Ctx, Plan, ReplicaGroup, Workload
from repro.core.policy import (KVCachePolicy, ReconfigPolicy, RecoveryPolicy,
                               RequestPolicy)
from repro.core.simulator import Simulator
from repro.models import lm
from repro.serving.engine import Engine, Request, cast_params
from repro.serving.pool import EnginePool, PoolDiff
from repro.serving.sharded import SubmeshAllocator, engine_for_group


def _percentile(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (rank ⌈q·n⌉) over a sorted sample (0 if
    empty) — e.g. the p50 of an even-sized sample is the lower middle
    element, and p95 of 20 values is the 19th, not the maximum."""
    if not sorted_vals:
        return 0.0
    idx = min(max(math.ceil(q * len(sorted_vals)) - 1, 0),
              len(sorted_vals) - 1)
    return float(sorted_vals[idx])


def measured_interval_metrics(done: Sequence, wall: float,
                              backlogged: int = 0,
                              shed: int = 0) -> IntervalMetrics:
    """Aggregate finished RequestStates into measured interval feedback.

    TTFT is reported as mean *and* p50/p95 (tail behaviour is what the
    slo-aware request genome optimises).  TPOT is pooled — Σ decode
    wall-clock / Σ post-first tokens across ALL completions — so
    single-token completions enter the accounting consistently: they
    contribute zero decode tokens and zero decode time, where the previous
    mean-of-per-request-ratios silently dropped them from the denominator
    while their tokens still counted in throughput."""
    def ngen(d) -> int:
        # tokens produced before a preemption live in the continuation's
        # prompt, not its ``generated`` list — count them as output
        return len(d.generated) + getattr(d, "prior_generated", 0)

    ttfts = sorted(d.first_token_time - d.request.arrival_time
                   for d in done if d.first_token_time is not None)
    decode_s = sum(d.finish_time - d.first_token_time for d in done
                   if d.finish_time is not None
                   and d.first_token_time is not None
                   and ngen(d) > 1)
    decode_tokens = sum(max(ngen(d) - 1, 0) for d in done)
    tokens = sum(ngen(d) for d in done)
    return IntervalMetrics(
        requests=len(done), tokens=tokens, wall_s=wall,
        ttft_s=sum(ttfts) / len(ttfts) if ttfts else 0.0,
        ttft_p50_s=_percentile(ttfts, 0.50),
        ttft_p95_s=_percentile(ttfts, 0.95),
        tpot_s=decode_s / decode_tokens if decode_tokens > 0 else 0.0,
        tokens_per_s=tokens / wall if wall > 0 else 0.0,
        backlogged=backlogged, shed=shed,
        measured=True)   # reconfig_s merged in by DataPlane.step


@dataclass(frozen=True)
class ReconfigReport:
    """What applying a plan did, and what it cost.

    In-flight requests on removed replicas are handled per the reconfig
    policy: ``drained_requests`` ran to completion on the old replica
    (blocking), ``migrated_requests`` carried their live KV/SSM slot state
    to a survivor, ``recomputed_requests`` were requeued as continuations
    (paying re-prefill).  ``migrate_wall_s`` / ``drain_wall_s`` split the
    measured hand-off cost out of ``wall_s``.
    """
    wall_s: float                    # measured reconfiguration wall-clock
    simulated_s: float               # RECONFIG-COST estimate for the same diff
    built: Tuple[ReplicaGroup, ...] = ()
    reused: Tuple[ReplicaGroup, ...] = ()
    removed: Tuple[ReplicaGroup, ...] = ()
    drained_requests: int = 0
    migrated_requests: int = 0
    recomputed_requests: int = 0
    migrate_wall_s: float = 0.0
    drain_wall_s: float = 0.0

    @property
    def changed(self) -> bool:
        return bool(self.built or self.removed)


@runtime_checkable
class Backend(Protocol):
    """Data-plane execution target for serving plans."""

    def apply_plan(self, plan: Plan, ctx: Ctx) -> ReconfigReport:
        """Reconfigure to ``plan``; returns measured + simulated cost."""
        ...

    def serve_interval(self, workloads: Sequence[Workload]) -> IntervalMetrics:
        """Serve one monitoring interval's workloads under the current plan."""
        ...

    def set_request_policy(self, rp: Optional[RequestPolicy]) -> None:
        """Install (or clear, with None) the request-domain scheduling hooks
        of the live PolicyProgram — Policy API v2's second evolvable surface."""
        ...

    def set_reconfig_policy(self, rp: Optional[ReconfigPolicy]) -> None:
        """Install (or clear, with None) the reconfig-domain hook deciding
        drain|migrate|recompute per in-flight request on plan changes —
        the third evolvable surface (reconfiguration-overhead axis)."""
        ...

    def set_kv_cache_policy(self, kp: Optional[KVCachePolicy]) -> None:
        """Install (or clear, with None) the kv_cache-domain hooks governing
        cross-request prefix retention and eviction over the paged KV pool —
        the fourth evolvable surface (cache-memory axis)."""
        ...

    def set_recovery_policy(self, rp: Optional[RecoveryPolicy]) -> None:
        """Install (or clear, with None) the recovery-domain hook deciding
        salvage|recompute|shed per in-flight request when a replica dies
        unexpectedly, plus the retry/backoff/straggler knobs — the fifth
        evolvable surface (unplanned-failure containment)."""
        ...


# --------------------------------------------------------------------------- #
# simulator-backed (closes the loop without hardware)
# --------------------------------------------------------------------------- #
@dataclass
class SimBackend:
    """Plan execution modelled by the roofline simulator.  Produces the same
    interval totals as the pre-backend accounting path (metrics carry
    ``measured=False`` and are never blended into fitness)."""
    sim: Simulator
    plan: Optional[Plan] = None
    applied: List[Plan] = field(default_factory=list)
    request_policy: Optional[RequestPolicy] = None
    reconfig_policy: Optional[ReconfigPolicy] = None
    kv_cache_policy: Optional[KVCachePolicy] = None
    recovery_policy: Optional[RecoveryPolicy] = None

    def set_request_policy(self, rp: Optional[RequestPolicy]) -> None:
        # the roofline simulator has no per-request queue to reorder; the
        # hooks are recorded so tests (and future sim upgrades) can see what
        # the control plane pushed
        self.request_policy = rp

    def set_reconfig_policy(self, rp: Optional[ReconfigPolicy]) -> None:
        # no live slots to migrate in the simulator; recorded for visibility
        self.reconfig_policy = rp

    def set_kv_cache_policy(self, kp: Optional[KVCachePolicy]) -> None:
        # no page pool in the simulator either; recorded for visibility
        self.kv_cache_policy = kp

    def set_recovery_policy(self, rp: Optional[RecoveryPolicy]) -> None:
        # no replicas to kill in the simulator; recorded for visibility
        self.recovery_policy = rp

    def apply_plan(self, plan: Plan, ctx: Ctx) -> ReconfigReport:
        sim_cost = self.sim.reconfig_cost(self.plan, plan)
        old_groups = set(self.plan.groups) if self.plan is not None else set()
        new_groups = set(plan.groups)
        self.plan = plan
        self.applied.append(plan)
        # same group-set diff semantics as EnginePool.reconfigure — dropping
        # a model entirely is a change even if every surviving group matches
        return ReconfigReport(
            wall_s=0.0, simulated_s=sim_cost,
            built=tuple(sorted(new_groups - old_groups, key=repr)),
            reused=tuple(sorted(new_groups & old_groups, key=repr)),
            removed=tuple(sorted(old_groups - new_groups, key=repr)))

    def serve_interval(self, workloads: Sequence[Workload]) -> IntervalMetrics:
        serve_s = self.sim.serve_cost(self.plan, list(workloads))
        tokens = sum(w.batch * (w.prefill_len + w.decode_len) for w in workloads)
        return IntervalMetrics(
            requests=sum(w.batch for w in workloads), tokens=tokens,
            wall_s=serve_s, tokens_per_s=tokens / serve_s if serve_s > 0 else 0.0,
            simulated_serve_s=serve_s, measured=False)


# --------------------------------------------------------------------------- #
# real JAX engine pool
# --------------------------------------------------------------------------- #
@dataclass
class JaxBackend:
    """Physical data plane: a reduced-config model zoo engine per replica.

    One ``(cfg, params)`` stands in for every logical model in the plan (the
    cluster-scale models do not fit a CPU test host); the *topology* — how
    many replicas, what per-replica batch, what gets rebuilt on a plan
    change — is exercised for real, and all costs are measured wall-clock.
    """
    cfg: ModelConfig
    params: object
    max_seq_len: int = 96
    slots_cap: int = 8               # per-replica engine slots cap
    max_replicas_per_group: int = 2
    requests_per_model: int = 3      # synthetic requests per workload model
    max_new_tokens: int = 6
    # optional deterministic fault injection (serving/faults.FaultInjector):
    # applied once per serve_interval, keyed on the interval index so the
    # same injector seed replays the same faults at the same points
    fault_injector: Optional[object] = None
    # mesh-sharded replicas: when the process has >1 device, groups with
    # tp*dp > 1 run as ShardedEngines on per-replica submeshes carved by
    # the allocator (single-device hosts degrade to plain engines)
    shard_replicas: bool = True
    pool: EnginePool = field(init=False)
    allocator: Optional[SubmeshAllocator] = field(init=False, default=None)
    params_cast: int = field(init=False, default=0)
    _rid: int = 0
    _interval_no: int = 0
    _shed_seen: int = 0

    def __post_init__(self):
        # one served copy of the weights: every engine a plan builds shares it
        self.params, self.params_cast = cast_params(self.cfg, self.params)
        if self.shard_replicas and len(jax.devices()) > 1:
            self.allocator = SubmeshAllocator()
        self.pool = EnginePool(self._make_engine,
                               max_replicas_per_group=self.max_replicas_per_group)

    def _make_engine(self, group: ReplicaGroup) -> Engine:
        return engine_for_group(
            self.cfg, self.params, group, self.allocator,
            n_slots=max(1, min(group.batch, self.slots_cap)),
            max_seq_len=self.max_seq_len)

    # ------------------------------------------------------------------ #
    def set_request_policy(self, rp: Optional[RequestPolicy]) -> None:
        self.pool.set_request_policy(rp)

    def set_reconfig_policy(self, rp: Optional[ReconfigPolicy]) -> None:
        self.pool.set_reconfig_policy(rp)

    def set_kv_cache_policy(self, kp: Optional[KVCachePolicy]) -> None:
        self.pool.set_kv_cache_policy(kp)

    def set_recovery_policy(self, rp) -> None:
        self.pool.set_recovery_policy(rp)

    @property
    def failure_count(self) -> int:
        """Replica deaths so far (DataPlane reads this to trigger re-plans)."""
        return self.pool.failures

    @property
    def breaker(self):
        """The pool's shared hook circuit breaker (trip surfacing)."""
        return self.pool.breaker

    def apply_plan(self, plan: Plan, ctx: Ctx) -> ReconfigReport:
        sim_cost = 0.0
        if ctx is not None and ctx.simulator is not None:
            sim_cost = ctx.simulator.reconfig_cost(self.pool.plan, plan)
        diff: PoolDiff = self.pool.reconfigure(plan)
        return ReconfigReport(wall_s=diff.wall_s, simulated_s=sim_cost,
                              built=diff.built, reused=diff.reused,
                              removed=diff.removed,
                              drained_requests=diff.drained_requests,
                              migrated_requests=diff.migrated_requests,
                              recomputed_requests=diff.recomputed_requests,
                              migrate_wall_s=diff.migrate_wall_s,
                              drain_wall_s=diff.drain_wall_s)

    def serve_interval(self, workloads: Sequence[Workload]) -> IntervalMetrics:
        """Serve a scaled-down burst per workload model and measure."""
        t0 = time.monotonic()
        for w in workloads:
            # prompt/decode lengths scaled into the reduced engine's window
            p_len = max(2, min(w.prefill_len // 64, self.max_seq_len // 3))
            d_len = max(2, min(w.decode_len // 256, self.max_new_tokens))
            for i in range(self.requests_per_model):
                self._rid += 1
                req = Request(rid=self._rid,
                              prompt=[(self._rid + j) % (self.cfg.vocab_size - 1) + 1
                                      for j in range(p_len)],
                              max_new_tokens=d_len,
                              arrival_time=time.monotonic())
                if not self.pool.submit(w.model, req):
                    # no replica serves this model (or the admit gate is
                    # throttling): hold the request rather than dropping it
                    self.pool.add_backlog(w.model, req)
        if self.fault_injector is not None:
            # a step of real progress first, so kills land mid-decode (the
            # interesting case), then the interval's scheduled faults
            for eng in self.pool.engines:
                if eng.waiting or eng.active:
                    eng.step()
            self.fault_injector.step(self.pool, self._interval_no)
        self._interval_no += 1
        done = self.pool.run_until_drained()
        wall = time.monotonic() - t0
        # backlogged = requests STILL unserved after the drain; a request the
        # admit gate merely deferred and then served this interval is not
        # penalised twice (its queueing delay already shows up in TTFT).
        # shed = NEW drops this interval (recovery policy / retry budget /
        # backlog cap) — a loss the canary guard weighs against TTFT wins
        shed_total = len(self.pool.shed_requests) + self.pool.backlog_dropped
        shed_new, self._shed_seen = shed_total - self._shed_seen, shed_total
        return measured_interval_metrics(done, wall, len(self.pool.backlog),
                                         shed=shed_new)


def make_jax_backend(arch: str = "qwen2-1.5b", seed: int = 0,
                     **kwargs) -> JaxBackend:
    """Convenience constructor: reduced config + fresh params."""
    from repro.configs import get_config
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(seed))
    return JaxBackend(cfg, params, **kwargs)
