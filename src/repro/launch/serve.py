"""Serving driver: ``python -m repro.launch.serve --arch <id>``.

Boots the plan-driven engine pool on a reduced config: a serving plan maps
each replica group to continuous-batching JAX engines (chunked prefill +
single-dispatch decode).  A batch of synthetic requests is routed across the
replicas; ``--resize`` then applies a second plan with a different
per-replica batch to demonstrate a measured (wall-clock) reconfiguration —
unchanged groups keep their warm engines.

``--guarded`` additionally demonstrates the control plane's guarded
rollout: one evolution cycle through the evaluation ladder (analytic
screen → shadow replay), a canary-ticketed publish, and a planted
regression that is caught and rolled back — commit/rollback counts and
reasons are printed.

``--faults SEED`` replays a seeded kill schedule against the pool while it
serves: each injected replica death is contained by the recovery domain
(salvage live slots onto a survivor, requeue the rest with backoff) and the
per-failure :class:`~repro.serving.pool.FailureReport` is printed.

:func:`serve` — apply a plan, submit requests, drain — is the served path
end to end; ``chip_smoke.py`` at the repository root drives the same
function at full model width on a TPU.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence

import jax

from repro.configs import get_config, list_archs
from repro.core.plan import Plan, ReplicaGroup
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.serving.backend import JaxBackend, ReconfigReport
from repro.serving.engine import Request, RequestState
from repro.serving.pool import EnginePool


@dataclass
class ServeResult:
    """What one :func:`serve` call did."""
    report: ReconfigReport           # what applying the plan built/reused
    done: List[RequestState]         # every request finished by the drain
    wall_s: float                    # submission through drain

    @property
    def tokens(self) -> int:
        return sum(len(d.generated) for d in self.done)


def serve(backend: JaxBackend, plan: Plan, requests: Sequence[Request],
          before_drain: Optional[Callable[[EnginePool], None]] = None
          ) -> ServeResult:
    """Apply ``plan``, submit ``requests`` to its (single) model, drain.

    ``JaxBackend.apply_plan`` builds the plan's engines (or keeps warm ones
    whose group is unchanged), ``EnginePool.submit`` routes each request to
    the least-loaded replica, and ``run_until_drained`` steps every engine
    until all finish.  ``before_drain(pool)`` runs between submission and
    the drain (fault injection hooks in there)."""
    models = {g.model for g in plan.groups}
    if len(models) != 1:
        raise ValueError(f"serve() routes to one model, plan has {models}")
    (model,) = models
    report = backend.apply_plan(plan, None)
    t0 = time.monotonic()
    for req in requests:
        if not backend.pool.submit(model, req):
            backend.pool.add_backlog(model, req)
    if before_drain is not None:
        before_drain(backend.pool)
    done = backend.pool.run_until_drained()
    return ServeResult(report, done, time.monotonic() - t0)


def guarded_demo() -> None:
    """Evaluation ladder + canary/rollback on the deterministic shadow
    data plane (no JAX engines involved — runs in seconds)."""
    from repro.core.evaluator import Evaluator
    from repro.core.evolution import EvolutionConfig
    from repro.core.plan import HARDWARE, QWEN25_FAMILY
    from repro.core.policy import Policy, seed_policies
    from repro.core.runtime import (Autopoiesis, CanaryTicket)
    from repro.core.simulator import Simulator
    from repro.serving.shadow import (BAD_REQUEST_SOURCE, ShadowBackend,
                                      ShadowReplayEval)
    from repro.traces import volatile_workload_trace

    models = {m.name: m for m in QWEN25_FAMILY.values()}
    sim = Simulator(models, HARDWARE)
    ev = Evaluator(sim, models, HARDWARE)
    ap = Autopoiesis(
        ev, seed_policies()["greedy-reactive"],
        EvolutionConfig(max_iterations=4, patience=4,
                        evolution_timeout_s=45, shadow_top_k=3, seed=0),
        window=6, evolve_every=3,
        backend=ShadowBackend(sim, seed=0),
        shadow=ShadowReplayEval(sim, models, HARDWARE,
                                candidate_timeout_s=20.0))
    trace = volatile_workload_trace()
    print("guarded evolution over the volatile trace "
          "(shadow data plane, virtual clock):")
    for i, obs in enumerate(trace.observations):
        out = ap.data_plane.step(obs)
        c = out["canary"]
        if c is not None:
            print(f"  step {i}: canary[{c['candidate']}] {c['status']}"
                  + (f" — {c['reason']}" if c.get("reason") else ""))
        if i > 0 and i % 3 == 0:
            ap.control_plane.run_cycle(ap.data_plane.policy)
    # plant a regression: it must be canaried and rolled back, not committed
    ap.stage.publish(Policy(source=BAD_REQUEST_SOURCE, name="regressor"),
                     ticket=CanaryTicket(intervals=2, max_regression=0.5,
                                         policy_name="regressor"))
    for i, obs in enumerate(trace.observations[:3]):
        out = ap.data_plane.step(obs)
        c = out["canary"]
        if c is not None and c["status"] != "running":
            print(f"  planted regressor: {c['status']}"
                  + (f" — {c['reason']}" if c.get("reason") else ""))
    cp, dp = ap.control_plane, ap.data_plane
    print(f"control plane: cycles={cp.cycles} skipped={cp.skipped_cycles} "
          f"published={cp.published} cache_hits={cp.incumbent_cache_hits}")
    print(f"data plane: swaps={dp.swap_count} commits={dp.commits} "
          f"rollbacks={dp.rollbacks}")
    for reason in dp.rollback_reasons:
        print(f"  rollback: {reason}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list_archs())
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--resize", action="store_true",
                    help="apply a second plan (halved batch) and report the "
                         "measured reconfiguration wall-clock")
    ap.add_argument("--priority", default="fifo",
                    choices=["fifo", "sjf", "slo-aware"],
                    help="request-domain admission order (Policy API v2); "
                         "fifo keeps the v1 behaviour")
    ap.add_argument("--reconfig", default="drain",
                    choices=["drain", "migrate", "recompute"],
                    help="what happens to in-flight requests when --resize "
                         "removes their replica (reconfig domain)")
    ap.add_argument("--guarded", action="store_true",
                    help="demonstrate the evaluation ladder + canary "
                         "rollout/rollback on the shadow data plane")
    ap.add_argument("--faults", type=int, default=None, metavar="SEED",
                    help="replay a seeded fault schedule (replica kills + "
                         "stragglers) against the pool while it serves and "
                         "print each FailureReport (recovery domain)")
    args = ap.parse_args()

    if args.guarded:
        guarded_demo()
        return 0

    use_compile_cache()
    cfg = get_config(args.arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    backend = JaxBackend(cfg, params, max_seq_len=128, slots_cap=args.slots,
                         max_replicas_per_group=args.replicas)
    if args.priority != "fifo":
        from repro.core.policy import render_policy
        backend.set_request_policy(render_policy(
            {"domains": ["placement", "request"],
             "priority_kind": args.priority},
            name=args.priority).request_policy())
        print(f"request policy: {args.priority} admission order")
    model = cfg.name
    plan = Plan((ReplicaGroup(model, "TPU-v5e", tp=1, batch=args.slots,
                              count=args.replicas),))

    inj = None
    inject = None
    if args.faults is not None:
        from repro.core.policy import render_policy
        from repro.serving.faults import FaultInjector
        backend.pool.set_recovery_policy(render_policy(
            {"domains": ["placement", "recovery"],
             "recovery_mode": "salvage", "retry_budget": 3,
             "backoff_base_s": 0.01},
            name="retry-migrate").recovery_policy())
        inj = FaultInjector.from_seed(args.faults, n_events=3, horizon=3,
                                      kill_ratio=1.0, deny_export_rate=0.0)
        print(f"fault injection: seed={args.faults} schedule="
              f"{[(ev.step, ev.kind) for ev in inj.schedule]} "
              f"(recovery policy: retry-migrate)")

        def inject(pool: EnginePool) -> None:
            for i in range(3):
                for eng in pool.engines:
                    eng.step(); eng.step()   # let kills land mid-decode
                seen = len(pool.failure_log)
                inj.step(pool, i)
                for rep in pool.failure_log[seen:]:
                    print(f"  fault@step{i}: {rep.reason} model={rep.model} "
                          f"salvaged={rep.salvaged} "
                          f"recomputed={rep.recomputed} "
                          f"requeued={rep.requeued} shed={rep.shed} "
                          f"leaked_pages={rep.leaked_pages}")
                backend.apply_plan(plan, None)   # heal to the target count

    requests = [Request(rid=r, prompt=[1 + (r + j) % 9
                                       for j in range(args.prompt_len)],
                        max_new_tokens=args.max_new,
                        arrival_time=time.monotonic())
                for r in range(args.requests)]
    res = serve(backend, plan, requests, before_drain=inject)
    print(f"plan applied: built={len(res.report.built)} groups "
          f"({args.replicas}×{args.slots}-slot engines) "
          f"in {res.report.wall_s * 1e3:.1f}ms")
    dt, toks = res.wall_s, res.tokens
    disp = backend.pool.total_dispatches
    print(f"arch={args.arch} served {len(res.done)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks / dt:.1f} tok/s, jitted dispatches={disp}, "
          f"{disp / max(len(res.done), 1):.1f}/request)")
    if inj is not None:
        pool = backend.pool
        print(f"faults: kills={inj.kills} skipped={inj.skipped} "
              f"straggles={inj.straggles} | recovered: "
              f"salvaged={pool.salvaged_requests} "
              f"retry_exhausted={pool.retry_exhausted} "
              f"shed={len(pool.shed_requests)} "
              f"leaked_pages={sum(r.leaked_pages for r in pool.failure_log)}")

    if args.resize:
        if args.reconfig != "drain":
            from repro.core.policy import render_policy
            backend.set_reconfig_policy(render_policy(
                {"domains": ["placement", "reconfig"],
                 "migration_mode": args.reconfig},
                name=args.reconfig).reconfig_policy())
        # resubmit a burst so the resize happens with requests in flight
        for r in range(args.requests, args.requests + args.slots):
            backend.pool.submit(model, Request(
                rid=r, prompt=[1 + (r + j) % 9 for j in range(args.prompt_len)],
                max_new_tokens=args.max_new, arrival_time=time.monotonic()))
        for eng in backend.pool.engines:
            eng.step()
        plan2 = Plan((ReplicaGroup(model, "TPU-v5e", tp=1,
                                   batch=max(args.slots // 2, 1),
                                   count=args.replicas),))
        rep2 = backend.apply_plan(plan2, None)
        print(f"resize[{args.reconfig}]: rebuilt={len(rep2.built)} "
              f"reused={len(rep2.reused)} removed={len(rep2.removed)} "
              f"drained={rep2.drained_requests} "
              f"migrated={rep2.migrated_requests} "
              f"recomputed={rep2.recomputed_requests} "
              f"measured reconfig={rep2.wall_s * 1e3:.1f}ms "
              f"(hand-off: migrate {rep2.migrate_wall_s * 1e3:.1f}ms / "
              f"drain {rep2.drain_wall_s * 1e3:.1f}ms)")
        done2 = backend.pool.run_until_drained()
        print(f"post-resize: served {len(done2)} carried/queued requests")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
