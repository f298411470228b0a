"""Continuous-batching serving engine (Orca-style) over the JAX model zoo.

The engine maintains a fixed set of decode slots backed by the unified
KV/SSM cache (repro.models.lm.init_cache).  Each step:
  1. admit waiting requests into free slots (chunked prefill: the prompt is
     split into power-of-two chunks, each advanced in ONE jitted dispatch);
  2. run one batched decode step for all active slots (inputs are assembled
     in NumPy and shipped to the device once — no per-slot ``.at[].set``
     dispatch chain);
  3. retire finished requests (EOS / max tokens).

Dispatch count per request is O(log prompt_len) for prefill plus one shared
dispatch per decode step, versus O(prompt_len) + O(n_slots) for the legacy
per-token path (kept behind ``chunked_prefill=False`` for benchmarking).

This is the JaxBackend engine of the Autopoiesis data plane — the plan's
per-replica batch maps to ``n_slots``; reconfiguration maps to engine
rebuilds, whose wall-clock cost is what the simulator's RECONFIG-COST models
(and what repro.serving.pool measures for real).  Works on CPU for
tests/examples and under pjit on the production mesh.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.policy import KVCachePolicy, RequestPolicy
from repro.kernels import default_interpret
from repro.models import lm
from repro.serving import kvcache

EOS_DEFAULT = -1        # disabled unless the tokenizer defines one

# candidate prefill chunk sizes (powers of two, greedy binary decomposition)
_CHUNK_CANDIDATES = (256, 128, 64, 32, 16, 8, 4, 2, 1)

# profiler spans of one engine step, always on (about a microsecond each with
# the profiler off); a span's keyword args become its trace event's stats
SPANS = ("step", "prefill", "decode", "decode.dispatch", "decode.sync",
         "retire")


def _span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """The span ``engine.<name>``; ``name`` is one of :data:`SPANS`."""
    return jax.profiler.TraceAnnotation(f"engine.{name}", **args)


def cast_params(cfg: ModelConfig, params) -> Tuple[object, int]:
    """``lm.serving_params`` of ``params``, and how many leaves it cast."""
    served = lm.serving_params(cfg, params)
    return served, sum(a is not b for a, b in zip(jax.tree.leaves(params),
                                                  jax.tree.leaves(served)))


class DrainStallError(RuntimeError):
    """``run_until_drained`` exhausted ``max_steps`` with work still in
    flight — a stall (e.g. a retry loop that never converges, or a backoff
    horizon past the step budget), not a clean drain.  Raised instead of
    returning silently so stalls cannot masquerade as empty queues."""


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: int = EOS_DEFAULT
    arrival_time: float = 0.0
    # accounting carry for continuations of preempted/migrated requests:
    # riding on the Request itself means it survives a requeue onto ANY
    # replica (engine-local carry maps lose it across the pool)
    first_token_time: Optional[float] = None
    prior_generated: int = 0     # tokens already produced in earlier lives
    # failure-recovery carry: how many times this request was requeued off a
    # dead replica, and the capped-exponential-backoff eligibility time the
    # pool's backlog flush honours (0.0 = immediately eligible)
    retries: int = 0
    not_before: float = 0.0


@dataclass(frozen=True)
class RequestCtx:
    """Typed view of one request against the engine's current load — the
    argument the request-domain policy hooks (``admit``/``prioritize``)
    receive.  Kept to plain scalars so evolved code stays cheap and cannot
    reach mutable engine state from the serving hot path."""
    rid: int
    prompt_len: int
    max_new_tokens: int
    age_s: float                     # now − arrival_time (queueing delay)
    queue_depth: int                 # requests waiting on this engine
    active: int                      # requests currently decoding
    n_slots: int

    @property
    def slot_load(self) -> float:
        return self.active / max(self.n_slots, 1)


@dataclass(frozen=True)
class MigrationCtx:
    """Typed view of one in-flight request at reconfiguration time — the
    argument the reconfig-domain policy hook (``migration_mode``) receives.
    Plain scalars only, like :class:`RequestCtx`."""
    rid: int
    prompt_len: int
    generated: int                   # tokens produced so far (all lives)
    remaining: int                   # decode budget left
    position: int                    # next cache position

    @property
    def progress(self) -> float:
        """Fraction of the decode budget already spent — the knob
        ``migrate_min_progress`` thresholds on (young requests are cheap to
        recompute; old ones carry state worth moving)."""
        return self.generated / max(self.generated + self.remaining, 1)


@dataclass(frozen=True)
class FailureCtx:
    """Typed view of one in-flight request on a replica that just died — the
    argument the recovery-domain policy hook (``on_failure``) receives.
    Plain scalars only, like :class:`MigrationCtx`."""
    rid: int
    prompt_len: int
    generated: int                   # tokens produced so far (all lives)
    remaining: int                   # decode budget left
    retries: int                     # times already requeued off a failure
    exportable: bool                 # slot state can be salvaged right now
    survivors: int                   # replicas left serving this model
    free_slots: int                  # open slots across those survivors
    queue_depth: int                 # pool backlog depth

    @property
    def progress(self) -> float:
        """Fraction of the decode budget already spent (salvage pays off on
        old requests; young ones are cheap to recompute or shed)."""
        return self.generated / max(self.generated + self.remaining, 1)


@dataclass
class RequestState:
    request: Request
    slot: int
    generated: List[int] = field(default_factory=list)
    position: int = 0
    done: bool = False
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    prefill_dispatches: int = 0
    prior_generated: int = 0     # tokens produced before a preemption
                                 # (folded into the continuation's prompt)
    admitted_at: Optional[float] = None   # time.monotonic() at admission
    prefix_matched: int = 0      # prompt tokens mapped from the prefix index


@dataclass
class SlotExport:
    """One active slot packed for migration (Engine.export_active).

    ``request`` is the continuation — prompt + tokens generated so far,
    remaining budget, accounting carry — the recompute-fallback currency any
    engine can re-prefill.  ``cache`` is the extracted device state
    (:func:`repro.models.lm.extract_slot`) that lets a compatible engine
    resume decoding in place, skipping the re-prefill entirely; ``state`` is
    the live RequestState (its ``slot`` is stale until re-installed).
    """
    request: Request
    state: RequestState
    cfg: ModelConfig
    cache: Optional[object]          # None when exported for recompute only
    position: int


class RequestSchedulingMixin:
    """Request-domain policy dispatch (Policy API v2) shared by the
    production :class:`Engine` and the shadow-replay twin
    (:class:`repro.serving.shadow.ShadowEngine`) — ONE implementation of
    admission ordering, preemption, and hook-context construction, so the
    evaluation ladder's fidelity contract cannot drift from live serving.

    Host requirements: ``waiting``, ``active``, ``n_slots``,
    ``request_policy``, ``policy_errors``, ``preemptions``,
    ``max_prompt_len``; ``_now`` supplies the clock (wall for the real
    engine, virtual for the shadow).
    """

    def _now(self) -> float:
        return time.monotonic()

    def _on_slot_released(self, slot: int, st: "RequestState") -> None:
        """Hook fired when a request leaves its slot outside the normal
        retire path (preemption).  Paged engines release page references
        here; the contiguous engine and the shadow twin need nothing."""

    def request_ctx_for(self, req: Request,
                        now: Optional[float] = None) -> RequestCtx:
        now = self._now() if now is None else now
        return RequestCtx(rid=req.rid, prompt_len=len(req.prompt),
                          max_new_tokens=req.max_new_tokens,
                          age_s=max(now - req.arrival_time, 0.0),
                          queue_depth=len(self.waiting),
                          active=len(self.active), n_slots=self.n_slots)

    def migration_ctx_for(self, st: RequestState) -> MigrationCtx:
        req = st.request
        return MigrationCtx(rid=req.rid, prompt_len=len(req.prompt),
                            generated=st.prior_generated + len(st.generated),
                            remaining=req.max_new_tokens - len(st.generated),
                            position=st.position)

    def failure_ctx_for(self, st: RequestState, exportable: bool,
                        survivors: int, free_slots: int,
                        queue_depth: int) -> FailureCtx:
        req = st.request
        return FailureCtx(rid=req.rid, prompt_len=len(req.prompt),
                          generated=st.prior_generated + len(st.generated),
                          remaining=max(req.max_new_tokens
                                        - len(st.generated), 0),
                          retries=req.retries, exportable=exportable,
                          survivors=survivors, free_slots=free_slots,
                          queue_depth=queue_depth)

    # --- circuit-breaker plumbing (shared by engines and the pool) ----- #
    # ``breaker`` is an optional HookCircuitBreaker the owning pool shares
    # across its replicas; standalone engines run without one (advisory
    # fallbacks only, exactly the pre-breaker behaviour).
    def _hook_open(self, domain: str) -> bool:
        br = getattr(self, "breaker", None)
        return br is not None and br.tripped(domain)

    def _hook_error(self, domain: str) -> None:
        self.policy_errors += 1
        br = getattr(self, "breaker", None)
        if br is not None:
            br.failure(domain)

    def _hook_ok(self, domain: str) -> None:
        br = getattr(self, "breaker", None)
        if br is not None:
            br.success(domain)

    def _score(self, req: Request, now: float) -> float:
        """Priority score (lower runs first).  The ``admit`` gate is NOT
        consulted here: work in ``waiting`` is already accepted, and a
        load-cap admit is self-referential at slot admission (the candidate
        counts itself in queue_depth, so deferring can never satisfy the
        cap) — ``admit`` gates ingress at EnginePool.submit instead.  Hook
        failures are advisory, never fatal: the request falls back to
        FIFO-neutral priority and serving continues; a tripped breaker skips
        the hook entirely."""
        rp = self.request_policy
        if rp is None or self._hook_open("request"):
            return 0.0
        try:
            score = rp.prioritize(self.request_ctx_for(req, now))
        except Exception:  # noqa: BLE001 — evolved code must not kill serving
            self._hook_error("request")
            return 0.0
        self._hook_ok("request")
        return score

    def _select_admissions(self, n: int) -> List[Request]:
        """Pick up to ``n`` waiting requests to admit now.  Without a request
        policy this is exactly the v1 FIFO pop; with one, ``prioritize``
        orders the queue (ties break FIFO)."""
        if n <= 0 or not self.waiting:
            return []                    # full house: don't score the queue
        if self.request_policy is None:
            take, self.waiting = self.waiting[:n], self.waiting[n:]
            return take
        now = self._now()
        scored = sorted((self._score(req, now), i)
                        for i, req in enumerate(self.waiting))
        picked = sorted(i for _, i in scored[:n])
        out = [self.waiting[i] for i in picked]
        for i in reversed(picked):
            del self.waiting[i]
        return out

    def _maybe_preempt(self) -> None:
        """Policy-gated preemption: when every slot is busy and a waiting
        request outranks the worst-priority running one, evict the victim.
        Its progress is folded into a continuation request (prompt = original
        prompt + tokens generated so far) so greedy decoding resumes exactly;
        the victim's KV/SSM state is re-prefilled on re-admission — the
        recompute-on-preempt trade every vLLM-style engine makes."""
        rp = self.request_policy
        if (rp is None or not rp.preempt or not self.waiting
                or len(self.active) < self.n_slots):
            return
        now = self._now()
        # rank by prioritize alone: the admit gate answers "may this start
        # now", which would both veto challengers at exactly the saturation
        # preemption exists for and shield unadmittable victims
        best_score = min(self._score(req, now) for req in self.waiting)
        victims = []
        for slot, st in self.active.items():
            req = st.request
            remaining = req.max_new_tokens - len(st.generated)
            cont_prompt = list(req.prompt) + list(st.generated)
            if remaining < 1 or len(cont_prompt) > self.max_prompt_len(remaining):
                continue                 # nearly done / would not fit: keep it
            proxy = Request(req.rid, cont_prompt, remaining, req.eos_id,
                            req.arrival_time)
            victims.append((self._score(proxy, now), slot, proxy))
        if not victims:
            return
        worst_score, slot, proxy = max(victims, key=lambda v: v[0])
        if best_score >= worst_score:    # challenger must strictly outrank
            return
        st = self.active.pop(slot)       # slot wiped at next claim (reset path)
        self._on_slot_released(slot, st)
        # the carry travels ON the continuation so TTFT/token accounting
        # survives a requeue onto a different replica
        proxy.first_token_time = st.first_token_time
        proxy.prior_generated = st.prior_generated + len(st.generated)
        self.waiting.append(proxy)
        self.preemptions += 1


class Engine(RequestSchedulingMixin):
    def __init__(self, cfg: ModelConfig, params, n_slots: int = 4,
                 max_seq_len: int = 256, greedy: bool = True,
                 chunked_prefill: bool = True, max_prefill_chunk: int = 64,
                 truncate_long_prompts: bool = True,
                 request_policy: Optional[RequestPolicy] = None,
                 paged: Optional[bool] = None, page_size: int = 16,
                 n_pages: Optional[int] = None, prefix_cache: bool = True,
                 kv_cache_policy: Optional[KVCachePolicy] = None,
                 use_paged_kernel: Optional[bool] = None):
        self.cfg = cfg
        # the step reads its weights in the compute dtype, rounded once here
        # (not in every step); 0 leaves cast when handed a served tree
        self.params, self.params_cast = cast_params(cfg, params)
        self.n_slots = n_slots
        self.max_seq_len = max_seq_len
        self.chunked_prefill = chunked_prefill
        self.truncate_long_prompts = truncate_long_prompts
        self.request_policy = request_policy
        self.kv_cache_policy = kv_cache_policy
        self.policy_errors = 0       # request-hook failures (hooks are advisory)
        self.preemptions = 0
        # fault-tolerance state.  ``breaker`` is installed by the owning pool
        # (shared across replicas); ``fault_slowdown`` is the injected
        # straggler multiplier scaling the *recorded* step time (no real
        # sleeps — tests and shadow replay stay fast); the EMA feeds the
        # pool's straggler detector.
        self.breaker = None
        self.fault_slowdown = 1.0
        self.step_ema_s = 0.0
        self.health_samples = 0
        if paged is None:
            paged = lm.pageable(cfg)         # the default serving path
        elif paged and not lm.pageable(cfg):
            raise ValueError(f"family {cfg.family!r} cannot use the paged "
                             f"KV cache (recurrent/xattn/paired state)")
        self.paged = bool(paged)
        self.page_size = page_size
        # how decode attention runs: the fused paged Pallas kernel (compiled
        # on TPU, interpreted elsewhere) or the jnp gather path
        self.use_paged_kernel = False
        self.interpret = default_interpret()
        # trace-time flags (repro.models.flags) every jitted step runs
        # under; sharded engines set their mesh switches here
        self.trace_flags: Dict[str, object] = {}
        self.prefix_cache_enabled = self.paged and prefix_cache
        cache_dtype = lm.compute_dtype(cfg)
        self.waiting: List[Request] = []
        self.active: Dict[int, RequestState] = {}       # slot -> state
        self.finished: List[RequestState] = []
        self.steps = 0
        self.dispatches = 0          # jitted-callable invocations (perf metric)
        self._chunk_sizes = self._allowed_chunk_sizes(max_prefill_chunk)

        if self.paged:
            pps = -(-max_seq_len // page_size)          # ceil
            self._pages_per_slot = pps
            if n_pages is None:
                # full occupancy + trash + two slots' worth of retained
                # prefixes (the evictable reuse budget under full load)
                n_pages = 1 + (n_slots + 2) * pps
            self.page_pool = kvcache.PagePool(n_pages)
            self.prefix_index = kvcache.PrefixIndex(page_size)
            self.prefix_evictions = 0
            self._slot_pages: Dict[int, List[int]] = {}
            self._ptab = np.zeros((n_slots, pps), np.int32)
            self.cache = lm.init_paged_cache(cfg, n_pages, page_size,
                                             dtype=cache_dtype)
            # paged chunks have no rolling-ring placement constraint
            self._rolling_limit = None
            self._chunk_sizes = tuple(
                c for c in _CHUNK_CANDIDATES
                if c <= max(max_prefill_chunk, 1)) or (1,)
            if use_paged_kernel is None:
                # the fused kernel runs compiled on TPU; in interpret mode
                # the jnp gather path is the faster correctness path
                use_paged_kernel = jax.default_backend() == "tpu"
            self.use_paged_kernel = bool(use_paged_kernel)
            interp = self.interpret

            def _pgexec(p, c, t, pos2, ptab, act):
                logits, c2 = lm.paged_step(
                    p, cfg, c, t, pos2, ptab, act, page_size=page_size,
                    use_kernel=use_paged_kernel, interpret=interp)
                next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
                return next_tok, c2

            self._paged_exec = jax.jit(_pgexec)
            return

        self.cache = lm.init_cache(cfg, n_slots, max_seq_len, dtype=cache_dtype)

        def _step(p, c, t, pos, active, reset):
            c = lm.reset_slots(cfg, c, reset)
            logits, c2 = lm.decode_step(p, cfg, c, t, pos)
            c2 = lm.mask_cache_update(cfg, c, c2, active)
            next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return next_tok, c2

        self._decode = jax.jit(_step)

        def _pstep(p, c, t, pos, active, reset):
            # reset fuses into the step: a freshly-claimed slot is wiped of
            # its previous occupant's KV *and* recurrent SSM state
            c = lm.reset_slots(cfg, c, reset)
            logits, c2 = lm.prefill_step(p, cfg, c, t, pos)
            c2 = lm.mask_cache_update(cfg, c, c2, active)
            # greedy token after the chunk's last position (all the caller
            # consumes; earlier columns' logits are dead)
            next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return next_tok, c2

        self._prefill = jax.jit(_pstep)

    def _allowed_chunk_sizes(self, cap: int) -> Tuple[int, ...]:
        """Power-of-two chunk sizes compatible with every cache family: they
        must not violate the SSD scan's chunk-divisibility requirement, and
        rolling SWA buffers additionally bound *where* chunks may be used —
        a multi-token write at positions >= window evicts ring slots that
        the chunk's own earlier queries still need, so chunking is only
        sound while the whole prompt prefix fits the ring (see
        ``_prefill_chunks``)."""
        cfg = self.cfg
        rolling: List[int] = []
        if cfg.local_global_every == 2 and cfg.sliding_window:
            rolling.append(min(cfg.sliding_window, self.max_seq_len))
        elif cfg.sliding_window is not None and cfg.local_global_every == 0:
            rolling.append(lm.cache_seq_len(cfg, self.max_seq_len))
        self._rolling_limit = min(rolling) if rolling else None
        ssd_chunk = cfg.ssm.chunk_size if cfg.ssm is not None else 0
        out = []
        for c in _CHUNK_CANDIDATES:
            if c > max(cap, 1):
                continue
            if any(r % c != 0 for r in rolling):
                continue
            if ssd_chunk and c > ssd_chunk and c % ssd_chunk != 0:
                continue
            out.append(c)
        return tuple(out) or (1,)

    # ------------------------------------------------------------------ #
    def _adopt_cache(self, cache):
        """Hook for subclasses to re-commit device placement after a
        host-side cache mutation (slot install).  Identity here; the
        sharded engine re-applies its NamedShardings so the next step hits
        the already-compiled partitioned program."""
        return cache

    def release_devices(self) -> None:
        """Return any exclusively-held devices when this replica retires.
        The single-device engine owns nothing exclusively; the sharded
        engine hands its submesh back to the allocator."""

    # ------------------------------------------------------------------ #
    def max_prompt_len(self, max_new_tokens: int = 1) -> int:
        """Longest prompt that still fits the cache AND leaves decode room
        for ``max_new_tokens`` before step()'s position guard trips: prefill
        writes positions 0..P-1, decode writes P..P+max_new-2 and the guard
        stops at max_seq_len-1."""
        return max(1, self.max_seq_len - max(max_new_tokens, 1))

    def submit(self, req: Request) -> None:
        if req.arrival_time == 0.0:
            # an unstamped arrival would make age_s/TTFT ≈ monotonic() since
            # boot — every slo-aware genome would see a violated SLO
            req.arrival_time = time.monotonic()
        limit = self.max_prompt_len(req.max_new_tokens)
        if len(req.prompt) > limit:
            if not self.truncate_long_prompts:
                raise ValueError(
                    f"prompt of {len(req.prompt)} tokens exceeds engine limit "
                    f"{limit} (max_seq_len={self.max_seq_len})")
            # replace() keeps every accounting field (first_token_time,
            # prior_generated, retries, not_before) on the truncated copy
            req = replace(req, prompt=req.prompt[-limit:])
        self.waiting.append(req)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.n_slots) if s not in self.active]

    @property
    def load(self) -> int:
        """Outstanding work: queued + in-flight requests (pool routing key)."""
        return len(self.waiting) + len(self.active)

    @property
    def param_bytes(self) -> int:
        """Bytes of the weights each step reads (all stages, all shards)."""
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(self.params))

    # request-domain policy dispatch (request_ctx_for/_score/
    # _select_admissions/_maybe_preempt/migration_ctx_for) is inherited
    # from RequestSchedulingMixin — shared verbatim with the shadow twin

    # ------------------------------------------------------------------ #
    # paged KV pool: page accounting, prefix index, kv_cache policy hooks
    # ------------------------------------------------------------------ #
    @property
    def prefix_hits(self) -> int:
        return self.prefix_index.hits if self.paged else 0

    @property
    def prefix_tokens_saved(self) -> int:
        return self.prefix_index.tokens_matched if self.paged else 0

    def _kv_ctx(self, node=None, prefix_pages: int = 0,
                prompt_len: int = 0, now: float = 0.0) -> kvcache.KVCacheCtx:
        pool = self.page_pool
        if node is None:
            return kvcache.KVCacheCtx(
                prefix_pages=prefix_pages, prompt_len=prompt_len, hits=0,
                idle_s=0.0, pool_free=pool.free_pages,
                pool_total=pool.n_pages)
        return kvcache.KVCacheCtx(
            prefix_pages=node.depth, prompt_len=0, hits=node.hits,
            idle_s=max(now - node.last_used, 0.0),
            pool_free=pool.free_pages, pool_total=pool.n_pages)

    def _evict_one(self) -> bool:
        """Drop the retained prefix block the kv_cache policy likes least
        (default LRU).  Frees a physical page only when no active request
        still shares it — the loop in _alloc_page keeps evicting until one
        does."""
        cands = self.prefix_index.leaves()
        if not cands:
            return False
        now = time.monotonic()
        kp = self.kv_cache_policy

        def prio(node):
            if kp is not None and not self._hook_open("kv_cache"):
                try:
                    p = float(kp.evict_priority(self._kv_ctx(node, now=now)))
                except Exception:  # noqa: BLE001 — advisory, never fatal
                    self._hook_error("kv_cache")
                else:
                    self._hook_ok("kv_cache")
                    return p
            return max(now - node.last_used, 0.0)           # LRU fallback

        victim = max(cands, key=prio)
        self.prefix_index.remove(victim)
        self.page_pool.unref(victim.page)
        self.prefix_evictions += 1
        return True

    def _alloc_page(self) -> int:
        pid = self.page_pool.alloc()
        while pid is None:
            if not self._evict_one():
                raise RuntimeError(
                    "KV page pool exhausted with nothing left to evict")
            pid = self.page_pool.alloc()
        return pid

    def _ensure_pages(self, slot: int, upto_tokens: int) -> None:
        """Map enough logical blocks for positions < upto_tokens."""
        pages = self._slot_pages[slot]
        need = -(-upto_tokens // self.page_size)
        while len(pages) < need:
            pid = self._alloc_page()
            self._ptab[slot, len(pages)] = pid
            pages.append(pid)

    def _maybe_insert_prefix(self, seq: List[int], pages: List[int],
                             now: float) -> None:
        """Retain a finished request's full pages in the prefix index, gated
        by the kv_cache policy's ``cache_prefix`` admission hook."""
        n_full = min(len(seq) // self.page_size, len(pages))
        for j in range(n_full):          # a migrated-in SWA slot may map the
            if pages[j] == kvcache.TRASH_PAGE:   # trash page below its window
                n_full = j
                break
        if n_full == 0:
            return
        admit = True
        kp = self.kv_cache_policy
        if kp is not None and not self._hook_open("kv_cache"):
            try:
                admit = bool(kp.cache_prefix(self._kv_ctx(
                    prefix_pages=n_full, prompt_len=len(seq))))
            except Exception:  # noqa: BLE001 — advisory, never fatal
                self._hook_error("kv_cache")
                admit = True
            else:
                self._hook_ok("kv_cache")
        if not admit:
            return
        new_nodes = self.prefix_index.insert(
            seq[:n_full * self.page_size], pages[:n_full], now)
        for node in new_nodes:           # the index holds its own page share
            self.page_pool.ref(node.page)

    def _release_pages(self, slot: int, st: RequestState) -> None:
        """Return a departing request's page references; its written-through
        full pages are first offered to the prefix index so the NEXT request
        sharing the prompt (or this one's own continuation after preemption)
        maps them copy-free."""
        pages = self._slot_pages.pop(slot, [])
        if pages and self.prefix_cache_enabled:
            seq = (list(st.request.prompt) + list(st.generated))[:st.position]
            self._maybe_insert_prefix(seq, pages, time.monotonic())
        for pid in pages:
            self.page_pool.unref(pid)
        self._ptab[slot, :] = 0

    def _on_slot_released(self, slot: int, st: RequestState) -> None:
        if self.paged:
            self._release_pages(slot, st)

    def _retire(self, slot: int, st: RequestState) -> None:
        st.done = True
        st.finish_time = time.monotonic()
        self.finished.append(st)
        del self.active[slot]
        if self.paged:
            self._release_pages(slot, st)

    def release_all_pages(self) -> int:
        """Drop every page reference this engine holds — active slots AND
        retained prefix nodes — so a dead replica's refcounts return to the
        pool exactly once.  Returns the pool's remaining used_pages (0 means
        no leak; the pool object may be shared in tests)."""
        if not self.paged:
            return 0
        for slot in list(self._slot_pages):
            for pid in self._slot_pages.pop(slot):
                self.page_pool.unref(pid)
        self._ptab[:, :] = 0
        while True:
            leaves = self.prefix_index.leaves()
            if not leaves:
                break
            for leaf in leaves:
                self.prefix_index.remove(leaf)
                self.page_pool.unref(leaf.page)
        return self.page_pool.used_pages

    # ------------------------------------------------------------------ #
    # live slot migration (cache-state transfer across engines)
    # ------------------------------------------------------------------ #
    def export_slot(self, slot: int, with_state: bool = True) -> SlotExport:
        """Pop one active request out of its slot, packed for migration.

        ``with_state=False`` skips the device→host cache copy when the
        caller already knows it will recompute (requeue the continuation).
        """
        st = self.active.pop(slot)
        req = st.request
        remaining = max(req.max_new_tokens - len(st.generated), 1)
        cont = Request(req.rid, list(req.prompt) + list(st.generated),
                       remaining, req.eos_id, req.arrival_time,
                       first_token_time=st.first_token_time,
                       prior_generated=st.prior_generated + len(st.generated),
                       retries=req.retries)   # retry budget survives migration
        if self.paged:
            # page-granular export in the CONTIGUOUS extract format: the
            # target may be paged or not — one wire format either way
            cache = (self._extract_paged_slot_state(slot, st.position)
                     if with_state else None)
            self._release_pages(slot, st)
        else:
            cache = self._extract_slot_state(slot) if with_state else None
        return SlotExport(cont, st, self.cfg, cache, st.position)

    def _extract_slot_state(self, slot: int):
        """Contiguous-path slot extract — overridden by engines whose cache
        is not one monolithic pytree (PipelinedEngine reassembles per-stage
        slices into the same full per-layer wire format)."""
        return lm.extract_slot(self.cfg, self.cache, slot)

    def _install_slot_state(self, slot: int, state, position: int):
        """Contiguous-path slot install; returns the new cache pytree.
        The pipelined override slices ``state`` at its stage boundaries."""
        return lm.install_slot(self.cfg, self.cache, slot, state, position)

    def _extract_paged_slot_state(self, slot: int, position: int):
        """Paged slot extract into the contiguous wire format — overridden
        by PipelinedEngine to concatenate per-stage pool slices (same page
        ids in every stage, lockstep pools)."""
        return lm.extract_paged_slot(self.cfg, self.cache,
                                     self._slot_pages[slot], position,
                                     self.page_size)

    def _install_paged_slot_state(self, pages, state, position: int):
        """Scatter a contiguous-format state into freshly-owned pages;
        returns the new cache pytree.  The pipelined override slices
        ``state`` at its stage boundaries and installs per stage."""
        return lm.install_paged_slot(self.cfg, self.cache, pages, state,
                                     position, self.page_size)

    def export_active(self, with_state: bool = True) -> List[SlotExport]:
        """Export every in-flight request (lowest slot first)."""
        return [self.export_slot(s, with_state=with_state)
                for s in sorted(self.active)]

    def install_active(self, export: SlotExport) -> bool:
        """Adopt a migrated slot directly into a free slot — no re-prefill.

        Returns False (engine unchanged) when the state cannot live here:
        no free slot, different model config, not enough decode headroom for
        the remaining budget (step()'s position guard would silently cut the
        request short — the same fit rule as ``max_prompt_len``), or buffer
        shapes the extracted state cannot be scattered into.  Callers then
        fall back to resubmitting ``export.request`` (recompute).
        """
        free = self.free_slots()
        remaining = max(export.request.max_new_tokens, 1)
        # step() retires a slot once position hits max_seq_len - 1, so the
        # full remaining budget needs position + remaining < max_seq_len
        # (budget completing exactly at the guard is fine)
        if (not free or export.cache is None or export.cfg != self.cfg
                or export.position + remaining >= self.max_seq_len):
            return False
        slot = free[0]
        if self.paged:
            return self._install_paged(export, slot)
        try:
            cache = self._install_slot_state(slot, export.cache,
                                             export.position)
        except lm.SlotMigrationError:
            return False
        self.cache = self._adopt_cache(cache)
        st = export.state
        st.slot = slot
        self.active[slot] = st
        return True

    def _install_paged(self, export: SlotExport, slot: int) -> bool:
        """Adopt a migrated slot into freshly-owned pages.  SWA blocks wholly
        below the attention window map the trash page (their positions are
        never read again) instead of spending physical pages."""
        page = self.page_size
        position = export.position
        window = lm.paged_window(self.cfg)
        lo_req = 0 if window is None else max(position - window + 1, 0)
        n_blocks = -(-position // page)
        pages: List[int] = []
        try:
            for j in range(n_blocks):
                if (j + 1) * page <= lo_req:
                    pages.append(kvcache.TRASH_PAGE)
                else:
                    pages.append(self._alloc_page())
            cache = self._install_paged_slot_state(pages, export.cache,
                                                   position)
        except (lm.SlotMigrationError, RuntimeError):
            for pid in pages:
                self.page_pool.unref(pid)
            return False
        self.cache = self._adopt_cache(cache)
        self._slot_pages[slot] = pages
        self._ptab[slot, :] = 0
        self._ptab[slot, :len(pages)] = pages
        st = export.state
        st.slot = slot
        self.active[slot] = st
        return True

    # ------------------------------------------------------------------ #
    def _prefill_into_slot(self, req: Request, slot: int) -> None:
        """Write the prompt's KV/SSM state into the slot region and produce
        the first generated token (greedy logits at the last prompt position).

        Chunked mode decomposes the prompt into descending power-of-two
        chunks — O(log prompt_len) dispatches, exact semantics (no padding).
        """
        st = RequestState(req, slot, admitted_at=time.monotonic())
        self.active[slot] = st
        prompt = req.prompt or [0]
        with _span("prefill", rid=req.rid, prompt=len(prompt)) as span:
            if self.paged:
                last = self._paged_prefill(st, prompt)
            elif not self.chunked_prefill:
                last = 0
                for i, tok in enumerate(prompt):
                    last = self._advance_slot(st, tok, wipe_slot=(i == 0))
                    st.prefill_dispatches += 1
            else:
                last = self._prefill_chunks(st, prompt)
            span.set_metadata(matched=st.prefix_matched,
                              tokens=len(prompt) - st.prefix_matched)
        st.generated.append(last)
        st.first_token_time = time.monotonic()
        if req.first_token_time is not None:
            # continuation of a preempted/migrated request: keep the original
            # first-token time and the tokens produced in earlier lives
            st.first_token_time = req.first_token_time
        st.prior_generated = req.prior_generated

    def _prefill_chunks(self, st: RequestState, prompt: List[int]) -> int:
        slot = st.slot
        prompt_arr = np.asarray(prompt, np.int32)
        active = np.zeros((self.n_slots,), bool)
        active[slot] = True
        no_reset = np.zeros((self.n_slots,), bool)
        off, last = 0, 0
        remaining = len(prompt)
        for c in self._chunk_sizes:
            while remaining >= c:
                if (self._rolling_limit is not None and c > 1
                        and off + c > self._rolling_limit):
                    # past the ring boundary a multi-token write would evict
                    # keys this chunk's earlier queries attend to; only the
                    # per-token granularity is sound there
                    break
                tokens = np.zeros((self.n_slots, c), np.int32)
                positions = np.zeros((self.n_slots, c), np.int32)
                tokens[slot] = prompt_arr[off:off + c]
                positions[slot] = np.arange(off, off + c, dtype=np.int32)
                # first chunk wipes the slot's previous occupant
                reset = active if off == 0 else no_reset
                next_tok, self.cache = self._prefill(
                    self.params, self.cache, tokens, positions, active, reset)
                self.dispatches += 1
                st.prefill_dispatches += 1
                off += c
                remaining -= c
                last = next_tok  # device array; fetched once after the loop
        st.position = off
        return int(np.asarray(last)[slot])

    def _paged_prefill(self, st: RequestState, prompt: List[int]) -> int:
        """Prefill into pages.  A resident prompt prefix (full pages, capped
        one token short of the prompt) is mapped copy-free from the prefix
        index — those chunks are never recomputed; only the remainder is
        prefilled.  Inactive lanes' writes land in the trash page, so no
        reset/mask passes run against the shared pool."""
        slot = st.slot
        pages: List[int] = []
        matched = 0
        if self.prefix_cache_enabled:
            pages, matched = self.prefix_index.match(prompt, time.monotonic())
            st.prefix_matched = matched
            for pid in pages:            # the request's own share of each page
                self.page_pool.ref(pid)
        self._slot_pages[slot] = list(pages)
        self._ptab[slot, :] = 0
        self._ptab[slot, :len(pages)] = pages

        prompt_arr = np.asarray(prompt, np.int32)
        active = np.zeros((self.n_slots,), bool)
        active[slot] = True
        off, last = matched, 0
        remaining = len(prompt) - matched
        sizes = self._chunk_sizes if self.chunked_prefill else (1,)
        for c in sizes:
            while remaining >= c:
                self._ensure_pages(slot, off + c)
                tokens = np.zeros((self.n_slots, c), np.int32)
                positions = np.zeros((self.n_slots, c), np.int32)
                tokens[slot] = prompt_arr[off:off + c]
                positions[slot] = np.arange(off, off + c, dtype=np.int32)
                next_tok, self.cache = self._paged_exec(
                    self.params, self.cache, tokens, positions,
                    self._ptab, active)
                self.dispatches += 1
                st.prefill_dispatches += 1
                off += c
                remaining -= c
                last = next_tok              # device array; fetched once below
        st.position = off
        return int(np.asarray(last)[slot])

    def _advance_slot(self, st: RequestState, token: int,
                      wipe_slot: bool = False) -> int:
        """Legacy per-token path (one dispatch per prompt token)."""
        tokens = np.zeros((self.n_slots, 1), np.int32)
        tokens[st.slot, 0] = token
        positions = np.zeros((self.n_slots,), np.int32)
        for slot, s in self.active.items():
            positions[slot] = s.position
        active = np.zeros((self.n_slots,), bool)
        active[st.slot] = True
        reset = np.zeros((self.n_slots,), bool)
        reset[st.slot] = wipe_slot
        next_tok, self.cache = self._decode(self.params, self.cache,
                                            tokens, positions, active, reset)
        self.dispatches += 1
        st.position += 1
        return int(next_tok[st.slot])

    # ------------------------------------------------------------------ #
    def step(self) -> int:
        """One engine iteration; returns number of tokens produced."""
        with _span("step", step=self.steps):
            return self._step_body()

    def _step_body(self) -> int:
        t0 = time.monotonic()
        # 0. policy-gated preemption frees slots before admission
        self._maybe_preempt()
        # 1. admission in request-policy order (v1: FIFO slot-filling);
        #    prefill produces the first generated token, which can already
        #    satisfy the request — max_new_tokens=1 or immediate EOS
        free = self.free_slots()
        for slot, req in zip(free, self._select_admissions(len(free))):
            self._prefill_into_slot(req, slot)
            st = self.active[slot]
            if (len(st.generated) >= req.max_new_tokens
                    or st.generated[-1] == req.eos_id):
                self._retire(slot, st)

        if not self.active:
            return 0

        # 2. batched decode: assemble inputs host-side, ship once
        with _span("decode", step=self.steps, live=len(self.active)):
            tokens = np.zeros((self.n_slots, 1), np.int32)
            positions = np.zeros((self.n_slots,), np.int32)
            active = np.zeros((self.n_slots,), bool)
            live: List[RequestState] = []
            for slot, st in self.active.items():
                tokens[slot, 0] = st.generated[-1]
                positions[slot] = st.position
                active[slot] = True
                live.append(st)
            if self.paged:
                for st in live:          # map the block this write lands in
                    self._ensure_pages(st.slot, st.position + 1)
            with _span("decode.dispatch"):
                if self.paged:
                    next_tok, self.cache = self._paged_exec(
                        self.params, self.cache, tokens, positions[:, None],
                        self._ptab, active)
                else:
                    next_tok, self.cache = self._decode(
                        self.params, self.cache, tokens, positions, active,
                        np.zeros((self.n_slots,), bool))
            self.dispatches += 1
            with _span("decode.sync"):
                next_np = np.asarray(next_tok)  # one device→host transfer
        # 3. append each slot's token and retire the finished
        produced = 0
        retired = 0
        with _span("retire") as span:
            for st in live:
                tok = int(next_np[st.slot])
                st.position += 1
                st.generated.append(tok)
                produced += 1
                req = st.request
                if (len(st.generated) >= req.max_new_tokens
                        or tok == req.eos_id
                        or st.position >= self.max_seq_len - 1):
                    self._retire(st.slot, st)
                    retired += 1
            span.set_metadata(retired=retired)
        self.steps += 1
        self._record_step_time(time.monotonic() - t0)
        return produced

    def _record_step_time(self, dt: float) -> None:
        """EMA of measured step wall-time, scaled by the injected straggler
        multiplier (the fault model degrades the *observation*, so the pool's
        detector sees the slowdown without real sleeps)."""
        dt *= self.fault_slowdown
        if self.health_samples == 0:
            self.step_ema_s = dt
        else:
            self.step_ema_s = 0.7 * self.step_ema_s + 0.3 * dt
        self.health_samples += 1

    def run_until_drained(self, max_steps: int = 10_000) -> List[RequestState]:
        taken = 0
        while (self.waiting or self.active) and taken < max_steps:
            self.step()
            taken += 1
        if self.waiting or self.active:
            raise DrainStallError(
                f"engine stalled: {len(self.waiting)} waiting, "
                f"{len(self.active)} active after {max_steps} steps")
        return self.finished
