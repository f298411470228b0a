"""Mesh-sharded replica execution: TP×DP×PP engines on carved submeshes.

Each :class:`repro.core.plan.ReplicaGroup` with ``tp * dp > 1`` materialises
as a :class:`ShardedEngine` running on a private ``(dp, tp)`` submesh carved
out of the process's device set by a :class:`SubmeshAllocator` (the dynamic
counterpart of :func:`repro.launch.mesh.carve_submeshes` — same deterministic
device order, but replicas come and go, so carving is an alloc/release
protocol instead of a one-shot partition).  A group with ``pp > 1`` instead
builds a :class:`PipelinedEngine`: the layer stack is cut at the group's
``stage_cuts`` and each stage runs on its OWN ``(dp, tp)`` stage submesh —
stages tolerate fragmented free sets because each stage submesh can land on
a different free fragment (FlexPipe's observation: pipeline depth is the
degree of freedom that soaks up odd-sized capacity TP cannot use).

Execution strategy (how the sharding actually happens):

  * **Dense TP** — parameters and KV caches are committed onto the submesh
    with :mod:`repro.distributed.sharding`'s Megatron rules via
    ``jax.device_put``; the engine's ordinary ``jax.jit`` step closures then
    compile to partitioned SPMD programs (GSPMD propagates the committed
    input shardings — no explicit ``in_shardings`` needed, and host-side
    NumPy step inputs stay replicated).  ``fsdp_axis`` is disabled: a
    serving replica replicates weights across its data axis rather than
    paying per-step ZeRO-3 all-gathers.
  * **Expert parallelism** (Mixtral-family) — GSPMD has no partition rule
    for ``pallas_call``, so the MoE FFN routes through
    :func:`repro.distributed.expert_parallel.ep_moe_mix`: an explicit
    ``shard_map`` over the expert axis running the grouped
    ``kernels/moe_gmm`` matmul per shard.  The engine requests it by setting
    the trace-time ``ep_shard`` flag around every jitted call (the flag is
    read inside ``lm._ffn_fwd`` when the closure first traces).
  * **DP** — the slot batch is sharded across the submesh's ``data`` axis
    when divisible (``sharding._batch_entry`` falls back to replication
    otherwise), so one replica's decode step fans out over dp weight copies.
  * **PP** — per-stage params are pure ``layers[lo:hi]`` slices
    (:func:`repro.models.lm.slice_stage_params`); prefill streams each
    chunk through the stages in up to ``pp`` micro-chunks (bounding the
    inter-stage activation footprint; jax's async dispatch lets stage ``i``
    start on micro-chunk ``m+1`` while stage ``i+1`` still runs ``m``) and
    decode hands the (B, 1, D) hidden state between stage submeshes via a
    replicated ``device_put`` — d_model·dtype bytes per token, the
    hand-off term :mod:`repro.distributed.hlo_analysis` prices.

Migration interop: slot export/install rides the existing host-side NumPy
wire formats (:func:`repro.models.lm.extract_slot` and friends), which are
TP-agnostic AND stage-agnostic — a pipelined export concatenates its
per-stage slices back into the full per-layer wire format
(:func:`repro.models.lm.concat_stage_states`), so a slot exported from a
pp=2 replica installs into a pp=4, tp=2, or plain replica unchanged; that
is what lets a reconfigure RE-CUT stage boundaries mid-decode without
dropping in-flight requests.  :meth:`ShardedEngine._adopt_cache` (and the
pipelined per-stage variant) re-commits the cache sharding after such
host-side installs so the next step hits the compiled partitioned program
instead of recompiling for an uncommitted layout.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.core.plan import ReplicaGroup, default_stage_cuts, valid_stage_cuts
from repro.distributed import sharding
from repro.models import flags, lm
from repro.serving import kvcache
from repro.serving.engine import Engine


class SubmeshOversubscribed(RuntimeError):
    """An allocation asked for more devices than the allocator has free."""


class SubmeshAllocator:
    """Carves per-replica (or per-stage) submeshes from a fixed device set.

    Deterministic: devices are handed out in ascending ``device.id`` order
    and returned to the free list in sorted order, so the same alloc/release
    sequence always yields the same physical placement — replica rebuilds
    are reproducible and the shadow rung's cost attribution stays stable.

    The free set FRAGMENTS under interleaved alloc/release (elastic traces
    release replicas out of order), so allocation is fragment-aware:
    :meth:`alloc` best-fits the request into the smallest contiguous-id
    fragment that holds it (a TP/DP submesh wants one bandwidth island) and
    falls back to gathering across fragments rather than spuriously raising
    :class:`SubmeshOversubscribed` while enough devices are free.
    :meth:`alloc_stages` carves one submesh PER pipeline stage, so a pp
    replica soaks up capacity no single fragment could serve.
    """

    def __init__(self, devices: Optional[Sequence] = None,
                 axes: Tuple[str, ...] = ("pipe", "data", "model"),
                 mesh_factory: Optional[Callable] = None):
        if devices is None:
            devices = jax.devices()
        self.axes = tuple(axes)
        self._mesh_factory = mesh_factory or Mesh
        self._free: List = sorted(devices, key=lambda d: d.id)
        # id(mesh) -> (mesh, devices): holding the mesh keeps its id stable
        self._owned: Dict[int, Tuple[Mesh, List]] = {}

    @property
    def free_devices(self) -> int:
        return len(self._free)

    @property
    def total_devices(self) -> int:
        return len(self._free) + sum(len(d) for _, d in self._owned.values())

    def fragments(self) -> List[List]:
        """Maximal runs of consecutive device ids in the free set — the
        bandwidth islands interleaved releases leave behind."""
        out: List[List] = []
        for d in self._free:
            if out and d.id == out[-1][-1].id + 1:
                out[-1].append(d)
            else:
                out.append([d])
        return out

    def _select(self, n: int) -> List:
        """Pick ``n`` free devices: best-fit into the smallest fragment that
        holds the whole request, else gather across fragments in id order
        (correct, just bandwidth-fragmented — never a spurious failure)."""
        fits = [f for f in self.fragments() if len(f) >= n]
        take = min(fits, key=len)[:n] if fits else self._free[:n]
        ids = {d.id for d in take}
        self._free = [d for d in self._free if d.id not in ids]
        return take

    def can_alloc(self, shape: Sequence[int]) -> bool:
        return int(np.prod(tuple(shape))) <= len(self._free)

    def alloc(self, shape: Sequence[int]) -> Mesh:
        """Carve one submesh.  ``shape`` maps onto the TRAILING axis names:
        2-D shapes become ``(data, model)`` meshes, 3-D ``(pipe, data,
        model)``.  Raises only when the free set is genuinely too small."""
        shape = tuple(int(s) for s in shape)
        n = int(np.prod(shape))
        if n > len(self._free):
            raise SubmeshOversubscribed(
                f"submesh {shape} needs {n} devices but only "
                f"{len(self._free)} of {self.total_devices} are free")
        take = self._select(n)
        grid = np.array(take, dtype=object).reshape(shape)
        mesh = self._mesh_factory(grid, self.axes[-len(shape):])
        self._owned[id(mesh)] = (mesh, take)
        return mesh

    def try_alloc(self, shape: Sequence[int]) -> Optional[Mesh]:
        return self.alloc(shape) if self.can_alloc(shape) else None

    def can_alloc_stages(self, pp: int, stage_shape: Sequence[int]) -> bool:
        return pp * int(np.prod(tuple(stage_shape))) <= len(self._free)

    def alloc_stages(self, pp: int,
                     stage_shape: Sequence[int]) -> List[Mesh]:
        """Carve ``pp`` stage submeshes of ``stage_shape`` each.  Stages may
        land on different fragments — that is the point: a (pp=2, tp=2)
        replica fits a free set of two 2-device islands that no (1, 4)
        submesh prefers."""
        if not self.can_alloc_stages(pp, stage_shape):
            n = pp * int(np.prod(tuple(stage_shape)))
            raise SubmeshOversubscribed(
                f"{pp} stages of {tuple(stage_shape)} need {n} devices but "
                f"only {len(self._free)} of {self.total_devices} are free")
        return [self.alloc(stage_shape) for _ in range(pp)]

    def try_alloc_stages(self, pp: int,
                         stage_shape: Sequence[int]) -> Optional[List[Mesh]]:
        if not self.can_alloc_stages(pp, stage_shape):
            return None
        return self.alloc_stages(pp, stage_shape)

    def release(self, mesh: Mesh) -> None:
        """Return a submesh's devices; releasing twice (or a foreign mesh)
        is a no-op so teardown paths need no is-mine bookkeeping."""
        entry = self._owned.pop(id(mesh), None)
        if entry is None:
            return
        self._free = sorted(self._free + entry[1], key=lambda d: d.id)


def fused_paged_unsupported_reason(cfg: ModelConfig,
                                   tp: int) -> Optional[str]:
    """Why the fused paged flash-decode kernel cannot run for this
    (config, tp) — ``None`` when it can.

    The shard_map wrapper splits the pool's KV heads across ``tp`` shards,
    so head counts must divide; the kernel itself has no softcap epilogue
    and no MLA (latent-cache) variant.  Mirrors the engine's trace-time
    gate in :func:`repro.models.layers.paged_attention_fwd` so the recorded
    fallback and the actual execution path cannot drift apart.
    """
    if cfg.mla is not None:
        return "mla"
    if cfg.attn_logit_softcap is not None:
        return "softcap"
    if tp > 1 and cfg.n_kv_heads % tp != 0:
        return "kv_heads"
    return None


class ShardedEngine(Engine):
    """An :class:`Engine` whose params/cache live sharded on a submesh.

    Behaviourally identical to the base engine (same slots, paging,
    migration, scheduling hooks) — only the placement of device state and
    the compiled step programs differ.  Token outputs are identical to a
    single-device engine up to floating-point reduction order; the sharded
    parity tests pin float32 so greedy argmax matches exactly.
    """

    def __init__(self, cfg: ModelConfig, params, mesh: Mesh,
                 allocator: Optional[SubmeshAllocator] = None, **kw):
        self.mesh = mesh
        self.allocator = allocator
        pol = sharding.make_policy(mesh, cfg)
        # serving replicas replicate weights across the data axis: ZeRO-3
        # gathers per decode step would swamp the tiny per-token compute
        pol = dataclasses.replace(pol, fsdp_axis=None)
        self.sharding_policy = pol
        # the decision records every divisibility fallback so downstream
        # costing (hlo_analysis / shadow) prices replicated dims honestly
        self.decision = sharding.sharding_decision(cfg, pol, params)
        self._ep_flag = ({"mesh": mesh, "axis": pol.tp_axis}
                         if pol.ep else None)
        # pallas_call has no GSPMD partition rule, so the fused paged-decode
        # kernel cannot run inside a partitioned jit directly — but (like
        # the EP moe_gmm path) it CAN run under an explicit shard_map over
        # the head-sharded pool.  Enable it when the config supports that;
        # otherwise force the unfused gather path and RECORD the downgrade
        # in the ShardingDecision so costing consumers see it.
        self._paged_shard_flag = None
        self.paged_kernel_fused = False
        paged_will = kw.get("paged")
        if paged_will is None:
            paged_will = lm.pageable(cfg)
        if paged_will:
            tp = mesh.shape[pol.tp_axis]
            reason = fused_paged_unsupported_reason(cfg, tp)
            if reason is None:
                self.paged_kernel_fused = True
                if tp > 1:
                    self._paged_shard_flag = {"mesh": mesh,
                                              "axis": pol.tp_axis}
            else:
                kw["use_paged_kernel"] = False
                if reason == "kv_heads":
                    # a real tp downgrade: the pool replicates its KV heads
                    # and decode gathers — visible to tp_fallback_fraction
                    self.decision.fallbacks.append(sharding.FallbackRecord(
                        "paged_kernel", 3, cfg.n_kv_heads, pol.tp_axis, tp))
                else:
                    # kernel-capability gap (mla/softcap), not a sharding
                    # downgrade: axis="" keeps tp_fallback_fraction honest
                    self.decision.fallbacks.append(sharding.FallbackRecord(
                        f"paged_kernel:{reason}", 3, cfg.n_kv_heads, "", tp))
        super().__init__(cfg, params, **kw)
        self.params = jax.device_put(
            self.params, sharding._ns(mesh, self.decision.param_specs))
        spec_fn = (sharding.paged_cache_pspecs if self.paged
                   else sharding.cache_pspecs)
        self._cache_ns = sharding._ns(mesh, spec_fn(cfg, pol, self.cache))
        self.cache = jax.device_put(self.cache, self._cache_ns)
        scope = {}
        if self._ep_flag is not None:
            scope["ep_shard"] = self._ep_flag
        if self.paged and self._paged_shard_flag is not None:
            scope["paged_shard"] = self._paged_shard_flag
        self.trace_flags = scope
        if scope:
            if self.paged:
                self._paged_exec = self._with_flags(self._paged_exec, scope)
            else:
                self._decode = self._with_flags(self._decode, scope)
                self._prefill = self._with_flags(self._prefill, scope)

    # -------------------------------------------------------------- #
    @property
    def tp(self) -> int:
        return self.mesh.shape[self.sharding_policy.tp_axis]

    @property
    def dp(self) -> int:
        return self.mesh.shape.get("data", 1)

    def _with_flags(self, fn, scope):
        """Every call enters the given trace-time flag scope (``ep_shard``,
        ``paged_shard``): the flags only matter when the jitted closure
        first traces, but the context entry is cheap and keying on it keeps
        retraces correct."""
        def run(*args):
            with flags.scoped(**scope):
                return fn(*args)
        return run

    def _adopt_cache(self, cache):
        """Re-commit the sharded layout after a host-side slot install —
        ``lm.install_slot``/``install_paged_slot`` scatter NumPy state into
        the cache eagerly, which can leave leaves with a propagated (or
        uncommitted) layout; without this the next decode step would
        recompile against the wrong input sharding."""
        return jax.device_put(cache, self._cache_ns)

    def release_devices(self) -> None:
        """Return this replica's submesh to the allocator (idempotent).
        Called by the pool when the replica retires — planned teardown in
        ``reconfigure`` or unplanned death in ``fail`` — so the freed
        devices are immediately carveable for the next plan's groups."""
        if self.allocator is not None:
            self.allocator.release(self.mesh)
            self.allocator = None


class PipelinedEngine(Engine):
    """An :class:`Engine` whose layer stack is cut into ``pp`` stages.

    Stage ``i`` holds params/cache for layers ``[bounds[i], bounds[i+1])``
    (``bounds = (0,) + stage_cuts + (n_layers,)``) — a pure slice of the
    stacked ``params["layers"]`` pytree — plus the embedding on the first
    stage and the final norm + LM head on the last.  With ``stage_meshes``
    each stage commits onto its own ``(dp, tp)`` submesh exactly like a
    :class:`ShardedEngine`; without meshes (single-device hosts, tier-1
    tests) the stages share the default device and the pipeline is purely
    logical — token-identical either way, because composing the per-stage
    scans reproduces the monolithic forward's reduction order.

    Scheduling, slots, chunked prefill and migration all come from the base
    engine unchanged: only the jitted step closures are replaced by Python
    stage loops (prefill additionally micro-chunks each prefill chunk, see
    :meth:`_pipe_prefill`).  Paged KV serves from PER-STAGE page pools:
    each stage's cache is its layer slice of the paged pool, and the
    host-side :class:`~repro.serving.kvcache.StagedPagePool` /
    ``StagedPrefixIndex`` keep every stage's allocator and prefix trie in
    lockstep, so one page table drives all stages and cross-request prefix
    reuse works under pp.  Slot export/install reassembles / re-slices the
    full per-layer wire format (contiguous OR paged), so re-cutting stage
    boundaries (or moving pp↔tp, paged↔contiguous) migrates in-flight
    requests without dropping them.
    """

    def __init__(self, cfg: ModelConfig, params,
                 stage_cuts: Sequence[int],
                 stage_meshes: Optional[Sequence[Mesh]] = None,
                 allocator: Optional[SubmeshAllocator] = None,
                 microbatches: Optional[int] = None, **kw):
        if not lm.stage_sliceable(cfg):
            raise ValueError(
                f"{cfg.name}: family {cfg.family!r} cannot be stage-sliced")
        cuts = tuple(int(c) for c in stage_cuts)
        pp = len(cuts) + 1
        if pp < 2 or not valid_stage_cuts(cfg.n_layers, pp, cuts):
            raise ValueError(
                f"invalid stage cuts {cuts} for a {cfg.n_layers}-layer model")
        self.stage_cuts = cuts
        self._bounds = (0,) + cuts + (cfg.n_layers,)
        self.stage_meshes = (list(stage_meshes)
                             if stage_meshes is not None else None)
        if self.stage_meshes is not None and len(self.stage_meshes) != pp:
            raise ValueError(
                f"got {len(self.stage_meshes)} stage meshes for pp={pp}")
        self.allocator = allocator
        self.microbatches = pp if microbatches is None else int(microbatches)
        # the base init builds the engine-global paged bookkeeping and a
        # monolithic pool; _build_stages then slices the pool per stage and
        # swaps the allocator/trie for their lockstep per-stage versions
        self._use_paged_kernel_kw = kw.get("use_paged_kernel")
        super().__init__(cfg, params, **kw)
        self._build_stages(self.params)

    # -------------------------------------------------------------- #
    @property
    def pp(self) -> int:
        return len(self._bounds) - 1

    @property
    def tp(self) -> int:
        if self.stage_meshes:
            return self.stage_meshes[0].shape.get("model", 1)
        return 1

    @property
    def dp(self) -> int:
        if self.stage_meshes:
            return self.stage_meshes[0].shape.get("data", 1)
        return 1

    def _build_stages(self, params) -> None:
        cfg, pp = self.cfg, self.pp
        full_cache = self.cache
        self._stage_fns: List = []
        self._stage_flags: List = []
        self._stage_ns: List = [None] * pp
        self.stage_decisions: List = [None] * pp
        stage_tp = (self.stage_meshes[0].shape.get("model", 1)
                    if self.stage_meshes else 1)
        use_kernel = False
        self.paged_kernel_fused = False
        if self.paged:
            reason = fused_paged_unsupported_reason(cfg, stage_tp)
            if reason is None:
                self.paged_kernel_fused = True
                use_kernel = self._use_paged_kernel_kw
                if use_kernel is None:
                    use_kernel = jax.default_backend() == "tpu"
        self.use_paged_kernel = bool(use_kernel)
        stage_params, stage_caches = [], []
        for i in range(pp):
            lo, hi = self._bounds[i], self._bounds[i + 1]
            first, last = i == 0, i == pp - 1
            sp = lm.slice_stage_params(cfg, params, lo, hi, first, last)
            sc = lm.slice_stage_cache(full_cache, lo, hi)
            mesh = self.stage_meshes[i] if self.stage_meshes else None
            scope = {}
            if mesh is not None:
                pol = dataclasses.replace(sharding.make_policy(mesh, cfg),
                                          fsdp_axis=None)
                decision = sharding.sharding_decision(cfg, pol, sp)
                if self.paged and not self.paged_kernel_fused:
                    # same record the single-submesh engine keeps: the
                    # unfused downgrade must be visible to costing
                    reason = fused_paged_unsupported_reason(cfg, stage_tp)
                    axis = pol.tp_axis if reason == "kv_heads" else ""
                    path = ("paged_kernel" if reason == "kv_heads"
                            else f"paged_kernel:{reason}")
                    decision.fallbacks.append(sharding.FallbackRecord(
                        path, 3, cfg.n_kv_heads, axis, stage_tp))
                self.stage_decisions[i] = decision
                sp = jax.device_put(
                    sp, sharding._ns(mesh, decision.param_specs))
                spec_fn = (sharding.paged_cache_pspecs if self.paged
                           else sharding.cache_pspecs)
                ns = sharding._ns(mesh, spec_fn(cfg, pol, sc))
                sc = jax.device_put(sc, ns)
                self._stage_ns[i] = ns
                if pol.ep:
                    scope["ep_shard"] = {"mesh": mesh, "axis": pol.tp_axis}
                if (self.paged and self.paged_kernel_fused
                        and use_kernel and stage_tp > 1):
                    scope["paged_shard"] = {"mesh": mesh,
                                            "axis": pol.tp_axis}
            self._stage_flags.append(scope or None)
            stage_params.append(sp)
            stage_caches.append(sc)
            if self.paged:
                self._stage_fns.append(self._make_paged_stage_fn(
                    first, last, bool(use_kernel)))
            else:
                self._stage_fns.append(self._make_stage_fn(first, last))
        self.params = stage_params
        self.cache = stage_caches
        if self.paged:
            # swap the monolithic host bookkeeping for per-stage lockstep
            # pools/tries over the stages' layer slices; page ids and trie
            # contents stay engine-wide consistent by construction
            stages = [(self._bounds[i], self._bounds[i + 1])
                      for i in range(pp)]
            self.page_pool = kvcache.StagedPagePool(self.page_pool.n_pages,
                                                    stages)
            self.prefix_index = kvcache.StagedPrefixIndex(self.page_size,
                                                          stages)
            self._paged_exec = self._pipe_paged_exec
        else:
            self._decode = self._pipe_decode
            self._prefill = self._pipe_prefill

    def _make_stage_fn(self, first: bool, last: bool):
        cfg = self.cfg

        def _fn(p, c, x, pos2, active, reset):
            c = lm.reset_slots(cfg, c, reset)
            out, c2 = lm.stage_step(p, cfg, c, x, pos2,
                                    first=first, last=last)
            c2 = lm.mask_cache_update(cfg, c, c2, active)
            if last:
                out = jnp.argmax(out[:, -1, :], axis=-1).astype(jnp.int32)
            return out, c2
        return jax.jit(_fn)

    def _make_paged_stage_fn(self, first: bool, last: bool,
                             use_kernel: bool):
        cfg, page_size, interp = self.cfg, self.page_size, self.interpret

        def _fn(p, c, x, pos2, ptab, act):
            out, c2 = lm.paged_stage_step(
                p, cfg, c, x, pos2, ptab, act, page_size=page_size,
                first=first, last=last, use_kernel=use_kernel,
                interpret=interp)
            if last:
                out = jnp.argmax(out[:, -1, :], axis=-1).astype(jnp.int32)
            return out, c2
        return jax.jit(_fn)

    def _run_stages(self, params, caches, x, pos2, active, reset):
        """One micro-chunk through every stage in order.  Between stage
        submeshes the hidden state is re-committed replicated onto the next
        stage's mesh — the inter-stage activation hand-off (d_model·dtype
        bytes per token) that the shadow cost model charges for."""
        new = []
        for i, fn in enumerate(self._stage_fns):
            if i and self.stage_meshes is not None:
                x = jax.device_put(
                    x, NamedSharding(self.stage_meshes[i], PartitionSpec()))
            scope = self._stage_flags[i]
            if scope is not None:
                with flags.scoped(**scope):
                    x, c2 = fn(params[i], caches[i], x, pos2, active, reset)
            else:
                x, c2 = fn(params[i], caches[i], x, pos2, active, reset)
            new.append(c2)
        return x, new

    def _run_paged_stages(self, params, caches, x, pos2, ptab, act):
        """One micro-chunk through every stage's paged layer slice.  Same
        hand-off contract as :meth:`_run_stages`; the page table and active
        mask ride along replicated (host NumPy), and every stage recomputes
        the identical write indices from them."""
        new = []
        for i, fn in enumerate(self._stage_fns):
            if i and self.stage_meshes is not None:
                x = jax.device_put(
                    x, NamedSharding(self.stage_meshes[i], PartitionSpec()))
            scope = self._stage_flags[i]
            if scope is not None:
                with flags.scoped(**scope):
                    x, c2 = fn(params[i], caches[i], x, pos2, ptab, act)
            else:
                x, c2 = fn(params[i], caches[i], x, pos2, ptab, act)
            new.append(c2)
        return x, new

    def _pipe_paged_exec(self, params, caches, tokens, positions, ptab, act):
        """Drop-in for the base engine's jitted ``_paged_exec`` against the
        stage lists: decode (C == 1) is a single pass; prefill chunks are
        micro-chunked like :meth:`_pipe_prefill` (sequential micro-chunks
        against the pool are exactly chunked prefill — no reset/rollback
        needed, the trash page isolates inactive lanes)."""
        B, C = tokens.shape
        mb = max(min(self.microbatches, C), 1)
        if mb > 1 and C % mb == 0:
            w = C // mb
            spans = [(j * w, (j + 1) * w) for j in range(mb)]
        else:
            spans = [(0, C)]
        out = None
        for s, e in spans:
            out, caches = self._run_paged_stages(
                params, caches, tokens[:, s:e], positions[:, s:e], ptab, act)
        return out, caches

    def _pipe_decode(self, params, caches, tokens, positions, active, reset):
        """Decode hands ONE token's hidden state stage to stage — a decode
        step's latency spans all stages (the cost model does not divide
        decode time by pp; that honesty is what keeps pp from dominating
        tp in shadow ranking)."""
        return self._run_stages(params, caches, tokens, positions[:, None],
                                active, reset)

    def _pipe_prefill(self, params, caches, tokens, positions, active, reset):
        """Microbatched prefill: split the chunk into up to ``microbatches``
        equal micro-chunks and stream them through the stages.  Sequential
        micro-chunks against the cache are exactly chunked prefill, so this
        is semantically identical to one big chunk; structurally it bounds
        the inter-stage activation buffer and (via jax async dispatch) lets
        consecutive stages overlap on different micro-chunks.  Only the
        first micro-chunk applies the slot reset."""
        B, C = tokens.shape
        mb = max(min(self.microbatches, C), 1)
        if mb > 1 and C % mb == 0:
            w = C // mb
            spans = [(j * w, (j + 1) * w) for j in range(mb)]
        else:
            spans = [(0, C)]
        no_reset = np.zeros((B,), bool)
        out = None
        for j, (s, e) in enumerate(spans):
            out, caches = self._run_stages(
                params, caches, tokens[:, s:e], positions[:, s:e],
                active, reset if j == 0 else no_reset)
        return out, caches

    # ------------------------------------------------------------------ #
    # migration wire format: reassemble / re-slice at stage boundaries
    # ------------------------------------------------------------------ #
    def _extract_slot_state(self, slot: int):
        return lm.concat_stage_states(
            [lm.extract_slot(self.cfg, c, slot) for c in self.cache])

    def _install_slot_state(self, slot: int, state, position: int):
        new = []
        for i, c in enumerate(self.cache):
            lo, hi = self._bounds[i], self._bounds[i + 1]
            part = jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi], state)
            new.append(lm.install_slot(self.cfg, c, slot, part, position))
        return new

    def _extract_paged_slot_state(self, slot: int, position: int):
        # lockstep pools ⇒ the slot's page ids are valid in every stage's
        # pool slice; concatenating the per-stage gathers reproduces the
        # monolithic engine's wire format byte-for-byte
        return lm.concat_stage_states(
            [lm.extract_paged_slot(self.cfg, c, self._slot_pages[slot],
                                   position, self.page_size)
             for c in self.cache])

    def _install_paged_slot_state(self, pages, state, position: int):
        new = []
        for i, c in enumerate(self.cache):
            lo, hi = self._bounds[i], self._bounds[i + 1]
            part = jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi], state)
            new.append(lm.install_paged_slot(self.cfg, c, pages, part,
                                             position, self.page_size))
        return new

    def _adopt_cache(self, caches):
        if self.stage_meshes is None:
            return caches
        return [c if ns is None else jax.device_put(c, ns)
                for c, ns in zip(caches, self._stage_ns)]

    def release_devices(self) -> None:
        """Return every stage submesh to the allocator (idempotent)."""
        if self.allocator is not None and self.stage_meshes:
            for m in self.stage_meshes:
                self.allocator.release(m)
        self.allocator = None


def engine_for_group(cfg: ModelConfig, params, group: ReplicaGroup,
                     allocator: Optional[SubmeshAllocator], **kw) -> Engine:
    """Build the right engine for one replica of ``group``.

    ``pp > 1`` groups build a :class:`PipelinedEngine` whose stages each get
    their own carved ``(dp, tp)`` stage submesh (or no meshes at all on a
    CPU test host — the logical pipeline is still token-identical).  A
    ``tp*dp > 1`` single-stage group gets a :class:`ShardedEngine` on one
    carved submesh.  Otherwise — single-device group, or not enough free
    devices (a plan the guard chain admitted but hardware shrank under) —
    it degrades to the plain single-device :class:`Engine`, which is
    token-identical, just slower.
    """
    if group.pp > 1 and lm.stage_sliceable(cfg) and cfg.n_layers >= group.pp:
        cuts = group.stage_cuts or default_stage_cuts(cfg.n_layers, group.pp)
        if valid_stage_cuts(cfg.n_layers, group.pp, cuts):
            meshes = None
            if allocator is not None:
                meshes = allocator.try_alloc_stages(
                    group.pp, group.stage_submesh_shape)
                if meshes is None:  # shrunk hardware: degrade below
                    cuts = None
            if cuts is not None:
                return PipelinedEngine(cfg, params, cuts,
                                       stage_meshes=meshes,
                                       allocator=allocator, **kw)
    if allocator is not None and group.tp * group.dp > 1:
        sub = allocator.try_alloc(group.stage_submesh_shape)
        if sub is not None:
            return ShardedEngine(cfg, params, sub, allocator=allocator, **kw)
    return Engine(cfg, params, **kw)
