"""Offloading-aware inference engine (the paper's system, §4) with a
continuous-batching slot pool.

Slot-pool architecture (default, ``mode="continuous"``):

  * one persistent KV pool of ``num_ubs × ubatch`` slots is allocated at
    engine construction — ``num_ubs`` rotation groups (the CGOPipe
    micro-batches) of ``ubatch`` batch rows each.  A slot is one row of
    one group's cache; it is recycled in place (models.kvcache
    ``reset_slot`` / ``insert_slot``) without touching its neighbors;
  * the Scheduler tracks per-slot lifecycle (free → prefilling → decoding
    → drained) and admits *individual* requests into freed slots
    mid-flight via Algorithm 2's balance criterion
    (core.batching.place_request) — the effective batch stays saturated
    under the fixed cache budget instead of waiting for whole
    micro-batches to retire;
  * admission prefills a request either monolithically at a bucketed
    prompt width (batch 1, compiled once per bucket), or — with
    ``overlap=True`` — as a *staged chunked prefill*: the prompt drains
    through fixed-width chunks (compiled once per chunk bucket), one
    chunk per engine tick, interleaved with every group's decode chunk.
    This is Algorithm 1's CGOPipe idea applied at request level: a long
    admission no longer stalls the decode groups, and prefill shapes stay
    fixed so novel prompt lengths never trigger fresh XLA compiles on the
    serving path.  Each chunk runs on a double-buffered batch-1 scratch
    cache and lands in the pool row immediately via a partial slot insert
    at the row offset (kvcache.insert_slot_span), keeping per-tick copy
    work bounded and the pool cache donated on the hot path;
  * decode runs one jit-stable fixed-shape chunk per rotation group
    (serving.steps.``decode_chunk``): ``decode_chunk`` tokens under an
    inner ``lax.scan`` with a per-row *active* mask, so finished rows are
    masked — they emit nothing and their cache position is frozen —
    rather than resampled, and Python/dispatch overhead is amortized
    between admission checks;
  * reservations are worst-case remaining quota by default, or EOS-aware
    (``reserve_mode="ewma"``): expected generation lengths from a running
    EWMA, with recompute preemption when the optimism was wrong (the
    scheduler's ``enforce_budget`` runs before every group decode);
  * groups still rotate in CGOPipe launch order (Algorithm 1): while
    group j runs its accelerator half, group j+1's attention inputs and
    the next layer's weight pages are in flight (on TPU the pages live in
    host memory and stream; on this CPU container the same jitted step
    consumes the page pool in-scan).

``mode="static"`` keeps the original whole-micro-batch semantics — a
group is admitted as a unit and retired only when every row finishes —
as the baseline that benchmarks/bench_engine.py compares against.  All
modes share the same masked decode step (static uses chunk size 1 so it
can retire groups every token), so greedy outputs per request are
bit-identical across static / continuous / overlapped admission.

``paged=True`` routes weights through core.paging (pack_block_groups) —
the 2×W_L double-buffer lives in XLA's scan pipelining on TPU.

``expert_paged=True`` switches to the expert-granular path
(pack_block_groups_split): the layer scan streams only each layer's
*shared* span (attention/norm/router), the MoE expert weights are
fetched router-gated per layer — resident spans read in place from a
fixed device pool sized by ``w_gpu_ratio`` (core.residency), misses
streamed from the host store — and, while group j's decode chunk is in
flight, the engine prefetches the expert set group j+1's router gated
last chunk (the request-level analogue of Algorithm 1's j+2 lookahead),
drained in ``paging.transfer_plan`` slices so the H2D work rides
alongside every rotation position's compute.  ``weight_traffic()``
reports the accounted bytes + hit/miss counters.

``module_batch=True`` decouples the attention and expert phases
(module-based batching, the MoE-Gen direction): ``module_groups``
rotation groups decode through ONE combined dispatch per accumulation
window — attention + router run for every group's rows back-to-back,
the MoE layers stage all groups' routed tokens into per-(layer, expert)
buckets, and each activated expert's span streams exactly once per
window (``core.residency.observe_window`` books hits/misses per-window,
not per-group).  Greedy transcripts stay bit-identical to the lockstep
schedule; ``weight_traffic()`` reports the per-phase breakdown and the
measured amortization factor.

See DESIGN.md for the slot pool + admission walkthrough, the paged
weights / expert residency section, and §7 for the two-phase
module-batched schedule.
"""
from __future__ import annotations

from dataclasses import dataclass, replace as dc_replace
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import blockpool, offload, paging, residency
from repro.core.batching import blocks_for_tokens
from repro.kernels import ops as kernel_ops
from repro.models import kvcache
from repro.models.model import ExecPolicy
from repro.runtime import faults as faults_mod
from repro.runtime.transfer import TransferEngine
from repro.runtime.watchdog import Watchdog
from repro.serving import steps as serve_steps
from repro.serving.sampling import sample
from repro.serving.scheduler import Scheduler, ServeRequest, Slot, SlotState

# KV host-tier layout: each spilled block is one row of the tier's most
# major axis, its payload flattened and zero-padded to whole 128-lane
# rows.  The TPU compiler copies to and from host memory by whole rows:
# a block whose minor dims are narrower than a lane tile (slot_pos, the
# int8 scales) is refused ("Lane slice updating is not supported").
_LANES = 128


def _block_shape(name: str, arena_shape) -> Tuple[int, ...]:
    ax = kvcache.arena_block_axis(name, stacked=True)
    return tuple(arena_shape[:ax]) + tuple(arena_shape[ax + 1:])


def _lane_rows(shape) -> int:
    return -(-int(np.prod(shape)) // _LANES)


def _to_lane_rows(blk):
    flat = blk.reshape(-1)
    flat = jnp.pad(flat, (0, _lane_rows(blk.shape) * _LANES - flat.size))
    return flat.reshape(-1, _LANES)


def _from_lane_rows(rows, shape):
    return rows.reshape(-1)[:int(np.prod(shape))].reshape(shape)


@dataclass
class EngineConfig:
    ubatch: int = 4                   # μ rows per micro-batch / slot group
    num_ubs: int = 2                  # rotation groups in the slot pool
    max_seq: int = 128
    temperature: float = 0.0
    paged: bool = False               # paged-weight streaming path
    page_elems: int = 1 << 16
    eos_id: int = 1
    seed: int = 0
    mode: str = "continuous"          # "continuous" | "static"
    decode_chunk: int = 8             # tokens per inner scan (continuous)
    on_long_prompt: str = "reject"    # "reject" | "truncate" (> max_seq)
    overlap: bool = False             # staged chunked-prefill admission
    prefill_chunk: int = 32           # chunk width for overlapped prefill
    reserve_mode: str = "worst"       # "worst" | "ewma" (EOS-aware)
    cache_tokens: Optional[int] = None  # per-group KV policy budget;
    # default = the physical pool slice (max_seq × ubatch).  A tighter
    # budget (e.g. from the HRM policy) is what makes EOS-aware
    # reservations bite: more concurrent admissions, preemption on miss.
    # ------------------------------------ expert-granular paged weights
    expert_paged: bool = False        # per-(layer, expert) spans + residency
    w_gpu_ratio: float = 0.25         # r_w — sizes the resident expert pool
    expert_slots: Optional[int] = None  # explicit pool size (spans) override
    prefetch: bool = True             # router-ahead prefetch for group j+1
    residency_alpha: float = 0.25     # expert-popularity EWMA step
    residency_victim_quota: int = 1   # demand misses may evict this many
                                      # victims per chunk (cold-start aid)
    # intra-pass predictive prefetch: a per-layer-transition logistic
    # gate predictor (core.residency.GatePredictor, fit online on the
    # scan's activation counts) scores the experts the dispatching
    # group's NEXT chunk will activate at layers i+1..i+lookahead, and
    # enqueues the non-resident ones into the same transfer_plan-sliced
    # pending queue as the router-ahead prefetch (first-come dedupe).
    # Gated under the master `prefetch` switch: prefetch=False disables
    # every lookahead path.
    predict: bool = True
    predict_lookahead: int = 2        # layer shifts predicted per dispatch
    predict_topk: Optional[int] = None  # experts kept per predicted layer
                                      # (default: source activation breadth)
    # intra-pass transfer draining: the pending queue's transfer_plan
    # slices drain BETWEEN the forward passes of one dispatched chunk,
    # so (a) a span the in-flight drain admitted is resident from the
    # chunk's second pass onward, and (b) a demand-missed span streams
    # once and stays staged for the rest of the chunk (later passes hit
    # instead of re-streaming it every step — the PR 3 lockstep model).
    # False restores the frozen-snapshot accounting (the router-ahead
    # baseline the predict/replicate bench sweep compares against).
    intra_pass: bool = True
    # hot-expert replication: this fraction of the residency pool may be
    # pinned persistently to the popularity-EWMA top spans (hysteresis
    # exit at replica_exit × the enter bar) — see ExpertResidency
    replicate_frac: float = 0.0
    replica_exit: float = 0.5
    # ---------------------------------------- block-granular paged KV (r_c)
    kv_paged: bool = False            # shared block arena + page tables
    block_tokens: int = 16            # ring positions per KV block
    kv_gpu_ratio: float = 1.0         # r_c — sizes the device arena; the
                                      # remainder lives in the host tier
    kv_prefetch: bool = True          # stream the next rotation group's
                                      # spilled blocks back in
                                      # paging.transfer_plan slices
    # ------------------------------------ module-based batching (MoE-Gen)
    module_batch: bool = False        # decoupled attention/expert phases:
    # decode `module_groups` rotation groups through ONE combined dispatch
    # per accumulation window — attention/router run per row as before,
    # the MoE layers stage every group's routed tokens against a single
    # expert-span read per layer step, so streamed weight bytes amortize
    # over the window instead of one micro-batch
    module_groups: Optional[int] = None   # groups per window (default: all
                                      # num_ubs; capped at num_ubs)
    module_stage_tokens: Optional[int] = None  # staging-buffer row budget:
    # when G·ubatch would exceed it the window shrinks toward lockstep
    # (capacity overflow never drops tokens)
    # ------------------------------------ fault plane / degradation ladder
    # (runtime.faults / runtime.transfer — see DESIGN.md §10).  Faults may
    # cost throughput but never change tokens: every knob below only moves
    # where bytes stream from and when, never what the jitted step computes
    fault_plan: Optional[object] = None   # runtime.faults.FaultPlan — the
    # injected fault schedule (None = nothing fires; the chokepoints stay
    # wired through the same always-present injector)
    degrade: bool = True                  # degradation ladder armed
    degrade_down_after: int = 3           # consecutive faults per rung down
    degrade_up_after: int = 16            # healthy-op streak per rung up
                                          # (> down_after: hysteresis)
    shed_priority: int = 1                # bottom rung sheds new admissions
                                          # with priority >= this
    max_retries: int = 4                  # bounded-retry budget per cycle
    backoff_s: float = 0.0                # real backoff sleep base (0: none)
    watchdog: bool = True                 # per-dispatch EWMA deadline
    watchdog_policy: str = "log"          # log | skip | abort — "skip" ≡
    # "log" on the serving path (the chunk has already landed when the
    # deadline is scored; the violation still feeds the ladder)
    watchdog_factor: float = 8.0
    watchdog_min_s: float = 0.25


class _SlotGroup:
    """Device-side state of one rotation group: its slice of the KV pool
    plus the last sampled token per row (the next decode input)."""

    def __init__(self, cache, ubatch: int):
        self.cache = cache
        self.last_tok = np.zeros((ubatch,), np.int32)
        # expert-paged: the expert set this group's router gated on the
        # last step of its previous chunk ({key: (L, E) bool}) — the
        # router-ahead prefetch prediction for its next chunk
        self.pred: Dict[str, np.ndarray] = {}


class _ActiveBatch:
    """Static mode: a micro-batch admitted (and retired) as a unit."""

    def __init__(self, requests: List[ServeRequest], cache, last_tokens,
                 gid: Optional[int] = None):
        self.requests = requests
        self.cache = cache
        self.last_tokens = last_tokens       # (μ,) next input token
        self.pred: Dict[str, np.ndarray] = {}
        self.gid = gid                       # paged-KV slot group (kv_paged)


class Engine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 policy: Optional[ExecPolicy] = None):
        assert ecfg.mode in ("continuous", "static")
        assert ecfg.watchdog_policy in ("log", "skip", "abort")
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.policy = policy
        # ---------------------------- fault plane (runtime.faults, §10)
        self.faults = faults_mod.FaultInjector(ecfg.fault_plan)
        self._ladder = (faults_mod.DegradationLadder(
            down_after=ecfg.degrade_down_after,
            up_after=ecfg.degrade_up_after) if ecfg.degrade else None)
        self._xfer = TransferEngine(
            self.faults, max_retries=ecfg.max_retries,
            backoff_s=ecfg.backoff_s, ladder=self._ladder)
        self._watchdog = (Watchdog(
            deadline_factor=ecfg.watchdog_factor,
            min_deadline_s=ecfg.watchdog_min_s,
            policy=ecfg.watchdog_policy) if ecfg.watchdog else None)
        self._degraded_no_predict = False
        self.scheduler = Scheduler(
            ubatch=ecfg.ubatch, num_ubs=ecfg.num_ubs,
            cache_tokens=ecfg.cache_tokens or ecfg.max_seq * ecfg.ubatch,
            gen_len=32, max_input_len=ecfg.max_seq,
            on_long_prompt=ecfg.on_long_prompt,
            reserve_mode=ecfg.reserve_mode,
            block_tokens=ecfg.block_tokens if ecfg.kv_paged else None)
        self.active: List[_ActiveBatch] = []          # static mode only
        self.key = jax.random.key(ecfg.seed)
        self.paged_blocks = None
        # -------------------------------- expert-granular paged weights
        self.residency: Dict[str, residency.ExpertResidency] = {}
        self._expert_pool: Dict[str, jax.Array] = {}
        # prefetch queue entries are (key, layer, expert, cause,
        # priority) with cause ∈ {"router", "predicted"}; the dedupe set
        # keys on (key, layer, expert) so the two lookahead paths never
        # enqueue (hence never fetch) the same span twice — router-ahead
        # enqueues first and wins ties.  priority (predicted score ×
        # predictor accuracy; None for router entries) feeds the
        # residency victim test — see ExpertResidency.admit
        self._pending: List[Tuple[str, int, int, str, Optional[float]]] = []
        self._pending_set: set = set()
        self._predictors: Dict[str, residency.GatePredictor] = {}
        self._fwd_passes = 0          # forward passes dispatched (traffic)
        if ecfg.expert_paged:
            pw = paging.pack_block_groups_split(params["blocks"],
                                                ecfg.page_elems)
            if not pw.expert_manifests:
                raise ValueError("expert_paged requires a MoE config "
                                 "(no routed-expert leaves found)")
            self.paged_blocks = pw
            for key, em in pw.expert_manifests.items():
                slots = (ecfg.expert_slots if ecfg.expert_slots is not None
                         else residency.slots_from_ratio(
                             ecfg.w_gpu_ratio, em.num_layers,
                             em.num_experts))
                self.residency[key] = residency.ExpertResidency(
                    em.num_layers, em.num_experts, capacity=slots,
                    span_bytes=em.span_bytes, alpha=ecfg.residency_alpha,
                    victim_quota=ecfg.residency_victim_quota,
                    replicate_frac=ecfg.replicate_frac,
                    replica_exit=ecfg.replica_exit,
                    protect_ttl=max(2, ecfg.num_ubs))
                if ecfg.predict and ecfg.prefetch:
                    self._predictors[key] = residency.GatePredictor(
                        em.num_layers, em.num_experts)
                self._expert_pool[key] = jnp.zeros(
                    (max(1, slots), em.pages_per_expert, em.page_elems),
                    pw.expert_pages[key].dtype)
            # one span per call, sliced from the (pinned-host on TPU)
            # store and moved to device memory inside the jit
            def pool_write(pool, pages, l, e, slot):
                L, E = pages.shape[:2]
                span = jax.lax.dynamic_index_in_dim(
                    pages.reshape((L * E,) + pages.shape[2:]), l * E + e,
                    0, keepdims=False)
                return pool.at[slot].set(offload.device_operand(span))

            self._pool_write = jax.jit(pool_write, donate_argnums=(0,))
        elif ecfg.paged:
            self.paged_blocks = paging.pack_block_groups(
                params["blocks"], ecfg.page_elems)
        if self.paged_blocks is not None:
            # the steps take the packed stores as their "blocks" argument;
            # holding no reference to the unpacked blocks lets the caller
            # free their device copy
            self.params = {**params,
                           "blocks": paging.page_stores(self.paged_blocks)}
        # ---------------------------------- block-granular paged KV (r_c)
        # dense-equivalent device bytes of the max_seq-wide slot pool: the
        # baseline every paged-KV report compares against
        dense_abs = kvcache.abstract_cache(cfg, ecfg.ubatch, ecfg.max_seq)
        self._kv_dense_bytes = ecfg.num_ubs * sum(
            int(np.prod(l.shape)) * l.dtype.itemsize
            for l in jax.tree.leaves(dense_abs))
        self._kv: Optional[blockpool.BlockPool] = None
        self._kv_arena: Dict[str, Dict] = {}
        self._kv_keys: Tuple[str, ...] = ()
        if ecfg.kv_paged:
            if ecfg.max_seq % ecfg.block_tokens:
                raise ValueError("max_seq must be a multiple of "
                                 "block_tokens for the paged KV pool")
            self._kv_keys = kvcache.paged_period_keys(cfg)
            if not self._kv_keys:
                raise ValueError("kv_paged requires at least one "
                                 "full-attention kv/mla period position")
            mb = ecfg.max_seq // ecfg.block_tokens    # blocks per slot
            n_slots = ecfg.num_ubs * ecfg.ubatch
            total = n_slots * mb
            # r_c sizes the arena; the floor keeps one admission's worst
            # case (one slot continuous, one micro-batch static) mappable
            # so progress is always possible — kv_traffic() reports the
            # bytes actually allocated, never the un-clamped ratio
            floor = mb * (ecfg.ubatch if ecfg.mode == "static" else 1)
            device_blocks = min(total, max(
                floor, int(round(ecfg.kv_gpu_ratio * total))))
            self._kv_arena = kvcache.init_paged_arena(
                cfg, device_blocks, ecfg.block_tokens)
            self._kv_trash = device_blocks
            # per-leaf block axis: head-major leaves (k/v/scales) carry the
            # block dimension at stacked axis 2, the rest at axis 1
            block_bytes = sum(
                int(a.nbytes) // a.shape[kvcache.arena_block_axis(
                    name, stacked=True)]
                for g in self._kv_arena.values() for name, a in g.items())
            self._kv = blockpool.BlockPool(n_slots, mb, device_blocks,
                                           block_bytes, faults=self.faults)

            def _host_shape(name, a):
                # one block per row of the most major axis (the one a host
                # copy may cut), its payload in whole 128-lane rows
                return (total, _lane_rows(_block_shape(name, a.shape)),
                        _LANES)

            # host tier: big enough to hold every spillable block.  On TPU
            # the tier lives in pinned_host memory as jax arrays (spills
            # and fetches are explicit transfers inside their jits);
            # elsewhere it is pageable numpy (offload emits one
            # structured warning the first time).
            try:
                self._kv_pinned_shd = offload.pinned_host_sharding(
                    faults=self.faults)
            except faults_mod.HostMemoryError:
                # injected placement failure: fall back to the pageable
                # tier now; the ladder's re-promotion path re-probes
                self._kv_pinned_shd = None
                if self._ladder is not None:
                    self._ladder.force_at_least("pageable_host",
                                                site="host_alloc")
            self._kv_pinned = self._kv_pinned_shd is not None
            if self._kv_pinned:
                self._kv_host = {
                    key: {name: jax.device_put(
                        jnp.zeros(_host_shape(name, a), a.dtype),
                        self._kv_pinned_shd)
                        for name, a in g.items()}
                    for key, g in self._kv_arena.items()}
                self._build_host_write(self._kv_pinned_shd)
            else:
                self._kv_host = {
                    key: {name: np.zeros(_host_shape(name, a), a.dtype)
                          for name, a in g.items()}
                    for key, g in self._kv_arena.items()}
            # spill: one arena block, as the host tier's lane rows
            self._kv_read = jax.jit(
                lambda a, i, ax: _to_lane_rows(jnp.squeeze(
                    jax.lax.dynamic_index_in_dim(a, i, ax), ax)),
                static_argnums=(2,))
            # fetch: one pinned-tier row moved to device memory explicitly,
            # then the block it holds
            self._kv_host_read = jax.jit(
                lambda h, i, shape: _from_lane_rows(offload.device_operand(
                    jax.lax.dynamic_index_in_dim(h, i, 0, keepdims=False)),
                    shape),
                static_argnums=(2,))
            self._kv_write = jax.jit(
                lambda a, i, v, ax: a.at[(slice(None),) * ax + (i,)].set(v),
                static_argnums=(3,), donate_argnums=(0,))
            self._kv_clear = jax.jit(lambda sp, idx: sp.at[:, idx].set(-1),
                                     donate_argnums=(0,))
            self._kv_pending: List[Tuple[int, int]] = []
            self._kv_pending_set: set = set()
            self._static_gids: List[int] = list(range(ecfg.num_ubs))
            # decode-path gather accounting: the page-table-native kernel
            # reads each row's *mapped* blocks per step; the dense view
            # (kvcache.paged_view) gathered the full max_seq ring for
            # every row of the group
            self._kv_gather_steps = 0
            self._kv_gathered_blocks = 0
            self._kv_view_blocks = 0
            # constant byte terms for kv_traffic(): the arena itself, the
            # dense remainder (window/SSM/prologue/xattn rings), and the
            # page tables
            rem_abs = jax.eval_shape(
                lambda: kvcache.init_cache(cfg, ecfg.ubatch, ecfg.max_seq,
                                           skip_keys=self._kv_keys))
            self._kv_device_bytes = (
                sum(int(a.nbytes) for g in self._kv_arena.values()
                    for a in g.values())
                + ecfg.num_ubs * sum(
                    int(np.prod(l.shape)) * l.dtype.itemsize
                    for l in jax.tree.leaves(rem_abs))
                + int(self._kv.dev.nbytes))
            # resolve impl='auto' once, host-side (the impl string stays
            # a static jit arg), against a dense-vs-paged crossover the
            # caller installed explicitly (kernel_ops.load_paged_crossover;
            # none: the kernel on TPU, the ref elsewhere).  The occupancy
            # proxy is the device-resident fraction of the block pool
            if policy is not None and policy.paged_attn_impl == "auto":
                self.policy = policy = dc_replace(
                    policy, paged_attn_impl=kernel_ops.paged_auto_impl(
                        device_blocks / total))
        self._prefill = jax.jit(serve_steps.make_prefill_fill_step(
            cfg, policy, paged_blocks=self.paged_blocks))
        chunk = ecfg.decode_chunk if ecfg.mode == "continuous" else 1
        # the pool cache is donated on the hot path so slot writes and
        # chunk decodes update it in place instead of copying the pool
        self._decode_chunk = jax.jit(serve_steps.make_decode_chunk(
            cfg, policy, paged_blocks=self.paged_blocks,
            temperature=ecfg.temperature, eos_id=ecfg.eos_id, chunk=chunk),
            donate_argnums=(1,))
        # ------------------------------ module-based batching windows
        self._mg = 1
        self._decode_window_fn = None
        if ecfg.module_batch:
            mg = ecfg.module_groups or ecfg.num_ubs
            mg = max(1, min(mg, ecfg.num_ubs))
            if ecfg.module_stage_tokens is not None:
                # the staging buffer bounds how many groups' routed tokens
                # accumulate per window; overflow shrinks the window
                # toward lockstep instead of dropping tokens
                mg = max(1, min(mg, ecfg.module_stage_tokens // ecfg.ubatch))
            self._mg = mg
            if mg > 1:
                self._decode_window_fn = jax.jit(
                    serve_steps.make_decode_chunk(
                        cfg, policy, paged_blocks=self.paged_blocks,
                        temperature=ecfg.temperature, eos_id=ecfg.eos_id,
                        chunk=chunk, token_groups=mg),
                    donate_argnums=(1,))
        # continuous rotation order, windowed: full windows run combined,
        # the remainder groups fall back to lockstep individually
        self._windows = [list(range(i, min(i + self._mg, ecfg.num_ubs)))
                         for i in range(0, ecfg.num_ubs, self._mg)]
        # configured window width: the degradation ladder's lockstep rung
        # clamps self._mg toward 1 and re-promotion restores this
        self._mg_base = self._mg
        self._insert = jax.jit(kvcache.insert_slot, donate_argnums=(0,))
        # the persistent slot pool: allocated once, recycled per slot
        self.groups: List[_SlotGroup] = []
        self._prefill_scratch = None
        if ecfg.mode == "continuous":
            # with kv_paged the paged period positions live in the shared
            # arena; each group holds only the dense remainder (pos,
            # window/SSM rings, prologue, cross-attention)
            self.groups = [
                _SlotGroup(kvcache.init_cache(cfg, ecfg.ubatch, ecfg.max_seq,
                                              skip_keys=self._kv_keys),
                           ecfg.ubatch)
                for _ in range(ecfg.num_ubs)]
            # batch-1 admission-prefill input: _prefill is functional, so
            # this stays pristine and is reused for every admission
            self._prefill_scratch = kvcache.init_cache(cfg, 1, ecfg.max_seq)
        # ------------------------------ overlapped (chunked) admission
        self._staged: List[Slot] = []      # PREFILL slots, FIFO
        self._stage_scratch = None         # scratch of the in-flight head
        self._free_scratches = []
        if ecfg.overlap:
            if ecfg.mode != "continuous":
                raise ValueError("overlap admission requires continuous mode")
            specs = list(cfg.period) + list(cfg.prologue or ())
            if cfg.encoder_layers or \
                    any(s.cache_kind() == "ssm" for s in specs):
                raise ValueError(
                    "overlapped chunked-prefill admission needs "
                    "attention-only configs (no SSM / encoder layers)")
            self._prefill_chunk = jax.jit(serve_steps.make_prefill_chunk(
                cfg, policy, paged_blocks=self.paged_blocks),
                donate_argnums=(2,))
            self._insert_span = jax.jit(
                kvcache.insert_slot_span, static_argnames=("length",),
                donate_argnums=(0,))
            self._reset = jax.jit(kvcache.reset_slot, donate_argnums=(0,))
            # double-buffered: the next admission's first chunk dispatches
            # against one scratch while the previous one's reset drains
            self._free_scratches = [
                kvcache.init_cache(cfg, 1, ecfg.max_seq) for _ in range(2)]
        self.steps = 0
        self.tokens_out = 0

    # ----------------------------------------------------------- public
    def submit(self, prompt, max_new_tokens: int = 16,
               priority: int = 0) -> int:
        return self.scheduler.submit(np.asarray(prompt, np.int32),
                                     max_new_tokens, priority=priority)

    def step(self) -> bool:
        """One engine tick: admit new work, then decode every rotation
        group in CGOPipe launch order (Algorithm 1).  Continuous mode
        decodes a `decode_chunk`-token masked chunk per group and recycles
        slots that drain; with ``overlap=True`` admission itself is staged
        — one prompt chunk is prefilled per tick, round-robin with the
        decode chunks.  Static mode decodes one token per active
        micro-batch and retires whole groups.  Returns True if any work
        was done."""
        self._ladder_tick()       # safe point: no dispatch in flight
        if self.ecfg.mode == "static":
            return self._step_static()
        return self._step_continuous()

    def run_until_idle(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        while self.step() and self.steps < max_steps:
            pass
        return {rid: r.generated for rid, r in self.scheduler.requests.items()}

    # ----------------------------------------------------- shared pieces
    def _bucket(self, input_len: int) -> int:
        # bucket the padded prompt length so prefill compiles once per
        # bucket, not once per distinct length
        return min(-(-input_len // 16) * 16, self.ecfg.max_seq)

    def _chunk_bucket(self, rem: int) -> int:
        # next power of two capped at the full chunk width — mid-prompt
        # chunks always get the full width, the final partial chunk a
        # smaller bucket, so a C-wide config compiles ≤ log2(C)+1 shapes
        w = 1
        while w < rem:
            w <<= 1
        return min(w, self.ecfg.prefill_chunk)

    # ---------------------------------- expert residency (data+control)
    def _expert_state(self):
        """Snapshot of the residency data plane for one jitted call: the
        device pool plus the (layer, expert) → slot map.  The jit holds
        this snapshot, so control-plane mutations after dispatch can
        never corrupt an in-flight chunk."""
        return {k: (self._expert_pool[k],
                    jnp.asarray(self.residency[k].slot_of))
                for k in self.residency}

    def _copy_span(self, key: str, l: int, e: int, slot: int) -> None:
        # mandatory once residency assigned the slot: the dispatch
        # snapshot says the span is resident, so its bytes must land —
        # injected faults are retried by the transfer engine
        def _fill():
            self._expert_pool[key] = self._pool_write(
                self._expert_pool[key], self.paged_blocks.expert_pages[key],
                jnp.int32(l), jnp.int32(e), jnp.int32(slot))

        self._xfer.run_mandatory("expert_copy", _fill,
                                 nbytes=self.residency[key].span_bytes,
                                 on_hostmem=self._demote_host_tier)

    def _resident_snap(self) -> Dict[str, np.ndarray]:
        """Residency mask at dispatch time — what the jitted call's map
        snapshot says is resident; later admissions must not be booked as
        hits for this call's steps."""
        return {k: (r.slot_of >= 0).copy()
                for k, r in self.residency.items()}

    def _account_counts(self, counts, holder=None, snap=None,
                        holders=None, hidden=None) -> None:
        """Book a call's expert activation counts ({key: (..., P, E)}):
        per forward pass, hits/misses against the residency snapshot the
        pass actually read, then demand-admit the missed spans — hottest
        first, so the miss stream doubles as cache fill.  Updates
        `holder.pred` with the last pass's gating (the router-ahead
        prediction for that group's next chunk).

        ``hidden`` ({key: (L, E) bool}) marks the spans whose prefetch
        landed *while this call was in flight* (captured right after the
        sync, before the post-landing drain): a miss on such a span paid
        its bytes but its stream overlapped the dispatched compute, so
        it books as a hidden (stall-free) miss — the per-layer residue
        is the miss-stall estimate ``weight_traffic()`` reports.

        Each booked forward pass also takes one online SGD step of the
        cross-layer gate predictor (host numpy — no retrace), and, when
        replication is on, the replica set is reconciled against the
        refreshed popularity EWMA (promotions copy their spans in).

        With ``intra_pass`` the working resident mask evolves ACROSS the
        chunk's passes instead of staying the frozen dispatch snapshot:
        a demand-missed span streams once and stays staged for the rest
        of the chunk (later passes hit it — the pending queue's
        transfer_plan slices drain between the scan's passes, and the
        pass-local staging buffer holds what already streamed), and the
        spans the in-flight drain admitted count resident from the
        second pass onward.  This changes only WHEN bytes are charged —
        the computation reads identical weights either way.

        With ``holders`` (a module-batched window) the count arrays carry
        a group axis ({key: (..., P, G, E)}): each forward pass books ONE
        per-window union observation (``observe_window`` — an expert span
        streams at most once per window regardless of how many groups
        routed to it), and each group's holder gets its own last-pass
        prediction so router-ahead prefetch stays per group."""
        for key, arr in counts.items():
            r = self.residency[key]
            r.begin_chunk()          # refresh the demand-evict victim quota
            a = np.asarray(arr)
            mask = snap[key] if snap is not None else None
            hid = hidden.get(key) if hidden is not None else None
            gp = self._predictors.get(key)
            intra = self.ecfg.intra_pass and mask is not None
            cur = mask.copy() if intra else mask
            want: Dict[Tuple[int, int], bool] = {}

            def book(si, observe_fn, activated, token_counts):
                nonlocal cur
                if intra and si == 1 and hid is not None:
                    # in-flight admissions have landed by the second pass
                    cur = cur | hid
                missed = observe_fn(activated, token_counts=token_counts,
                                    resident_mask=cur, hidden_mask=hid)
                for pair in missed:
                    want[pair] = True
                    if intra:
                        # streamed once, staged for the rest of the chunk
                        cur[pair] = True

            if holders is not None:
                steps = a.reshape(-1, *a.shape[-3:])      # (n_fwd, P, G, E)
                for si, s in enumerate(steps):
                    per_g = np.moveaxis(s, 1, 0)          # (G, P, E)
                    book(si, r.observe_window, per_g > 0, per_g)
                    if gp is not None:
                        for g_counts in per_g:            # fit per group
                            gp.fit_step(g_counts)
            else:
                steps = a.reshape(-1, *a.shape[-2:])      # (n_fwd, P, E)
                for si, s in enumerate(steps):
                    book(si, r.observe, s > 0, s)
                    if gp is not None:
                        gp.fit_step(s)
            for l, e in want:
                # misses fill free slots only; popularity-driven
                # replacement is the router-ahead prefetch path's job
                slot = r.admit(l, e, demand=True, allow_evict=False)
                if slot is not None:
                    self._copy_span(key, l, e, slot)
            if r.replicate_frac > 0.0:
                for l, e, slot in r.update_replicas():
                    self._copy_span(key, l, e, slot)
            if holder is not None:
                holder.pred[key] = steps[-1] > 0
            if holders is not None:
                last = steps[-1]                          # (P, G, E)
                for g, h in enumerate(holders):
                    h.pred[key] = last[:, g, :] > 0

    def _next_gids(self, gid) -> List[int]:
        """The rotation group(s) decoding next: gid+1 for a lockstep
        group, the following window for a module-batched one."""
        if isinstance(gid, int):
            return [(gid + 1) % self.ecfg.num_ubs]
        g0 = (max(gid) + 1) % self.ecfg.num_ubs
        return [(g0 + j) % self.ecfg.num_ubs for j in range(len(gid))]

    def _enqueue_prediction(self, gid) -> None:
        """Queue the expert set group ``gid+1``'s router gated on the last
        step of its previous chunk (the request-level analogue of
        Algorithm 1's j+2 weight lookahead), hottest-first.  For a
        module-batched window `gid` is the window's gid list and the
        predictions of the NEXT window's groups are queued."""
        for g in self._next_gids(gid):
            nxt = self.groups[g]
            for key, act in nxt.pred.items():
                r = self.residency[key]
                pairs = [(int(l), int(e)) for l, e in zip(*np.nonzero(act))
                         if not r.is_resident(l, e)]
                pairs.sort(key=lambda p: -r.popularity[p])
                for p in pairs:
                    t = (key, *p)
                    if t not in self._pending_set:
                        self._pending.append((*t, "router", None))
                        self._pending_set.add(t)

    def _enqueue_gate_predictions(self, holders) -> None:
        """Intra-pass lookahead: from each dispatching holder's last
        observed gating, the cross-layer GatePredictor scores the experts
        layers i+1..i+lookahead will activate in that holder's NEXT chunk
        and queues the non-resident ones earliest-deadline-first
        (``paging.predicted_drain_order`` — a span must land before the
        scan's consuming layer step).  The entries join the SAME pending
        queue as the router-ahead group-j+1 prefetch and dedupe against
        it first-come (router-ahead enqueues first), so a span predicted
        by both paths is fetched exactly once.  Predicted admissions are
        eviction-protected until first use (residency ``protect_ttl``).

        Suspended (``predict=False`` semantics) while the degradation
        ladder sits at or below its no_predict rung."""
        if self._degraded_no_predict:
            return
        for h in holders:
            for key, act in h.pred.items():
                gp = self._predictors.get(key)
                if gp is None:
                    continue
                r = self.residency[key]
                preds = gp.predict(act,
                                   lookahead=self.ecfg.predict_lookahead,
                                   topk=self.ecfg.predict_topk)
                pairs = [(l, e) for l, e, _ in preds]
                scores = [s for _, _, s in preds]
                for i in paging.predicted_drain_order(pairs, scores):
                    l, e = pairs[i]
                    if r.is_resident(l, e):
                        continue
                    t = (key, l, e)
                    if t not in self._pending_set:
                        # short-horizon priority: the predicted
                        # activation probability discounted by the
                        # predictor's measured accuracy
                        self._pending.append(
                            (*t, "predicted", scores[i] * gp.acc))
                        self._pending_set.add(t)

    def _plan_slice(self, pending: List, gid) -> Tuple[List, List]:
        """This rotation position's ``paging.transfer_plan`` slice of a
        pending transfer queue (shared by the weight and KV prefetch
        drains); returns (chosen, keep).  A module-batched window passes
        its gid list and drains the union of its positions' slices
        (``paging.window_plan``) — the window spans those interleave
        slots, so its in-flight compute covers all of them.

        Fault chokepoint ("plan_drain"): an injected *partial* completes
        only a prefix of the slice (the rest re-queues), a *fail* defers
        the whole slice, a *stall* books a deadline violation — all three
        only delay advisory prefetch work, so tokens are untouched."""
        positions = [gid] if isinstance(gid, int) else list(gid)
        take = set(paging.window_plan(len(pending), self.ecfg.num_ubs,
                                      positions))
        chosen = [t for i, t in enumerate(pending) if i in take]
        keep = [t for i, t in enumerate(pending) if i not in take]
        ev = self.faults.fire("plan_drain")
        if ev is not None and chosen:
            if ev.kind == "partial":
                k = int(len(chosen) * ev.frac)
                chosen, deferred = chosen[:k], chosen[k:]
                keep = deferred + keep
                self._xfer.book_retry("plan_drain")
            elif ev.kind in ("fail", "exhaust", "hostmem"):
                keep = chosen + keep
                chosen = []
                self._xfer.book_retry("plan_drain")
            elif ev.kind == "stall":
                self._xfer.book_stall("plan_drain")
        return chosen, keep

    def _drain_prefetch(self, gid, *, retry_refused: bool) -> None:
        """Transfer this rotation position's ``paging.transfer_plan``
        slice of the pending prefetch queue into the pool.  While a chunk
        is in flight every resident span is pinned, so only free slots
        fill (H2D overlapping compute); refused entries are re-queued to
        retry after the chunk lands (``retry_refused=True``) or dropped
        (the cache is hotter than the prediction)."""
        if not self._pending:
            return
        chosen, keep = self._plan_slice(self._pending, gid)
        requeued = []
        for key, l, e, cause, pri in chosen:
            r = self.residency[key]
            if r.is_resident(l, e):
                self._pending_set.discard((key, l, e))
                continue
            # prefetch: charges span bytes
            slot = r.admit(l, e, cause=cause, priority=pri)
            if slot is not None:
                self._copy_span(key, l, e, slot)
                self._pending_set.discard((key, l, e))
            elif retry_refused:
                requeued.append((key, l, e, cause, pri))
            else:
                self._pending_set.discard((key, l, e))
        self._pending = keep + requeued

    def weight_traffic(self) -> Dict[str, float]:
        """Accounted H2D weight traffic (DESIGN.md §2: on this container
        traffic is modeled, not physically moved).  Whole-layer paging
        streams every group's full span each forward pass; the
        expert-granular path streams the shared spans plus the
        missed/prefetched expert spans booked by core.residency.

        Per-phase breakdown (module-based batching observability):
        ``attn_phase_bytes`` is what the attention phase streams (the
        shared attention/norm/router spans, once per forward pass — a
        window's pass serves all its groups), ``expert_phase_bytes`` is
        the expert-span traffic of the expert phase (misses + prefetch),
        ``bytes_per_token_amortized`` = total / tokens emitted, and
        ``module_groups_effective`` is the MEASURED amortization —
        lockstep-equivalent misses / per-window union misses — so the
        1/G claim is counter-verified, not inferred."""
        out: Dict[str, float] = {"fwd_passes": self._fwd_passes,
                                 "tokens_out": self.tokens_out,
                                 "module_batch": self._mg > 1,
                                 "module_groups": self._mg}
        if self.residency:
            pw = self.paged_blocks
            shared = sum(pw.shared_layer_bytes(k) * pw.manifests[k].num_layers
                         for k in pw.manifests)
            expert_full = sum(
                em.span_bytes * em.num_experts * em.num_layers
                for em in pw.expert_manifests.values())
            c = [r.counters for r in self.residency.values()]
            misses = sum(x.misses for x in c)
            lockstep = sum(x.lockstep_misses for x in c)
            pred_pf = sum(x.predicted_prefetches for x in c)
            out.update(
                mode="expert_paged",
                shared_bytes=shared * self._fwd_passes,
                expert_bytes=sum(x.h2d_bytes for x in c),
                hits=sum(x.hits for x in c),
                misses=misses,
                prefetches=sum(x.prefetches for x in c),
                evictions=sum(x.evictions for x in c),
                hit_rate=(sum(x.hits for x in c)
                          / max(1, sum(x.fetches for x in c))),
                # hit attribution by staging cause (sums to hits) and the
                # predictor/replication observability the policy consumes
                demand_hits=sum(x.demand_hits for x in c),
                router_hits=sum(x.router_hits for x in c),
                predicted_hits=sum(x.predicted_hits for x in c),
                replicated_hits=sum(x.replicated_hits for x in c),
                predicted_prefetches=pred_pf,
                predicted_used=sum(x.predicted_used for x in c),
                prefetch_accuracy=(sum(x.predicted_used for x in c)
                                   / max(1, pred_pf)),
                predictor_accuracy=(
                    float(np.mean([gp.acc
                                   for gp in self._predictors.values()]))
                    if self._predictors else 0.0),
                replications=sum(x.replications for x in c),
                replica_spans=sum(len(r.replicas)
                                  for r in self.residency.values()),
                # stall split: misses whose stream hid behind the
                # consuming dispatch's compute vs those that stalled it,
                # with the stalled bytes resolved per layer (the roofline
                # report divides by link bandwidth for stall time)
                hidden_misses=sum(x.hidden_misses for x in c),
                stall_misses=sum(x.stall_misses for x in c),
                miss_stall_bytes=int(sum(r.miss_stall_bytes.sum()
                                         for r in self.residency.values())),
                miss_stall_bytes_per_layer={
                    k: [int(b) for b in r.miss_stall_bytes]
                    for k, r in self.residency.items()},
                # what whole-layer streaming would have moved for the
                # same passes (shared + every expert span every layer)
                whole_layer_bytes=(shared + expert_full) * self._fwd_passes,
                module_groups_effective=(lockstep / misses if misses
                                         else float(self._mg)),
            )
            out["h2d_bytes"] = out["shared_bytes"] + out["expert_bytes"]
            out["attn_phase_bytes"] = out["shared_bytes"]
            out["expert_phase_bytes"] = out["expert_bytes"]
        elif self.ecfg.paged:
            _, manifests = self.paged_blocks
            per_pass = sum(
                m.pages_per_layer * m.page_elems * m.num_layers
                * np.dtype(m.dtype).itemsize for m in manifests.values())
            out.update(mode="paged", h2d_bytes=per_pass * self._fwd_passes,
                       attn_phase_bytes=per_pass * self._fwd_passes,
                       expert_phase_bytes=0,
                       module_groups_effective=float(self._mg))
        else:
            out.update(mode="resident", h2d_bytes=0, attn_phase_bytes=0,
                       expert_phase_bytes=0,
                       module_groups_effective=float(self._mg))
        out["bytes_per_token_amortized"] = (out["h2d_bytes"]
                                            / max(1, self.tokens_out))
        return out

    # ------------------------------ block-granular paged KV (data+control)
    def _slot_of(self, slot) -> int:
        return slot.gid * self.ecfg.ubatch + slot.row

    def _compose_kv(self, dense_cache: Dict, gid) -> Dict:
        """Assemble the jit-call cache for slot group `gid` (or, for a
        module-batched window, the gid list — the page table then covers
        every window row, group-major): its dense per-slot leaves plus
        the shared block arena and a fresh device page-table snapshot for
        the rows.  The control plane is host-side (core.blockpool); every
        dispatch reads the map at call time, mirroring the
        expert-residency snapshot discipline."""
        b = self.ecfg.ubatch
        gids = [gid] if isinstance(gid, int) else list(gid)
        pt = self._kv.device_table(
            [g * b + r for g in gids for r in range(b)])
        ptj = jnp.asarray(np.ascontiguousarray(
            np.broadcast_to(pt[None], (self.cfg.num_periods,) + pt.shape)))
        cache = dict(dense_cache)
        for key, g in self._kv_arena.items():
            cache[key] = {**g, "page_table": ptj}
        return cache

    def _absorb_kv(self, cache: Dict) -> Dict:
        """Take the (possibly donated-and-rebuilt) arena arrays back out
        of a returned cache; the remainder is the group's dense part."""
        out = dict(cache)
        for key in self._kv_arena:
            g = dict(out.pop(key))
            g.pop("page_table")
            self._kv_arena[key] = g
        return out

    def _kv_spill_op(self, pb: int, hb: int) -> None:
        for key, g in self._kv_arena.items():
            h = self._kv_host[key]
            for name in g:
                ax = kvcache.arena_block_axis(name, stacked=True)
                rows = self._kv_read(g[name], jnp.int32(pb), ax)
                if self._kv_pinned:             # D2H into the pinned tier
                    h[name] = self._kv_host_write(h[name], jnp.int32(hb),
                                                  rows)
                else:
                    h[name][hb] = np.asarray(rows)

    def _kv_fetch_op(self, hb: int, pb: int) -> None:
        for key, g in self._kv_arena.items():
            h = self._kv_host[key]
            for name in list(g):
                ax = kvcache.arena_block_axis(name, stacked=True)
                shape = _block_shape(name, g[name].shape)
                blk = (self._kv_host_read(h[name], jnp.int32(hb), shape)
                       if self._kv_pinned else
                       _from_lane_rows(jnp.asarray(h[name][hb]), shape))
                g[name] = self._kv_write(g[name], jnp.int32(pb), blk, ax)

    def _kv_exec(self, ops) -> None:
        """Execute a BlockPool plan in order: ``spill`` copies an arena
        block out to the host store (D2H), ``fetch`` copies a host block
        back in (H2D), ``alloc`` marks a fresh block (its slot_pos plane
        is cleared in one batched scatter at the end — stale positions
        from the previous owner must never satisfy a validity mask).

        Spill/fetch ops run through the retrying transfer engine: a plan
        already committed to the pool's map, so its bytes MUST land
        (mandatory, not advisory).  Faults fire before the copy closure
        runs, so a retried op never re-executes a donated-buffer write."""
        fresh = []
        nb = self._kv.block_bytes
        for op in ops:
            if op[0] == "spill":
                _, _s, _lb, pb, hb = op
                self._xfer.run_mandatory(
                    "kv_spill", lambda pb=pb, hb=hb: self._kv_spill_op(pb, hb),
                    nbytes=nb, on_hostmem=self._demote_host_tier)
            elif op[0] == "fetch":
                _, _s, _lb, hb, pb = op
                self._xfer.run_mandatory(
                    "kv_fetch", lambda hb=hb, pb=pb: self._kv_fetch_op(hb, pb),
                    nbytes=nb, on_hostmem=self._demote_host_tier)
            else:                                       # ("alloc", s, lb, pb)
                fresh.append(op[3])
        if fresh:
            # pad to a power-of-two bucket (aimed at the trash block) so
            # the clear scatter compiles a handful of shapes, not one per
            # allocation count
            n = 1
            while n < len(fresh):
                n <<= 1
            idx = np.full((n,), self._kv_trash, np.int32)
            idx[:len(fresh)] = fresh
            idxj = jnp.asarray(idx)
            for key, g in self._kv_arena.items():
                g["slot_pos"] = self._kv_clear(g["slot_pos"], idxj)

    def _kv_ensure(self, fn):
        """Run a BlockPool ensure closure on a path whose refusal is
        fatal or mode-changing (arena-floor asserts / lockstep
        fallbacks follow the call): injected pool exhaustions are
        retried until a genuine answer comes back, so a chaos schedule
        can never trip a floor assert or force a spurious fallback."""
        while True:
            ops, ok, nxt = fn()
            self._kv_exec(ops)
            if ok or not self._kv.last_refusal_injected:
                return ops, ok, nxt
            self._xfer.book_retry("kv_pool")

    def _kv_sweep(self) -> None:
        """Release arena/host blocks of any slot that fell back to FREE
        outside the engine's own retire path (budget preemption)."""
        for grp in self.scheduler.slots:
            for s in grp:
                if s.state == SlotState.FREE:
                    idx = self._slot_of(s)
                    if self._kv.slot_in_use(idx):
                        self._kv.free_slot(idx)

    def _kv_prepare_group(self, gid, chunk: int) -> None:
        """Pre-dispatch guard for the paged pool: every decoding row's
        mapped blocks must be device-resident (attention gathers its
        whole history) and the blocks its next `chunk` tokens will write
        must be mapped.  Cold blocks of other slots spill to the host
        tier to make room; on arena exhaustion the youngest decoding
        request in the group is preempted (recompute preemption — blocks
        freed, request re-queued with its transcript intact).  Retries
        resume each slot at its first unsatisfied block, so every needed
        block books exactly one hit or miss per preparation.

        A module-batched window passes its gid list: all of its groups'
        decoding rows dispatch in ONE combined call, so the protect set —
        and the residency requirement — spans the whole window (preparing
        a later group must never spill an earlier one's just-prepared
        blocks)."""
        gids = [gid] if isinstance(gid, int) else list(gid)
        slots = [s for g in gids for s in self.scheduler.slots[g]]
        booked: Dict[int, int] = {}          # slot idx -> blocks satisfied
        inj_retries = 0
        while True:
            decoding = [s for s in slots if s.state == SlotState.DECODE]
            protect = [self._slot_of(s) for s in decoding]
            ok = True
            for s in decoding:
                idx = self._slot_of(s)
                need = self._kv.blocks_needed(
                    s.req.footprint + min(chunk, s.req.remaining),
                    self.ecfg.block_tokens)
                if booked.get(idx, 0) >= need:
                    continue
                ops, ok, nxt = self._kv.ensure_range(
                    idx, booked.get(idx, 0), need, protect)
                self._kv_exec(ops)
                booked[idx] = nxt
                if not ok:
                    break
            if ok:
                return
            if self._kv.last_refusal_injected:
                # an injected pool-exhaustion refusal, not a real one:
                # retry the draw before paying a preemption.  With a lone
                # decoding slot retries are unbounded (there is no victim
                # to preempt, and the plan's faults are transient by
                # construction); otherwise an exhausted budget books an
                # abort and falls through to genuine recompute preemption.
                inj_retries += 1
                self._xfer.book_retry("kv_pool")
                if inj_retries <= self.ecfg.max_retries \
                        or len(decoding) <= 1:
                    continue
                self._xfer.book_abort("kv_pool")
            assert len(decoding) > 1, \
                "single request exceeds the KV arena (device_blocks floor)"
            victim = max(decoding, key=lambda s: s.req.rid)   # youngest
            self.scheduler.preempt(victim)
            self._kv.free_slot(self._slot_of(victim))
            booked.pop(self._slot_of(victim), None)
            inj_retries = 0

    def _kv_enqueue_prefetch(self, gid) -> None:
        """Queue the next rotation group's spilled blocks (the KV
        analogue of Algorithm 1's weight lookahead): while group `gid`'s
        chunk is in flight, group gid+1's history can stream back.  A
        module-batched window passes its gid list and queues the whole
        next window's spilled blocks."""
        for g in self._next_gids(gid):
            for s in self.scheduler.slots[g]:
                if s.state != SlotState.DECODE:
                    continue
                idx = self._slot_of(s)
                for lb in self._kv.host_resident_blocks(idx):
                    t = (idx, lb)
                    if t not in self._kv_pending_set:
                        self._kv_pending.append(t)
                        self._kv_pending_set.add(t)

    def _kv_drain_prefetch(self, gid) -> None:
        """Promote this rotation position's ``paging.transfer_plan``
        slice of the pending block queue into free arena blocks (no
        demotions on the prefetch path — mirroring residency's
        miss-fills-free-slots rule); entries that became stale or found
        no free block fall back to the demand path."""
        if not self._kv_pending:
            return
        chosen, self._kv_pending = self._plan_slice(self._kv_pending, gid)
        self._kv_pending_set.difference_update(chosen)
        for idx, lb in chosen:
            op = self._kv.prefetch(idx, lb)
            if op is not None:
                self._kv_exec([op])

    def _kv_note_gather(self, gid, steps: int) -> None:
        """Book the decode-path KV gather of one dispatched chunk: the
        paged flash-decode kernels read each row's mapped blocks once per
        decode step (per layer), so gathered bytes scale with the page
        table's mapped-block count — not with ``max_seq`` as the dense
        ``paged_view`` materialization did.  A module-batched window
        passes its gid list (its dispatch gathers every window row)."""
        b = self.ecfg.ubatch
        gids = [gid] if isinstance(gid, int) else list(gid)
        rows = [g * b + r for g in gids for r in range(b)]
        mapped = sum(self._kv.n_mapped(r) for r in rows)
        self._kv_gather_steps += steps
        self._kv_gathered_blocks += mapped * steps
        self._kv_view_blocks += len(rows) * self._kv.blocks_per_slot * steps

    def kv_traffic(self) -> Dict[str, float]:
        """Device-KV accounting: bytes the KV pool actually occupies on
        device vs the dense max_seq-wide equivalent, plus the host-tier
        stream counters (same modeled-traffic discipline as
        ``weight_traffic``)."""
        out: Dict[str, float] = {"tokens_out": self.tokens_out,
                                 "dense_equiv_bytes": self._kv_dense_bytes}
        if self._kv is None:
            out.update(mode="kv_dense",
                       device_kv_bytes=self._kv_dense_bytes,
                       h2d_bytes=0, d2h_bytes=0)
            return out
        arena_bytes = sum(int(a.nbytes) for g in self._kv_arena.values()
                          for a in g.values())
        c = self._kv.counters
        out.update(
            mode="kv_paged",
            block_tokens=self.ecfg.block_tokens,
            device_blocks=self._kv.device_blocks,
            peak_blocks_in_use=self._kv.peak_in_use,
            arena_utilization=(self._kv.peak_in_use
                               / max(1, self._kv.device_blocks)),
            device_kv_bytes=self._kv_device_bytes,
            arena_bytes=arena_bytes,
            hits=c.hits, misses=c.misses, prefetches=c.prefetches,
            spills=c.spills, allocs=c.allocs, frees=c.frees,
            h2d_bytes=c.h2d_bytes, d2h_bytes=c.d2h_bytes,
            hit_rate=c.hit_rate,
        )
        # what the decode hot path actually reads per step (mapped blocks
        # through the page table) vs what the dense paged_view gather
        # materialized (the group's full max_seq-wide ring) — this is the
        # quantity hrm.kv_block_hit_rate's traffic term models
        bb = self._kv.block_bytes
        steps = max(1, self._kv_gather_steps)
        out.update(
            gathered_bytes=self._kv_gathered_blocks * bb,
            gathered_bytes_per_step=self._kv_gathered_blocks * bb / steps,
            paged_view_bytes_per_step=self._kv_view_blocks * bb / steps,
            gather_reduction_vs_view=(self._kv_view_blocks
                                      / max(1, self._kv_gathered_blocks)),
        )
        return out

    # ------------------- fault plane: host tier / ladder / watchdog (§10)
    def _build_host_write(self, shd) -> None:
        # (re)built whenever the pinned tier (re)appears: the donated
        # update must carry the tier's sharding so D2H spills land in
        # pinned pages, not wherever the donation was last placed; the
        # block's lane rows move to host memory explicitly first
        def write(h, i, v):
            if offload.in_host_memory(h):
                v = jax.device_put(v, jax.memory.Space.Host)
            return jax.lax.dynamic_update_index_in_dim(h, v, i, 0)

        self._kv_host_write = jax.jit(write, donate_argnums=(0,),
                                      out_shardings=shd)

    def _demote_host_tier(self) -> None:
        """Reversible fall-back of the KV host tier from pinned jax
        arrays to pageable numpy — the HostMemoryError handler and the
        ladder's pageable_host rung.  Idempotent; block bytes are
        preserved, so spilled histories survive the demotion."""
        if self._ladder is not None:
            self._ladder.force_at_least("pageable_host", site="host_alloc")
        if self._kv is None or not self._kv_pinned:
            return
        self._kv_host = {
            key: {name: np.array(a) for name, a in g.items()}
            for key, g in self._kv_host.items()}
        self._kv_pinned = False

    def _repromote_host_tier(self) -> None:
        """Ladder re-promotion out of pageable_host: clear the offload
        module's one-shot warning latch, re-probe the pinned memory
        space and — if the probe succeeds — lift the host tier back into
        pinned jax arrays.  Stays pageable when the probe still fails
        (the rung flips back healthy; bytes keep flowing either way)."""
        if self._kv is None or self._kv_pinned:
            return
        offload.reset_host_probe()
        try:
            shd = offload.pinned_host_sharding(warn=False,
                                               faults=self.faults)
        except faults_mod.HostMemoryError:
            shd = None
        if shd is None:
            return                        # probe still failing: stay pageable
        self._kv_host = {
            key: {name: jax.device_put(jnp.asarray(a), shd)
                  for name, a in g.items()}
            for key, g in self._kv_host.items()}
        self._build_host_write(shd)
        self._kv_pinned = True
        self._kv_pinned_shd = shd

    def _set_module_groups(self, mg: int) -> None:
        """Clamp/restore the module-batch window width (the ladder's
        lockstep rung).  PR 6's transcript guarantee — windowed ≡
        lockstep bit-for-bit — is what makes this rung token-safe."""
        mg = max(1, min(int(mg), self._mg_base))
        if mg == self._mg:
            return
        self._mg = mg
        self._windows = [
            list(range(i, min(i + mg, self.ecfg.num_ubs)))
            for i in range(0, self.ecfg.num_ubs, mg)]

    def _ladder_tick(self) -> None:
        if self._ladder is not None and self._ladder.pending():
            self._ladder.apply(self._enact_rung, tick=self.steps)

    def _enact_rung(self, old: int, new: int, direction: str) -> None:
        """Apply ONE ladder rung's side effect (called from apply() at
        the step() safe point — no dispatch in flight).  Every rung is
        reversible, and none can change sampled tokens: each only moves
        where bytes stream from and when — except admission_shed, which
        by design drops work the submitter marked sheddable."""
        rung = faults_mod.LADDER_LEVELS[max(old, new)]
        down = direction == "down"
        if rung == "pageable_host":
            if down:
                self._demote_host_tier()
            else:
                self._repromote_host_tier()
        elif rung == "no_predict":
            self._degraded_no_predict = down
        elif rung == "lockstep":
            self._set_module_groups(1 if down else self._mg_base)
        elif rung == "residency_shrunk":
            for r in self.residency.values():
                if down:
                    r.drop_replicas()
                    r.set_limit(max(1, r.capacity // 2))
                else:
                    r.set_limit(None)
        elif rung == "admission_shed":
            self.scheduler.shed_priority = (
                self.ecfg.shed_priority if down else None)

    def _watchdog_end(self) -> None:
        """Close one dispatch's deadline window: injected 'dispatch'
        stalls charge virtual seconds (deterministic chaos, no real
        sleeps); a violation feeds the ladder like any other fault."""
        if self._watchdog is None:
            return
        virt = self.faults.stall_s("dispatch")
        ok = self._watchdog.step_end(extra_s=virt)
        if not ok and self._ladder is not None:
            self._ladder.note_fault("dispatch")

    def fault_traffic(self) -> Dict[str, object]:
        """Fault-plane observability, weight_traffic()-style: injected
        fault counts, transfer retry/abort/stall counters, dispatch
        deadline violations, shed admissions, and the degradation
        ladder's current level + transition history."""
        out: Dict[str, object] = {
            "injected": dict(self.faults.counts),
            "injected_total": self.faults.total(),
            "shed_requests": self.scheduler.shed_count,
            "host_tier_pinned": bool(getattr(self, "_kv_pinned", False)),
            "module_groups_now": self._mg,
            "predict_suspended": self._degraded_no_predict,
            "dispatch_slow_steps": (self._watchdog.slow_steps
                                    if self._watchdog is not None else 0),
        }
        out.update(self._xfer.stats())
        if self._ladder is not None:
            out.update(level=self._ladder.level,
                       level_name=self._ladder.level_name,
                       demotions=self._ladder.demotions,
                       promotions=self._ladder.promotions,
                       degradation_events=list(self._ladder.events))
        else:
            out.update(level=0, level_name="healthy", demotions=0,
                       promotions=0, degradation_events=[])
        return out

    def _decode_group(self, cache, last_tok, active, rem, *, holder=None,
                      gid: Optional[int] = None):
        """Run one masked decode chunk; returns (cache, new_last_tok,
        still_active, toks (T,B), emitted (T,B)) as host arrays where
        relevant.  On the expert-paged path: pins every resident span for
        the duration of the dispatch (the chunk may read any of them in
        place), issues the router-ahead prefetch for the next rotation
        group while the chunk is in flight, then books the returned
        activation counts."""
        self.key, k = jax.random.split(self.key)
        args = (self.params, cache, jnp.asarray(last_tok[:, None]),
                jnp.asarray(active), jnp.asarray(rem), k)
        chunk = self.ecfg.decode_chunk if self.ecfg.mode == "continuous" else 1
        self._fwd_passes += chunk
        if self._watchdog is not None:
            self._watchdog.step_start()
        if self.residency:
            snap = self._resident_snap()
            for r in self.residency.values():
                r.pin_resident()
            cache, tok, act2, _, toks, emitted, counts = self._decode_chunk(
                *args, self._expert_state())
            prefetching = (self.ecfg.prefetch and gid is not None
                           and self.groups)
            if prefetching:
                # in flight: fill free slots for group gid+1's predicted
                # set (H2D overlaps the dispatched compute), then the
                # gate predictor's intra-pass lookahead for THIS group's
                # next chunk (deduped against the router-ahead entries)
                self._enqueue_prediction(gid)
                if self._predictors and holder is not None:
                    self._enqueue_gate_predictions([holder])
                self._drain_prefetch(gid, retry_refused=True)
            res = (cache, np.array(tok)[:, 0], np.asarray(act2),
                   np.asarray(toks), np.asarray(emitted))   # sync
            self._watchdog_end()
            # spans that became resident between dispatch and landing:
            # their H2D stream overlapped this chunk's compute, so a
            # miss on them is a hidden (stall-free) miss
            hidden = {k: ((r.slot_of >= 0) & ~snap[k])
                      for k, r in self.residency.items()}
            for r in self.residency.values():
                r.unpin_all()
            if prefetching:
                # landed: retry the refused slice, evictions now allowed
                self._drain_prefetch(gid, retry_refused=False)
            self._account_counts(counts, holder=holder, snap=snap,
                                 hidden=hidden)
            return res
        cache, tok, act2, _, toks, emitted = self._decode_chunk(*args)
        res = (cache, np.array(tok)[:, 0], np.asarray(act2),
               np.asarray(toks), np.asarray(emitted))   # sync
        self._watchdog_end()
        return res

    def _decode_window(self, cache, last_tok, active, rem, *, holders, gids):
        """Module-batched analogue of ``_decode_group``: ONE combined
        masked decode chunk over a window of G rotation groups (G·ubatch
        rows, group-major).  Attention/norms are per-row so every row's
        numerics match its lockstep dispatch bit-for-bit; the MoE layers
        stage all groups' routed tokens against a single expert-span read
        per layer step.  The forward-pass counter therefore advances by
        `chunk` for the WHOLE window — each shared span (and each missed
        expert span, booked per-window by ``observe_window``) is charged
        once per window, not once per group: that is the amortization.
        Router-ahead prefetch targets the NEXT window's predicted sets
        and drains through the union of this window's transfer_plan
        slices."""
        self.key, k = jax.random.split(self.key)
        args = (self.params, cache, jnp.asarray(last_tok[:, None]),
                jnp.asarray(active), jnp.asarray(rem), k)
        chunk = self.ecfg.decode_chunk if self.ecfg.mode == "continuous" else 1
        self._fwd_passes += chunk
        if self._watchdog is not None:
            self._watchdog.step_start()
        if self.residency:
            snap = self._resident_snap()
            for r in self.residency.values():
                r.pin_resident()
            cache, tok, act2, _, toks, emitted, counts = \
                self._decode_window_fn(*args, self._expert_state())
            prefetching = bool(self.ecfg.prefetch and self.groups)
            if prefetching:
                self._enqueue_prediction(gids)
                if self._predictors:
                    self._enqueue_gate_predictions(holders)
                self._drain_prefetch(gids, retry_refused=True)
            res = (cache, np.array(tok)[:, 0], np.asarray(act2),
                   np.asarray(toks), np.asarray(emitted))   # sync
            self._watchdog_end()
            hidden = {k: ((r.slot_of >= 0) & ~snap[k])
                      for k, r in self.residency.items()}
            for r in self.residency.values():
                r.unpin_all()
            if prefetching:
                self._drain_prefetch(gids, retry_refused=False)
            self._account_counts(counts, holders=holders, snap=snap,
                                 hidden=hidden)
            return res
        cache, tok, act2, _, toks, emitted = self._decode_window_fn(*args)
        res = (cache, np.array(tok)[:, 0], np.asarray(act2),
               np.asarray(toks), np.asarray(emitted))   # sync
        self._watchdog_end()
        return res

    @staticmethod
    def _emit(toks, emitted, row_req):
        """Replay a chunk's emissions into request transcripts.
        row_req[i] is the request owning row i (or None)."""
        count = 0
        for t in range(toks.shape[0]):
            for i, r in enumerate(row_req):
                if r is not None and emitted[t, i]:
                    r.generated.append(int(toks[t, i]))
                    count += 1
        return count

    def _sample_first(self, logits) -> int:
        self.key, k = jax.random.split(self.key)
        return int(np.asarray(
            sample(logits, k, temperature=self.ecfg.temperature))[0])

    def _prefill_retires(self, r) -> bool:
        """Whether the token an admission prefill just appended ends r:
        its quota is met, or r was re-admitted after preemption and the
        token is EOS — that prefill stands in for the decode step which,
        unpreempted, would have sampled the token and stopped the row."""
        return (len(r.generated) >= r.max_new_tokens
                or (len(r.generated) > 1
                    and r.generated[-1] == self.ecfg.eos_id))

    def _run_prefill(self, step_fn, *args):
        """Shared prefill wrapper (monolithic fill AND staged chunk)
        absorbing the expert-paged protocol: one fwd pass booked, the
        residency snapshot taken at dispatch, activation counts
        accounted.  Returns (logits, cache)."""
        self._fwd_passes += 1
        if self.residency:
            snap = self._resident_snap()
            logits, cache, counts = step_fn(self.params, *args,
                                            self._expert_state())
            self._account_counts(counts, snap=snap)
            return logits, cache
        return step_fn(self.params, *args)

    # ------------------------------------------------- continuous mode
    def _admit_continuous(self):
        """Fill freed slots: per admitted request, prefill at its own
        bucket width (batch 1) and slot-write the KV into the pool row.
        Re-admitted (preempted) requests prefill prompt + transcript."""
        for slot in self.scheduler.admit_to_slots():
            r = slot.req
            eff = r.effective_prompt
            S = self._bucket(len(eff))
            toks = np.zeros((1, S), np.int32)
            toks[0, :len(eff)] = eff
            logits, single = self._run_prefill(
                self._prefill, jnp.asarray(toks), self._prefill_scratch,
                jnp.asarray([len(eff)], np.int32))
            first = self._sample_first(logits)
            r.generated.append(first)
            group = self.groups[slot.gid]
            if self._kv is not None:
                # book the prompt's blocks (alloc/fetch/spill-to-make-room)
                # before the slot-insert scatters through the page table
                idx = self._slot_of(slot)
                _, ok, _ = self._kv_ensure(lambda: self._kv.ensure_tokens(
                    idx, len(eff), self.ecfg.block_tokens, (idx,)))
                assert ok, "admission exceeds the KV arena floor"
                pooled = self._insert(self._compose_kv(group.cache, slot.gid),
                                      single, slot.row)
                group.cache = self._absorb_kv(pooled)
            else:
                group.cache = self._insert(group.cache, single, slot.row)
            group.last_tok[slot.row] = first
            if self._prefill_retires(r):
                self._retire_slot(slot)
            else:
                self.scheduler.start_decode(slot)

    # -------------------------------------- overlapped (staged) admission
    def _prefill_tick(self) -> bool:
        """Run ONE chunk of the staged admission at the head of the
        prefill queue (request-level CGOPipe: admission work interleaves
        with the groups' decode chunks instead of stalling them)."""
        if not self._staged:
            return False
        slot = self._staged[0]
        r = slot.req
        group = self.groups[slot.gid]
        if self._stage_scratch is None:          # head starts fresh
            self._stage_scratch = self._free_scratches.pop()
            # invalidate the previous occupant's remnants once: span
            # inserts only overwrite their own ring range
            group.cache = self._reset(group.cache, np.int32(slot.row))
        eff = r.effective_prompt
        t = slot.prefill_pos
        rem = len(eff) - t
        width = self._chunk_bucket(rem)
        n = min(rem, width)
        toks = np.zeros((1, width), np.int32)
        toks[0, :n] = eff[t:t + n]
        logits, self._stage_scratch = self._run_prefill(
            self._prefill_chunk, jnp.asarray(toks), self._stage_scratch,
            jnp.asarray([n], np.int32))
        # partial slot insert at the row offset: the chunk lands in the
        # pool immediately, so the final flip to DECODE copies nothing
        if self._kv is not None:
            # only the span's blocks need to be mapped & device-resident
            # for the insert; earlier prompt blocks may stay spilled until
            # the slot flips to DECODE (the chunk attends to the scratch
            # ring, never to the pool row)
            idx = self._slot_of(slot)
            _, ok, _ = self._kv_ensure(lambda: self._kv.ensure_range(
                idx, t // self.ecfg.block_tokens,
                blocks_for_tokens(t + width, self.ecfg.block_tokens),
                (idx,)))
            assert ok, "staged prefill chunk exceeds the KV arena floor"
            pooled = self._insert_span(
                self._compose_kv(group.cache, slot.gid), self._stage_scratch,
                np.int32(slot.row), np.int32(t), length=width)
            group.cache = self._absorb_kv(pooled)
        else:
            group.cache = self._insert_span(
                group.cache, self._stage_scratch, np.int32(slot.row),
                np.int32(t), length=width)
        self.scheduler.prefill_progress(slot, n)
        if slot.prefill_pos >= len(eff):         # final chunk: first token
            first = self._sample_first(logits)
            r.generated.append(first)
            group.last_tok[slot.row] = first
            # recycle the scratch (reset drains while the next admission's
            # first chunk dispatches against the other buffer)
            self._free_scratches.append(
                self._reset(self._stage_scratch, np.int32(0)))
            self._stage_scratch = None
            self._staged.pop(0)
            if self._prefill_retires(r):
                self._retire_slot(slot)
            else:
                self.scheduler.start_decode(slot)
        return True

    def _retire_slot(self, slot):
        # no cache reset here: the row stays masked while free, and the
        # next admission's insert_slot overwrites every leaf of the row
        # (kvcache.reset_slot exists for paths that must hand back a
        # clean row without refilling it).  Paged KV: the slot's arena
        # and host blocks return to the free lists; fresh allocations
        # clear their slot_pos plane at map time.
        if self._kv is not None:
            self._kv.free_slot(self._slot_of(slot))
        self.scheduler.finish(slot)

    def _step_continuous(self) -> bool:
        if self.ecfg.overlap:
            self._staged.extend(self.scheduler.admit_to_slots())
            did = self._prefill_tick()
            # cold pool: nothing is decodable yet, so drain prefill chunks
            # back-to-back instead of trickling one per (idle) tick
            while (did and self._staged and not any(
                    s.state == SlotState.DECODE
                    for grp in self.scheduler.slots for s in grp)):
                did = self._prefill_tick()
        else:
            self._admit_continuous()
            did = False
        if not (did or self.scheduler.has_live_slots()):
            return False
        for w in self._windows:                       # CGOPipe rotation
            if len(w) == self._mg and self._mg > 1:
                self._tick_window_continuous(w)
            else:
                # lockstep: remainder groups of a non-divisible rotation,
                # and the whole loop when module batching is off
                for gid in w:
                    self._tick_group_continuous(gid)
        self.steps += 1
        return True

    def _tick_group_continuous(self, gid: int) -> None:
        """One rotation group's decode chunk (the classic lockstep
        schedule: attention and expert FFN at the same ubatch size)."""
        group = self.groups[gid]
        # EOS-aware reservations are optimistic: preempt (recompute)
        # the youngest rows if this chunk could blow the group budget
        self.scheduler.enforce_budget(gid, self.ecfg.decode_chunk)
        if self._kv is not None:
            self._kv_sweep()              # blocks of budget-preempted slots
            # fetch/alloc this group's working set (may preempt more)
            self._kv_prepare_group(gid, self.ecfg.decode_chunk)
        slots = self.scheduler.slots[gid]
        active = np.array([s.state == SlotState.DECODE for s in slots])
        if not active.any():
            return
        rem = np.array(
            [s.req.remaining if s.state == SlotState.DECODE else 0
             for s in slots], np.int32)
        if self._kv is not None:
            self._kv_note_gather(gid, self.ecfg.decode_chunk)
            cache = self._compose_kv(group.cache, gid)
        else:
            cache = group.cache
        cache, group.last_tok, act2, toks, emitted = \
            self._decode_group(cache, group.last_tok, active, rem,
                               holder=group, gid=gid)
        group.cache = (self._absorb_kv(cache)
                       if self._kv is not None else cache)
        self.tokens_out += self._emit(
            toks, emitted, [s.req if s.state == SlotState.DECODE else None
                            for s in slots])
        for i, s in enumerate(slots):
            if s.state == SlotState.DECODE and not act2[i]:
                self._retire_slot(s)
        if self._kv is not None and self.ecfg.kv_prefetch:
            # the KV analogue of the router-ahead weight prefetch:
            # while this group's results land, stream the next
            # group's spilled blocks back in transfer_plan slices
            self._kv_enqueue_prefetch(gid)
            self._kv_drain_prefetch(gid)

    def _tick_window_continuous(self, gids: List[int]) -> None:
        """One module-batched accumulation window: the attention phase
        runs all `gids` groups' rows through ONE combined decode dispatch
        (their slot caches concatenated batch-wise, one shared arena
        composition with a window-wide page table), the expert phase
        inside it streams each activated expert's span exactly once for
        the whole window, and the results are split back per group.  Per
        request the greedy transcript is bit-identical to the lockstep
        schedule — rows are independent through attention, and the MoE
        staging reproduces per-group bucketing exactly."""
        b = self.ecfg.ubatch
        for gid in gids:
            self.scheduler.enforce_budget(gid, self.ecfg.decode_chunk)
        if self._kv is not None:
            self._kv_sweep()
            # the window dispatches combined: the whole window's working
            # set must be device-resident at once (union protect set)
            self._kv_prepare_group(gids, self.ecfg.decode_chunk)
        slot_rows = [self.scheduler.slots[g] for g in gids]
        active = np.array([s.state == SlotState.DECODE
                           for slots in slot_rows for s in slots])
        if not active.any():
            return
        rem = np.array(
            [s.req.remaining if s.state == SlotState.DECODE else 0
             for slots in slot_rows for s in slots], np.int32)
        last = np.concatenate([self.groups[g].last_tok for g in gids])
        dense = kvcache.concat_slot_caches(
            [self.groups[g].cache for g in gids])
        if self._kv is not None:
            self._kv_note_gather(gids, self.ecfg.decode_chunk)
            cache = self._compose_kv(dense, gids)
        else:
            cache = dense
        cache, last2, act2, toks, emitted = self._decode_window(
            cache, last, active, rem,
            holders=[self.groups[g] for g in gids], gids=gids)
        dense_out = self._absorb_kv(cache) if self._kv is not None else cache
        for j, (g, part) in enumerate(zip(
                gids, kvcache.split_slot_cache(dense_out, len(gids)))):
            self.groups[g].cache = part
            self.groups[g].last_tok = last2[j * b:(j + 1) * b]
            slots = slot_rows[j]
            sl = slice(j * b, (j + 1) * b)
            self.tokens_out += self._emit(
                toks[:, sl], emitted[:, sl],
                [s.req if s.state == SlotState.DECODE else None
                 for s in slots])
            for i, s in enumerate(slots):
                if s.state == SlotState.DECODE and not act2[j * b + i]:
                    self._retire_slot(s)
        if self._kv is not None and self.ecfg.kv_prefetch:
            self._kv_enqueue_prefetch(gids)
            self._kv_drain_prefetch(gids)

    # ----------------------------------------------------- static mode
    def _admit_static(self):
        # the pool budget is num_ubs rotation groups: only admit into
        # capacity actually freed by retired micro-batches (with kv_paged
        # every admission additionally books its rows' blocks against the
        # shared arena — the policy budget is enforced by allocation, not
        # by the group cap alone)
        avail = self.ecfg.num_ubs - len(self.active)
        for group in self.scheduler.admit(avail):
            mu = self.ecfg.ubatch
            S = self._bucket(max(r.input_len for r in group))
            toks = np.zeros((mu, S), np.int32)
            lens = np.zeros((mu,), np.int32)
            for i, r in enumerate(group):
                toks[i, :r.input_len] = r.prompt
                lens[i] = r.input_len
            # rows beyond len(group) are padding rows (len 0 → masked)
            cache = kvcache.init_cache(self.cfg, mu, self.ecfg.max_seq)
            logits, cache = self._run_prefill(self._prefill,
                                              jnp.asarray(toks), cache,
                                              jnp.asarray(lens))
            self.key, k = jax.random.split(self.key)
            first = np.asarray(
                sample(logits, k, temperature=self.ecfg.temperature))
            for i, r in enumerate(group):
                r.generated.append(int(first[i]))
                if len(r.generated) >= r.max_new_tokens:
                    r.done = True                 # 1-token request
            gid = None
            if self._kv is not None:
                # land the dense prefill in arena blocks: book each row's
                # prompt, then scatter the rows through the page table
                gid = self._static_gids.pop(0)
                rows = list(range(gid * mu, (gid + 1) * mu))
                for i, r in enumerate(group):
                    _, ok, _ = self._kv_ensure(
                        lambda i=i, r=r: self._kv.ensure_tokens(
                            rows[i], r.input_len, self.ecfg.block_tokens,
                            rows))
                    assert ok, "static micro-batch exceeds the KV arena"
                pooled = self._compose_kv(
                    kvcache.init_cache(self.cfg, mu, self.ecfg.max_seq,
                                       skip_keys=self._kv_keys), gid)
                for i in range(len(group)):
                    pooled = self._insert(pooled, cache, np.int32(i),
                                          np.int32(i))
                cache = self._absorb_kv(pooled)
            self.active.append(_ActiveBatch(
                list(group), cache, np.asarray(first, np.int32), gid))

    def _release_static(self, ab) -> None:
        self.active.remove(ab)
        if self._kv is not None and ab.gid is not None:
            for row in range(ab.gid * self.ecfg.ubatch,
                             (ab.gid + 1) * self.ecfg.ubatch):
                self._kv.free_slot(row)
            self._static_gids.append(ab.gid)

    def _kv_prepare_static(self, ab, active) -> None:
        """Static analogue of `_kv_prepare_group`: every live row's
        blocks device-resident plus its next token's block mapped (no
        preemption — the arena floor guarantees one micro-batch fits;
        other batches' blocks spill to make room)."""
        rows = list(range(ab.gid * self.ecfg.ubatch,
                          (ab.gid + 1) * self.ecfg.ubatch))
        protect = [rows[i] for i in range(len(ab.requests)) if active[i]]
        for i, r in enumerate(ab.requests):
            if not active[i]:
                continue
            _, ok, _ = self._kv_ensure(
                lambda i=i, r=r: self._kv.ensure_tokens(
                    rows[i], r.footprint + 1, self.ecfg.block_tokens,
                    protect))
            assert ok, "static micro-batch exceeds the KV arena"

    def _kv_prepare_window_static(self, window) -> bool:
        """Window analogue of `_kv_prepare_static` with a union protect
        set (preparing a later batch must not spill an earlier one's
        blocks).  The arena floor only guarantees ONE micro-batch fits,
        so this may fail — returns False and the caller falls back to
        lockstep (static mode never preempts)."""
        mu = self.ecfg.ubatch
        protect = [ab.gid * mu + i
                   for ab, active, _ in window
                   for i in range(len(ab.requests)) if active[i]]
        for ab, active, _ in window:
            for i, r in enumerate(ab.requests):
                if not active[i]:
                    continue
                _, ok, _ = self._kv_ensure(
                    lambda ab=ab, i=i, r=r: self._kv.ensure_tokens(
                        ab.gid * mu + i, r.footprint + 1,
                        self.ecfg.block_tokens, protect))
                if not ok:
                    return False
        return True

    def _tick_batch_static(self, ab, active, rem) -> None:
        """One micro-batch's single-token decode (lockstep)."""
        mu = self.ecfg.ubatch
        if self._kv is not None:
            self._kv_prepare_static(ab, active)
            self._kv_note_gather(ab.gid, 1)
            cache = self._compose_kv(ab.cache, ab.gid)
        else:
            cache = ab.cache
        cache, ab.last_tokens, act2, toks, emitted = \
            self._decode_group(cache, np.asarray(ab.last_tokens),
                               active, rem, holder=ab)
        ab.cache = (self._absorb_kv(cache)
                    if self._kv is not None else cache)
        row_req = [ab.requests[i] if i < len(ab.requests) else None
                   for i in range(mu)]
        self.tokens_out += self._emit(toks, emitted, row_req)
        for i, r in enumerate(ab.requests):
            if active[i] and not act2[i]:
                r.done = True
        if all(r.done for r in ab.requests):
            self._release_static(ab)

    def _tick_window_static(self, window) -> bool:
        """One combined single-token dispatch over `_mg` static
        micro-batches (module-based batching in static mode).  With
        paged KV the union working set must fit the arena at once; if it
        does not, returns False and the caller runs the window's batches
        lockstep instead."""
        mu = self.ecfg.ubatch
        abs_ = [ab for ab, _, _ in window]
        if self._kv is not None:
            if not self._kv_prepare_window_static(window):
                return False
            for ab in abs_:
                self._kv_note_gather(ab.gid, 1)
            dense = kvcache.concat_slot_caches([ab.cache for ab in abs_])
            cache = self._compose_kv(dense, [ab.gid for ab in abs_])
        else:
            cache = kvcache.concat_slot_caches([ab.cache for ab in abs_])
        active = np.concatenate([a for _, a, _ in window])
        rem = np.concatenate([r for _, _, r in window])
        last = np.concatenate([np.asarray(ab.last_tokens) for ab in abs_])
        cache, last2, act2, toks, emitted = self._decode_window(
            cache, last, active, rem, holders=abs_,
            gids=[ab.gid for ab in abs_])
        dense_out = self._absorb_kv(cache) if self._kv is not None else cache
        for j, (ab, part) in enumerate(zip(
                abs_, kvcache.split_slot_cache(dense_out, len(abs_)))):
            ab.cache = part
            ab.last_tokens = last2[j * mu:(j + 1) * mu]
            sl = slice(j * mu, (j + 1) * mu)
            row_req = [ab.requests[i] if i < len(ab.requests) else None
                       for i in range(mu)]
            self.tokens_out += self._emit(toks[:, sl], emitted[:, sl],
                                          row_req)
            for i, r in enumerate(ab.requests):
                if window[j][1][i] and not act2[j * mu + i]:
                    r.done = True
            if all(r.done for r in ab.requests):
                self._release_static(ab)
        return True

    def _step_static(self) -> bool:
        self._admit_static()
        if not self.active:
            return False
        mu = self.ecfg.ubatch
        work = []
        for ab in list(self.active):  # rotation: ub_0, ub_1, ... (Alg. 1)
            active = np.zeros((mu,), bool)
            rem = np.zeros((mu,), np.int32)
            for i, r in enumerate(ab.requests):
                if not r.done and len(r.generated) < r.max_new_tokens:
                    active[i] = True
                    rem[i] = r.max_new_tokens - len(r.generated)
            if not active.any():          # e.g. every quota met at prefill
                self._release_static(ab)
                continue
            work.append((ab, active, rem))
        i = 0
        while i < len(work):
            window = work[i:i + self._mg]
            if self._mg > 1 and len(window) == self._mg \
                    and self._tick_window_static(window):
                i += self._mg
            else:
                self._tick_batch_static(*work[i])
                i += 1
        self.steps += 1
        return True
