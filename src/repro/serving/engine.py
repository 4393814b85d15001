"""Serving engine: pure *execution mechanism* over a paged FP8/BF16 KV pool.

Since the scheduler split, this module runs device work and nothing else;
every admission / eviction / growth / chunking decision lives in
`serving.scheduler.Scheduler`.  The run loop is two lines:

    decision = scheduler.step(engine)   # policy + host bookkeeping
    engine.execute(decision)            # device work, in plan order

The paper's §2.3.2 chain — FP8 KV doubles block capacity, capacity raises
concurrency, concurrency removes preemptions — is reproduced by the layers
below; once capacity stops binding, the scheduler's chunked prefill and
eviction scoring take over as the throughput levers.

Paged KV cache
    Device KV memory is one shared pool of fixed-size blocks per attention
    layer (`models.attention.PagedKVCache`, pool shape (N+1, BS, KVH, D));
    each request owns an ordered list of physical block ids and attention
    gathers K/V through the per-slot block table.  Pool row N is the trash
    block: prompt padding, masked-slot decode writes and inactive slots
    scatter there, so one fused jit step serves every slot without
    branching.  Byte accounting is precision-aware: a block is
    `block_size` bf16-KV tokens' worth of bytes, so at equal byte budget
    FP8 KV holds 2x the tokens per block (`BlockManager`).

Prefill — one-shot or chunked
    Legacy (prefill_chunk=None): a request's whole prompt is prefilled in
    one batch-1 trace of fixed width `prompt_pad` at admission (prompts
    longer than `prompt_pad` are rejected).  Chunked (prefill_chunk=C):
    the scheduler slices the prompt into C-token chunks served across
    successive steps by `models.prefill_chunk`, which scatters the
    chunk's KV through the block table and gathers earlier chunks back
    from the pool — decode for other slots runs between chunks, prompts
    of any length stream through one fixed-width trace, and a prompt
    whose leading full blocks hit the prefix index skips straight past
    them (attention-only models).  During the fused decode step,
    mid-prefill slots have their table rows masked to the trash block so
    the batch-wide KV write cannot touch real (possibly shared) blocks.

Kernel hot path (`kernel_config`)
    One `KernelConfig` (string shorthands "off" / "decode" / "prefill" /
    "all") decides which attention mechanisms serve the hot path.
    Decode: one fused `decode_step` over every decode-ready slot per
    step — with the kernel on, one `fp8_paged_decode_attention` launch
    serves the whole batch, scalar-prefetched block tables clamped to
    each slot's live blocks (cost scales with actual context, not
    `max_seq_len`).  Prefill: chunked-prefill chunks run through
    `fp8_paged_prefill_attention`, reading prior-context K/V straight
    from the pool instead of materializing a gathered copy.  Both are
    interpret-mode on CPU, compiled on TPU; the jnp fallbacks remain
    the "off" baseline and slice their gathers to the same live blocks.
    (`decode_kernel="paged"` is the legacy spelling of
    `kernel_config="decode"`.)

Prefix sharing (refcount + content hash + copy-on-write)
    Admission dedups full-block prompt prefixes against the
    `BlockManager` index (hits are `acquire`d, refcount +1); prefill
    re-writes shared blocks bit-identically (causal prefix KV is a pure
    function of the prefix tokens; scales are global post-calibration);
    the first divergent decode append into a shared block is preceded by
    a copy-on-write planned by the scheduler and executed here
    (`paged_copy_rows`).  Freed blocks with a live index entry move to
    the BlockManager's evictor cache — the entry survives until the
    space is actually needed, so a re-submitted prompt can revive its
    own KV for free; when the space IS needed and the engine was built
    with `host_kv_blocks > 0`, the entry demotes to the host tier
    instead of dying (still a prefix hit, revived by copy-in).

Preemption = allocator demote/promote (two-tier swap)
    Host memory is a first-class KV tier: `BlockManager.demote` moves a
    victim's valid blocks to host-tier block ids at plan time (refcount
    -1 each; blocks another request holds stay resident) and hands back
    the ordered copy pairs the engine executes at the SwapOut action —
    the engine's role is purely the data plane (`host_pool` holds the
    rows, `_host_state` the non-KV slot state + pending token).  On
    re-admission `BlockManager.promote` re-dedups the prompt against
    the prefix index, drops the host copies a device-resident hit
    supersedes, and returns the tail copy-ins; decoding (or chunked
    prefill, for a victim preempted mid-prefill) resumes from the exact
    pending position — nothing is recomputed, and every restored token
    is counted in `wasted_tokens` (the swap tax the victim pays for the
    preemption).

Hybrid / enc-dec slot state
    SSM layers (mamba2 / jamba patterns) carry recurrent state (`h`,
    conv tail) and enc-dec decoders carry cross-attention KV; both live
    slot-indexed, NOT in the paged pool.  Swap-out copies the victim's
    state rows to host alongside its blocks and swap-in restores them
    into whichever slot the request resumes in; a fresh admission zeroes
    the slot's recurrent rows first.  During the fused decode step,
    mid-prefill slots' SSM rows are written back afterwards (the state
    analogue of the trash-block table mask), so piggybacked decode never
    advances a half-prefilled recurrence.  The per-request constant
    footprint (`request_state_bytes`) is priced into admission and swap
    accounting as block-equivalents — attention-free models are bounded
    purely by it.  Enc-dec requests carry `frames` through `submit()`
    (padded to `max_src_len`; the encoder masks via src_lengths), and
    prefix sharing is disabled there: decoder KV depends on the frames,
    so token-keyed dedup would alias different sources.

KV scales
    Calibrated on the engine's first prefill after weight load (vLLM's
    `calculate_kv_scales` semantics), stored once in the shared pool,
    reused by every later prefill/decode (scales survive swap).  Under
    chunked prefill the calibrating prefill runs as ONE full-width chunk
    so its amax window — and every quantized pool byte — matches the
    one-shot path exactly (chunked-vs-batch1 stays bit-exact with fp8
    KV); cross-attention scales calibrate once the same way.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import PrecisionConfig
from repro.core.sampling import rejection_sample, sample
from repro.data import tasks
from repro.kernels import KernelConfig
from repro.models import blocks as blocks_mod
from repro.models import ssm as ssm_mod
from repro.models import decode_step, init_cache, prefill, prefill_chunk
from repro.models.attention import paged_copy_rows
from repro.obs.scopes import span
from repro.obs.tracer import NULL_TRACER
from repro.serving.block_manager import BlockManager
from repro.serving.faults import NULL_INJECTOR
from repro.serving.scheduler import (
    Admit,
    Cow,
    Draft,
    Grow,
    Prefill,
    ScheduleDecision,
    Scheduler,
    StepBudget,
    SwapOut,
    Verify,
)
from repro.serving.spec_decode import SpecConfig


def kv_bytes_per_token(cfg, precision: PrecisionConfig) -> int:
    """*Self-attention* KV bytes one token occupies across all attention
    layers (the real target-device footprint; scales amortize to ~0).
    This is the per-token marginal cost only — the per-request *constant*
    footprint (SSM recurrent state, cross-attention KV) is
    `request_state_bytes`, and both enter the engine's byte accounting."""
    if cfg.attention_free:
        return 0
    n_attn = sum(cfg.is_attn_layer(i) for i in range(cfg.n_layers))
    elem = 1 if precision.kv_quantized else 2
    return n_attn * 2 * cfg.n_kv_heads * cfg.d_head * elem


def request_state_bytes(cfg, precision: PrecisionConfig,
                        src_len: int = 0) -> int:
    """Constant per-request slot-state bytes beyond the paged KV blocks:
    SSM recurrent state (`h` f32 + conv tail bf16 per SSM layer — never
    quantized, DESIGN §6) and the cross-attention KV a decoder holds over
    `src_len` encoder positions (quantized once at prefill, so FP8 halves
    it).  This is what the pre-fix `kv_bytes_per_token`-only accounting
    missed: enc-dec and hybrid models over-admitted against the byte
    budget because every admitted request silently pins this much extra
    memory."""
    total = 0
    repeats = blocks_mod.n_repeats(cfg)
    for spec in blocks_mod.layer_pattern(cfg):
        if spec.mixer == "ssm":
            h = cfg.ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
            conv = (cfg.ssm_conv - 1) * ssm_mod.conv_channels(cfg) * 2
            total += repeats * (h + conv)
        if spec.cross:
            elem = 1 if precision.kv_quantized else 2
            total += repeats * 2 * src_len * cfg.n_kv_heads * cfg.d_head \
                * elem
    return total


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (P,) unpadded
    max_new: int
    frames: Optional[np.ndarray] = None   # (S_src, D) enc-dec source frames
    generated: List[int] = dataclasses.field(default_factory=list)
    # parallel to `generated`: the weight version live when each token was
    # sampled (live-update attribution) and its rollout logprob under the
    # sampling distribution (recorded only when the engine was built with
    # want_logps=True — the pi^FP8 side of version-aware TIS/MIS)
    token_versions: List[int] = dataclasses.field(default_factory=list)
    token_logps: List[float] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    wasted_tokens: int = 0       # tokens re-restored after preemption
    prefilled: int = 0           # prompt tokens whose KV is (being) computed
    cached_tokens: int = 0       # valid KV rows in the pool (host truth)
    last_used: int = 0           # scheduler tick last scheduled (lru)
    # NOTE: swap residency lives in the allocator now, not here — while
    # preempted, `block_mgr.is_swapped(rid)` is true, the request's block
    # table is host-tier ids, and the engine keeps the block content in
    # `host_pool` (keyed by host block id) plus the non-KV slot state in
    # `_host_state` (keyed by rid)


@dataclasses.dataclass
class ServeReport:
    completed: List[Request]
    steps: int
    preemptions: int
    wasted_tokens: int
    emitted_tokens: int
    mean_occupancy: float
    budget_tokens: int
    swap_outs: int = 0
    swap_ins: int = 0
    peak_blocks_in_use: int = 0
    prefix_hit_blocks: int = 0     # block allocations avoided by sharing
    cow_copies: int = 0            # shared blocks privatized before a write
    prefill_chunks: int = 0        # chunked-prefill traces executed
    spec_steps: int = 0            # speculative verify traces executed
    draft_tokens: int = 0          # tokens proposed across all verifies
    accepted_tokens: int = 0       # draft tokens accepted by rejection
    # True when run() stopped WITHOUT finishing the submitted work — the
    # schedule went empty (capacity-stuck: nothing admissible, nothing
    # running) or the runaway guard tripped.  A partial report used to be
    # indistinguishable from success; callers must check this before
    # trusting `completed`.
    stalled: bool = False
    # fraction of the (possibly shrunk) block budget in live use at the
    # end of the run — the dispatch-pressure signal the fleet tie-breaks on
    kv_pressure: float = 0.0
    # p50/p95/p99 TTFT / TPOT / queue-wait summary (token-unit clock) —
    # populated only when the engine ran with a recording StepTracer
    latency: Optional[dict] = None
    # end-of-run pool/fleet gauge snapshot (`ServingEngine.gauge_snapshot`)
    gauges: Optional[dict] = None

    @property
    def useful_token_rate(self) -> float:
        """Useful tokens per decode step — the throughput proxy that maps to
        tokens/s on fixed-step-time hardware."""
        return self.emitted_tokens / max(self.steps, 1)

    @property
    def spec_tokens_per_step(self) -> float:
        """Tokens emitted per speculative verify step: accepted drafts
        plus the corrected/bonus token every verify also yields.  > 1 by
        construction when any verify ran; > 2 means speculation beats
        plain decode 2x on the slots it covered."""
        return (self.accepted_tokens + self.spec_steps) / \
            max(self.spec_steps, 1)


class ServingEngine:
    def __init__(self, params, cfg, precision: PrecisionConfig, *,
                 max_slots: int = 8, max_seq_len: int = 64,
                 kv_budget_bytes: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 prompt_pad: int = 16, block_size: int = 4,
                 admission: str = "reserve", prefix_sharing: bool = True,
                 eviction: str = "youngest",
                 prefill_chunk: Optional[int] = None,
                 step_budget: Optional[StepBudget] = None,
                 decode_kernel: str = "gather",
                 kernel_config=None,
                 eos_id: Optional[int] = tasks.EOS,
                 max_src_len: int = 8,
                 spec: Optional[SpecConfig] = None,
                 proposer=None,
                 want_logps: bool = False,
                 weight_version: int = 0,
                 host_kv_blocks: int = 0,
                 tracer=None,
                 faults=None,
                 replica_index: int = 0):
        assert admission in ("reserve", "ondemand"), admission
        assert decode_kernel in ("gather", "paged"), decode_kernel
        if kernel_config is None:
            kernel_config = KernelConfig(decode=(decode_kernel == "paged"))
        else:
            assert decode_kernel == "gather", (
                "pass either decode_kernel (legacy) or kernel_config, "
                "not both")
            kernel_config = KernelConfig.parse(kernel_config)
        assert not (kernel_config.any and cfg.attention_free), (
            "attention kernels have nothing to serve on an attention-free "
            "model; leave kernel_config off")
        assert prefill_chunk is None or not cfg.is_encdec, (
            "enc-dec requests prefill one-shot (the encoder pass over "
            "frames is not chunkable); leave prefill_chunk unset")
        self.prompt_pad = prompt_pad   # legacy one-shot prefill width
        self.params = params
        self.cfg = cfg
        self.precision = precision
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.temperature = temperature
        # top_k rides along with temperature to every sample() call —
        # serving must draw from the SAME truncated distribution as the
        # rollout sampler (and as the speculative verifier) for identical
        # sampler settings, or the one-sampler bit-identical contract in
        # core/sampling.py breaks
        self.top_k = top_k
        # record per-token rollout logprobs on Request.token_logps (one
        # vocab-wide log_softmax per sample call — off by default because
        # pure serving discards them; the RL fleet path needs them for
        # version-aware TIS/MIS)
        self.want_logps = want_logps
        # weight version currently serving (stamped onto every generated
        # token); bumped by install_weights at step boundaries
        self.weight_version = weight_version
        # one tracer per engine; NULL_TRACER keeps every instrumentation
        # site at a single `if self.tracer.enabled` branch when disabled
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # fault-injection seam (serving.faults): same single-branch
        # contract as the tracer.  `replica_index` keys the injector's
        # per-replica schedules; ServingFrontend overwrites it with the
        # engine's fleet position.
        self.faults = faults if faults is not None else NULL_INJECTOR
        self.replica_index = replica_index
        self._staged_weights = None     # (params, version) for next step()
        self._executing = False         # install_weights boundary guard
        self.admission = admission
        self.kernels = kernel_config
        self.use_kernel = kernel_config.decode   # legacy alias (decode path)
        self.eos_id = eos_id           # None = decode max_new tokens always
        self.src_pad = max_src_len     # enc-dec frames capacity per slot
        self.key = jax.random.key(seed)
        self.scheduler = Scheduler(eviction=eviction,
                                   prefill_chunk=prefill_chunk,
                                   budget=step_budget,
                                   spec=spec, proposer=proposer)
        # Speculation is sound only where the verify chunk's state is
        # FULLY rewindable by a length truncation: pure causal attention
        # over the paged pool.  SSM recurrence advances in place during
        # the chunk (no rewind), cross/multimodal prefills don't stream
        # through prefill_chunk at all.
        self._spec_ok = (
            not cfg.attention_free and not cfg.is_encdec
            and cfg.frontend is None
            and all(s.mixer == "attn" and not s.cross
                    for s in blocks_mod.layer_pattern(cfg)))
        if spec is not None and not self._spec_ok:
            raise ValueError(
                "speculative decoding needs an attention-only decoder "
                "(paged KV is the only state the rewind contract can "
                "truncate); this config has SSM/cross/multimodal state")
        # shared-prefix compute skip is sound only when prefix KV is the
        # whole carried state: pure causal attention, no recurrent/cross
        # state, no multimodal prefix
        self._chunk_skip_ok = (
            not cfg.is_encdec and cfg.frontend is None
            and all(s.mixer == "attn" and not s.cross
                    for s in blocks_mod.layer_pattern(cfg)))
        # prefix-index sharing keys blocks by prompt TOKENS; on enc-dec /
        # multimodal models the decoder's self-KV also depends on the
        # frames, so two same-token requests must never share blocks
        prefix_sharing = prefix_sharing and not cfg.is_encdec \
            and cfg.frontend is None

        per_tok = max(kv_bytes_per_token(cfg, precision), 1)
        # per-request constant footprint beyond paged KV (SSM state, cross
        # KV) — priced into the byte budget as block-equivalents below
        self.state_bytes = request_state_bytes(
            cfg, precision, src_len=max_src_len if cfg.is_encdec else 0)
        if kv_budget_bytes is None:
            kv_budget_bytes = per_tok * max_slots * max_seq_len \
                + max_slots * self.state_bytes
        # Physical block byte size is precision-INDEPENDENT (`block_size`
        # tokens at bf16 KV width), so quantizing the KV cache doubles the
        # tokens each block holds rather than the number of blocks — the
        # block-capacity mechanism of §2.3.2.
        per_tok_bf16 = max(kv_bytes_per_token(
            cfg, precision.replace(kv_cache_dtype="bf16")), 1)
        # host_kv_blocks reserves a host-memory tier for demoted cache
        # blocks (evictor demote-before-drop); 0 keeps the allocator's
        # single-tier drop-on-evict behavior.  Live swap-out demotions
        # always ride the host tier regardless — preemption correctness
        # is never capacity-gated.
        # kept for reset_for_rejoin: a cold restart rebuilds the
        # allocator with the exact construction-time sizing
        self._bm_init = dict(
            budget_bytes=kv_budget_bytes,
            block_bytes=block_size * per_tok_bf16, per_tok=per_tok,
            prefix_sharing=prefix_sharing, host_blocks=host_kv_blocks)
        self.block_mgr = BlockManager.from_byte_budget(
            kv_budget_bytes, block_size * per_tok_bf16, per_tok,
            enable_prefix_sharing=prefix_sharing,
            host_blocks=host_kv_blocks)
        self.block_mgr.set_host_callbacks(
            demote_copy=self._host_copy_out_block,
            host_drop=self._host_drop_block)
        # host tier storage: host block id -> per-layer (k, v) numpy rows;
        # rid -> snapshotted non-KV slot state + pending token while the
        # request is swapped out
        self.host_pool: Dict[int, Dict[str, Tuple[np.ndarray, np.ndarray]]] \
            = {}
        self._host_state: Dict[int, dict] = {}
        # host ids retired by the allocator BEFORE their swap-out copy
        # materialized (a same-plan swap-out -> re-admit promotes the
        # victim right back, and device prefix hits supersede the head's
        # host copies at plan time) — `_exec_swap_out` must skip writing
        # them or the storage leaks.  Ids are never recycled, so a
        # membership test here can never alias a later block.
        self._host_dead_on_arrival: set = set()
        # Mutable token-denominated view of the budget; shrinking it lowers
        # the effective block limit below the physical pool size.
        self.budget_tokens = self.block_mgr.capacity_tokens
        # block-equivalents one admitted request's slot state pins against
        # the budget, and the token-units moving it over the host link
        # costs a swap (scheduler StepBudget / cost accounting)
        self.state_blocks = -(-self.state_bytes
                              // max(self.block_mgr.block_bytes, 1)) \
            if self.state_bytes else 0
        self.state_swap_tokens = self.state_blocks * self.block_mgr.block_size

        self.cache = init_cache(cfg, max_slots, max_seq_len, precision,
                                page_size=self.block_mgr.block_size,
                                num_pages=self.block_mgr.num_blocks,
                                src_len=self.src_pad if cfg.is_encdec else 0)
        self.has_paged_kv = "block_tables" in self.cache
        self.slot_req: List[Optional[Request]] = [None] * max_slots
        self.queue: List[Request] = []
        self.done: List[Request] = []
        self._next_rid = 0
        self.pending_tok = np.zeros((max_slots,), np.int32)
        self._scales_calibrated = False
        self.stats = dict(preemptions=0, wasted_tokens=0, emitted=0,
                          steps=0, occupancy=0.0, swap_outs=0, swap_ins=0,
                          peak_blocks=0, prefix_hits=0, cow_copies=0,
                          prefill_chunks=0, spec_steps=0, draft_tokens=0,
                          accepted_tokens=0, demoted_blocks=0,
                          promoted_blocks=0)

    # ------------------------------------------------------------------
    def submit(self, prompt_ids, max_new: int, rid: Optional[int] = None,
               frames=None):
        prompt = np.asarray(prompt_ids, np.int32)
        if self.scheduler.prefill_chunk is None and \
                len(prompt) > self.prompt_pad:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds prompt_pad="
                f"{self.prompt_pad}; enable chunked prefill "
                f"(prefill_chunk=...) to serve long prompts")
        if len(prompt) + max_new > self.max_seq_len:
            # the block table has ceil(max_seq_len / block_size) entries;
            # a decode write past it would clamp into the wrong block and
            # silently corrupt live KV
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new}) exceeds "
                f"max_seq_len={self.max_seq_len}")
        if self.cfg.is_encdec:
            if frames is None:
                raise ValueError(
                    "encoder-decoder serving needs frames=(S_src, d_model) "
                    "source embeddings per request")
            frames = np.asarray(frames, np.float32)
            if frames.ndim != 2 or frames.shape[1] != self.cfg.d_model:
                raise ValueError(
                    f"frames must be (S_src, d_model={self.cfg.d_model}); "
                    f"got {frames.shape}")
            if frames.shape[0] > self.src_pad:
                raise ValueError(
                    f"{frames.shape[0]} frames exceed max_src_len="
                    f"{self.src_pad}")
        elif frames is not None:
            raise ValueError("frames only apply to encoder-decoder models")
        if rid is None:
            rid = self._next_rid
        # rid keys BlockManager ownership — collisions would merge two live
        # requests' block lists, so keep auto-assignment monotonic
        self._next_rid = max(self._next_rid, rid + 1)
        self.queue.append(Request(rid=rid, prompt=prompt, max_new=max_new,
                                  frames=frames))
        if self.tracer.enabled:
            self.tracer.record_submit(self, self.queue[-1])

    def cancel(self, rid: int) -> bool:
        """Drop a request wherever it lives — queued (including swapped-
        out victims, which sit at the queue head), or occupying a slot —
        and free its blocks on both tiers.  No further tokens are
        emitted; tokens already generated stay on the Request.  Returns
        False for an unknown rid (finished, or never here), so a double
        cancel / a cancel after a crash-reset is a safe no-op — the
        front-end's abort path must never be able to corrupt live
        state."""
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                self.queue.pop(i)
                self.block_mgr.free(rid)
                self._host_state.pop(rid, None)
                return True
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                self.slot_req[slot] = None
                self.block_mgr.free(rid)
                self._clear_slot(slot)
                self._host_state.pop(rid, None)
                return True
        return False

    def reset_for_rejoin(self, params, version: int):
        """Cold restart after a transient crash: everything device-side
        is considered lost.  Fresh allocator (construction-time sizing),
        fresh KV pool, cleared slots/queue/host tier — then the fleet's
        current weights are installed through the normal seam (so a
        rejoin install can itself fail and the front-end keeps the
        replica down).  `done` survives: those requests' finals were
        already streamed and the front-end's bookkeeping still keys on
        them.  Cumulative `stats` survive too — they are telemetry of
        work performed, and the work before the crash did happen."""
        bm = self._bm_init
        self.block_mgr = BlockManager.from_byte_budget(
            bm["budget_bytes"], bm["block_bytes"], bm["per_tok"],
            enable_prefix_sharing=bm["prefix_sharing"],
            host_blocks=bm["host_blocks"])
        self.block_mgr.set_host_callbacks(
            demote_copy=self._host_copy_out_block,
            host_drop=self._host_drop_block)
        self.budget_tokens = self.block_mgr.capacity_tokens
        self.cache = init_cache(
            self.cfg, self.max_slots, self.max_seq_len, self.precision,
            page_size=self.block_mgr.block_size,
            num_pages=self.block_mgr.num_blocks,
            src_len=self.src_pad if self.cfg.is_encdec else 0)
        self.slot_req = [None] * self.max_slots
        self.queue = []
        self.pending_tok = np.zeros((self.max_slots,), np.int32)
        self.host_pool = {}
        self._host_state = {}
        self._host_dead_on_arrival = set()
        self._staged_weights = None
        # a fresh pool holds no calibrated scales; the first prefill
        # after rejoin re-locks them (one full-width chunk, as at boot)
        self._scales_calibrated = False
        self.install_weights(params, version)

    # -- live weight updates ------------------------------------------------
    def install_weights(self, params, version: int):
        """In-place weight hot-swap at a step boundary — no draining.

        Replaces the rollout params between `Scheduler.step()` boundaries:
        in-flight requests keep their slots, blocks and pending tokens and
        simply continue decoding under the new weights; every token they
        emit from here on is stamped with `version`.  Their existing KV
        stays as-written (computed under the old weights) — that mixture
        is exactly the train-inference mismatch the per-token version
        attribution + TIS/MIS correction accounts for.

        KV-cache scales are NOT recalibrated: the pool already holds
        bytes quantized at the locked scales, and re-deriving scales
        mid-flight would silently re-interpret them.  The residual scale
        staleness is part of the same per-token-corrected mismatch.
        """
        assert not self._executing, (
            "install_weights must run between engine steps, never inside "
            "execute() — a mid-step swap would split one trace across "
            "two policies")
        assert version >= self.weight_version, (
            f"weight version must be monotonic: {version} < "
            f"{self.weight_version}")
        if self.faults.enabled:
            # the install-failure seam sits BEFORE any mutation: a failed
            # install leaves params/version untouched, so installs are
            # replica-atomic and a fleet push can only be fleet-partial
            # (which the front-end's retry/quarantine resolves)
            self.faults.on_install(self, version)
        self.params = params
        self.weight_version = version
        if self.tracer.enabled:
            self.tracer.record_weights(self, version, staged=False)

    def stage_weights(self, params, version: int):
        """Queue a hot-swap to be installed at the next `step()` boundary
        (the asynchronous spelling of `install_weights`: safe to call at
        any time, including while a step is executing)."""
        self._staged_weights = (params, version)
        if self.tracer.enabled:
            self.tracer.record_weights(self, version, staged=True)

    def _apply_staged_weights(self):
        if self._staged_weights is not None:
            params, version = self._staged_weights
            self._staged_weights = None
            self.install_weights(params, version)

    # -- accounting ---------------------------------------------------------
    @property
    def block_size(self) -> int:
        return self.block_mgr.block_size

    @property
    def _state_blocks_in_use(self) -> int:
        """Block-equivalents pinned by active slots' non-KV state (derived
        from slot occupancy, so plan-time slot updates are priced
        immediately)."""
        return self.state_blocks * sum(
            r is not None for r in self.slot_req)

    @property
    def _effective_blocks(self) -> int:
        """Block limit left for *paged KV* under the (possibly shrunk)
        token budget: active slots' constant state (SSM h/conv, cross KV)
        is netted out first, so a budget shrink can force preemption even
        on attention-free models whose KV usage is zero."""
        return min(self.block_mgr.num_blocks,
                   self.block_mgr.blocks_for_tokens(self.budget_tokens)) \
            - self._state_blocks_in_use

    @property
    def kv_pressure(self) -> float:
        """Fraction of the (possibly shrunk) block budget in live use:
        (allocated pool blocks + slot-state block-equivalents) / budget
        blocks.  The fleet's dispatch tie-break and the tracer's gauge
        stream both read this — 1.0 means the next growth preempts."""
        budget = min(self.block_mgr.num_blocks,
                     self.block_mgr.blocks_for_tokens(self.budget_tokens))
        used = self.block_mgr.blocks_in_use + self._state_blocks_in_use
        return used / max(budget, 1)

    def gauge_snapshot(self) -> dict:
        """Point-in-time pool/slot/spec gauges (JSON-native).  The tracer
        samples this every step into `GaugeEvent`s; `run()` attaches the
        final snapshot to `ServeReport.gauges`."""
        bm = self.block_mgr
        drafted = self.stats["draft_tokens"]
        return {
            "blocks_in_use": bm.blocks_in_use,
            "blocks_free": bm.num_free_blocks - bm.num_cached_blocks,
            "blocks_cached": bm.num_cached_blocks,
            "state_block_equiv": self._state_blocks_in_use,
            "slots_active": sum(r is not None for r in self.slot_req),
            "max_slots": self.max_slots,
            "queue_len": len(self.queue),
            "kv_pressure": self.kv_pressure,
            "prefix_hit_blocks": self.stats["prefix_hits"],
            "spec_acceptance": (self.stats["accepted_tokens"] / drafted
                                if drafted else 0.0),
            "weight_version": self.weight_version,
            # host tier: occupancy split (swapped requests' live blocks
            # vs demoted cache blocks) and cumulative cross-tier traffic
            "host_blocks_live": bm.num_host_live,
            "host_blocks_cached": bm.num_host_cached,
            "host_bytes_in_use": bm.host_bytes_in_use,
            "demoted_blocks": bm.demoted_blocks + bm.cache_demotions,
            "promoted_blocks": bm.promoted_blocks,
            "host_transfer_bytes": (bm.demoted_blocks + bm.cache_demotions
                                    + bm.promoted_blocks) * bm.block_bytes,
        }

    @property
    def _needs_kv_calibration(self) -> bool:
        """True until the first prefill locks the pool's KV scales (the
        scheduler widens that prefill's chunk to the whole prompt so the
        calibration amax window matches one-shot prefill exactly)."""
        return (self.precision.kv_quantized
                and self.precision.calculate_kv_scales
                and not self._scales_calibrated
                and not self.cfg.attention_free)

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _reserve_blocks(self, req: Request) -> int:
        """Paged-KV blocks a request needs at admission time (its constant
        state footprint is priced separately via `state_blocks`)."""
        if self.cfg.attention_free:
            return 0
        retained = self.block_mgr.swapped_tokens(req.rid)
        if self.admission == "reserve":
            # worst case: full prompt + every token it may still generate
            tokens = max(len(req.prompt) + req.max_new, retained + 1)
        else:
            # vLLM semantics: what it holds right now, +1 so the first
            # decode step's KV write is always mapped (a request admitted
            # after the growth pass ran would otherwise scatter its pending
            # token to the trash block when the prompt fills its last block)
            tokens = max(len(req.prompt) + 1, retained + 1)
        return self.block_mgr.blocks_for_tokens(tokens)

    # -- cache surgery ------------------------------------------------------
    def _set_table_row(self, slot: int, ids: List[int]):
        if not self.has_paged_kv:       # attention-free: no block tables
            return
        w = self.cache["block_tables"].shape[1]
        row = np.full((w,), -1, np.int32)
        row[:len(ids)] = ids[:w]
        self.cache["block_tables"] = \
            self.cache["block_tables"].at[slot].set(jnp.asarray(row))

    def _clear_slot(self, slot: int):
        if self.has_paged_kv:
            w = self.cache["block_tables"].shape[1]
            self.cache["block_tables"] = \
                self.cache["block_tables"].at[slot].set(
                    jnp.full((w,), -1, jnp.int32))
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(0)

    def _update_slot_state(self, ssm=None, cross=None):
        """Rebuild cache["slots"] with `ssm(name, state)` / `cross(name,
        kv_cache)` applied to every layer-stack entry holding that kind
        (None leaves the kind untouched).  The ONE writer for non-KV slot
        state — reset, swap-in restore and the decode write-back all go
        through here so a new state kind has a single seam to thread."""
        slots = {}
        changed = False
        for name, sd in self.cache["slots"].items():
            merged = dict(sd)
            if ssm is not None and "ssm" in sd:
                merged["ssm"] = ssm(name, sd["ssm"])
                changed = True
            if cross is not None and "cross" in sd:
                merged["cross"] = cross(name, sd["cross"])
                changed = True
            slots[name] = merged
        if changed:
            self.cache = dict(self.cache, slots=slots)

    def _snapshot_slot_state(self, slot: int) -> Dict[str, dict]:
        """Host copies of the slot's non-KV state rows, keyed by
        layer-stack name then kind (the read counterpart of
        `_update_slot_state`)."""
        state: Dict[str, dict] = {}
        for name, sd in self.cache["slots"].items():
            entry = {}
            if "ssm" in sd:
                entry["ssm"] = jax.tree.map(
                    lambda a: np.asarray(a[:, slot:slot + 1]), sd["ssm"])
            if "cross" in sd:
                cr = sd["cross"]
                entry["cross"] = (np.asarray(cr.k[:, slot:slot + 1]),
                                  np.asarray(cr.v[:, slot:slot + 1]))
            if entry:
                state[name] = entry
        return state

    def _reset_slot_state(self, slot: int):
        """Zero the slot's recurrent state for a FRESH occupant.  The
        previous occupant's SSM h/conv rows otherwise leak into the new
        request's prefill as a bogus h0 (cross caches need no reset — the
        enc-dec prefill overwrites them wholesale and `src_lengths` masks
        the stale tail)."""
        self._update_slot_state(
            ssm=lambda name, st: jax.tree.map(
                lambda a: a.at[:, slot].set(0), st))

    def _slot_view(self, slot: int) -> dict:
        """Batch-1 cache view for prefill into `slot`: KV pools are shared
        (paged — no batch dim), batched per-sequence state is sliced."""
        slots = {}
        for name, sd in self.cache["slots"].items():
            view = {}
            for key, state in sd.items():
                if key == "kv":
                    view[key] = state
                else:   # ssm / cross state: (R, B, ...) -> (R, 1, ...)
                    view[key] = jax.tree.map(
                        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 1)
                        if a.ndim >= 2 else a,
                        state)
            slots[name] = view
        out = {
            "slots": slots,
            "lengths": self.cache["lengths"][slot:slot + 1],
        }
        if self.has_paged_kv:
            out["block_tables"] = self.cache["block_tables"][slot:slot + 1]
        if "src_lengths" in self.cache:
            out["src_lengths"] = self.cache["src_lengths"][slot:slot + 1]
        return out

    def _merge_view(self, new_cache: dict, slot: int):
        slots = {}
        for name, sd in self.cache["slots"].items():
            merged = {}
            for key, state in sd.items():
                if key == "kv":
                    merged[key] = new_cache["slots"][name][key]
                else:
                    # batched leaves merge at the slot; scalar leaves (the
                    # per-layer cross k/v scales) are pool-wide globals and
                    # take the prefill's value — exactly like the paged
                    # pool's own scales, which ride along in "kv"
                    merged[key] = jax.tree.map(
                        lambda big, small: jax.lax.dynamic_update_slice_in_dim(
                            big, small, slot, 1) if big.ndim >= 2 else small,
                        state, new_cache["slots"][name][key])
            slots[name] = merged
        self.cache = dict(self.cache, slots=slots)
        if "src_lengths" in self.cache and "src_lengths" in new_cache:
            self.cache["src_lengths"] = \
                self.cache["src_lengths"].at[slot].set(
                    new_cache["src_lengths"][0])

    # -- execution mechanism -------------------------------------------------
    def execute(self, decision: ScheduleDecision):
        """Run one planned step.  Actions run strictly in plan order (the
        scheduler's bookkeeping already assumed it: a victim's rows are
        copied to host before any later-ordered action can overwrite
        them); the fused decode over `decode_slots` runs last."""
        with span("engine.step"):
            tracing = self.tracer.enabled
            if tracing:
                self.tracer.begin_step(self)
            self._executing = True
            try:
                self._execute(decision)
            finally:
                self._executing = False
            if tracing:
                self.tracer.end_step(self, decision)

    def _execute(self, decision: ScheduleDecision):
        tracing = self.tracer.enabled
        n_verify = 0
        for act in decision.actions:
            if isinstance(act, SwapOut):
                self._exec_swap_out(act)
                if tracing:
                    self.tracer.record_swap_out(self, act)
            elif isinstance(act, Admit):
                restored = self._exec_admit(act)
                if tracing:
                    self.tracer.record_admit(self, act, restored)
            elif isinstance(act, Grow):
                self._set_table_row(act.slot, act.block_ids)
                if tracing:
                    self.tracer.record_grow(
                        self, act, self.slot_req[act.slot].rid)
            elif isinstance(act, Cow):
                self._copy_block(act.src, act.dst)
                self._set_table_row(act.slot, act.block_ids)
                if tracing:
                    self.tracer.record_cow(
                        self, act, self.slot_req[act.slot].rid)
            elif isinstance(act, Prefill):
                self._exec_prefill(act)
                if tracing:
                    self.tracer.record_prefill(self, act)
            elif isinstance(act, Draft):
                self._exec_draft(act)
                if tracing:
                    self.tracer.record_draft(self, act)
            elif isinstance(act, Verify):
                accepted, committed = self._exec_verify(act)
                if tracing:
                    self.tracer.record_verify(self, act, accepted,
                                              committed)
                n_verify += 1
            else:                              # pragma: no cover
                raise TypeError(f"unknown action {act!r}")
        self.stats["peak_blocks"] = max(self.stats["peak_blocks"],
                                        self.block_mgr.blocks_in_use)
        if decision.decode_slots:
            self._exec_decode(decision.decode_slots)
        elif n_verify:
            # a verify-only step is still one serving step (the unit the
            # throughput proxy divides by) — counting it free would let
            # speculation fake its accepted-tokens/step win
            self.stats["steps"] += 1
        if n_verify:
            self.stats["occupancy"] += n_verify / self.max_slots

    def step(self) -> ScheduleDecision:
        """One scheduler+engine step (the unit external drivers — the
        continuous-batching benchmark, the property tests — advance by).
        Weights staged via `stage_weights` are installed here, before the
        scheduler plans — the step-boundary swap hook.

        The crash seam fires FIRST, before any state mutates: a crashed
        step did nothing, so everything the replica had streamed before
        it remains exactly-once deliverable and the front-end's failover
        replay starts from a step boundary.  A staged install that fails
        (`WeightInstallError` from `_apply_staged_weights`) also leaves
        the step un-run — the front-end retries the install and
        re-enters `step()`."""
        if self.faults.enabled:
            self.faults.on_step(self)        # may raise ReplicaCrash
        self._apply_staged_weights()
        decision = self.scheduler.step(self)
        if not decision.is_empty:
            self.execute(decision)
        return decision

    def _try_admit(self):
        """Admission-only pass (tests drive this directly): plan and run
        admissions plus their prefill work, nothing else."""
        self.execute(self.scheduler.step(self, admit_only=True))

    def _commit_first_token(self, req: Request, tok, logp, slot: int):
        """Record the token sampled off the final prefill logits: the
        ONE place a request's generated/version/logp lists start.  A
        max_new=1 request is already done here — without the check it
        would ride through one decode step and deliver two tokens (the
        failover replay path is the first caller to submit remaining
        budgets of 1)."""
        req.generated = [int(tok)]
        req.token_versions = [self.weight_version]
        req.token_logps = [float(logp)] if logp is not None else []
        if len(req.generated) >= req.max_new:
            self.done.append(req)
            self.slot_req[slot] = None
            self.block_mgr.free(req.rid)
            self._clear_slot(slot)
            if self.tracer.enabled:
                self.tracer.record_finish(self, req)

    # -- prefill -------------------------------------------------------------
    def _exec_admit(self, act: Admit) -> int:
        """Returns the restore traffic in tokens — the host->device half
        of the decision's `swap_tokens` accounting, which the tracer's
        `AdmitEvent` carries.  Fresh admits can carry traffic too: a
        host-cached prefix hit is revived by the ordered copy-ins in
        `act.moves` (executed here, before this request's first chunk is
        reached in plan order)."""
        req = act.req
        self._set_table_row(act.slot, act.block_ids)
        if act.swap_in:
            return self._swap_in(act.slot, req, act)
        else:
            # fresh occupant: the slot's recurrent state rows still hold
            # the previous occupant's h/conv (the preemption-clobber bug:
            # these rows are NOT part of the paged pool, so nothing else
            # resets them)
            if act.moves:
                self._promote_blocks(act.moves)
            self._reset_slot_state(act.slot)
            self.cache["lengths"] = self.cache["lengths"].at[act.slot].set(
                req.prefilled)
            return act.n_promoted * self.block_size

    def _exec_prefill(self, act: Prefill):
        if act.oneshot:
            self._prefill_into(act.slot, act.req,
                               self.block_mgr.blocks_of(act.req.rid))
            return
        req = act.req
        chunk = np.full((act.width,), tasks.PAD, np.int32)
        n = act.end - act.start
        chunk[:n] = req.prompt[act.start:act.end]
        prec = self.precision
        if self._scales_calibrated and prec.kv_quantized:
            prec = prec.replace(calculate_kv_scales=False)
        view = self._slot_view(act.slot)
        logits, new_cache = prefill_chunk(
            self.params, jnp.asarray(chunk)[None, :],
            jnp.array([act.start], jnp.int32), jnp.array([n], jnp.int32),
            view, self.cfg, prec, use_kernel=self.kernels.prefill)
        self._merge_view(new_cache, act.slot)
        self.cache["lengths"] = self.cache["lengths"].at[act.slot].set(
            act.end)
        req.cached_tokens = act.end
        self._scales_calibrated = True
        self.stats["prefill_chunks"] += 1
        if act.last:
            self.block_mgr.register_prefix(req.rid, req.prompt)
            self.key, k = jax.random.split(self.key)
            tok, logp = sample(logits[0], k, self.temperature, self.top_k,
                               want_logp=self.want_logps)
            self.pending_tok[act.slot] = tok
            self._commit_first_token(req, tok, logp, act.slot)

    def _prefill_into(self, slot: int, req: Request, ids: List[int]):
        """Legacy one-shot prefill: the whole prompt through one fixed
        `prompt_pad`-width batch-1 trace."""
        p = len(req.prompt)                  # <= prompt_pad (submit checks)
        padded = np.full((self.prompt_pad,), tasks.PAD, np.int32)
        padded[:p] = req.prompt
        prompt = jnp.asarray(padded)[None, :]
        prec = self.precision
        if self._scales_calibrated and prec.kv_quantized:
            # vLLM semantics: only the first forward after (re)load
            # calibrates; later prefills reuse the shared pool scales
            prec = prec.replace(calculate_kv_scales=False)
        self._set_table_row(slot, ids)
        view = self._slot_view(slot)
        view["lengths"] = jnp.zeros((1,), jnp.int32)
        inputs = {"tokens": prompt, "lengths": jnp.array([p])}
        if self.cfg.is_encdec:
            # encoder source: the request's frames padded to the slot's
            # fixed capacity; src_lengths masks the padding through the
            # encoder and every later cross-attention read
            n = req.frames.shape[0]
            fr = np.zeros((1, self.src_pad, self.cfg.d_model), np.float32)
            fr[0, :n] = req.frames
            inputs["frames"] = jnp.asarray(fr, jnp.bfloat16)
            inputs["src_lengths"] = jnp.array([n], jnp.int32)
        # Shared prefix blocks in `ids` are re-written here with the exact
        # bytes they already hold: causal attention makes prefix KV a pure
        # function of the prefix tokens, and scales are global post-
        # calibration — so the logits get their full prompt while the
        # other holders' KV stays bit-identical.
        logits, new_cache = prefill(self.params, inputs, view, self.cfg, prec)
        self._merge_view(new_cache, slot)
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(p)
        self._scales_calibrated = True
        self.block_mgr.register_prefix(req.rid, req.prompt)
        self.key, k = jax.random.split(self.key)
        tok, logp = sample(logits[0], k, self.temperature, self.top_k,
                           want_logp=self.want_logps)
        self.pending_tok[slot] = tok
        self.slot_req[slot] = req
        req.cached_tokens = p
        self._commit_first_token(req, tok, logp, slot)

    # -- preemption / swap ---------------------------------------------------
    def _host_copy_out_block(self, dev: int, host: int):
        """Copy one device pool row to host storage under host block id
        `host` — the allocator's `demote_copy` hook.  Only the evictor's
        demote-before-drop calls this synchronously (the content was
        written in an earlier step, so plan-time copying cannot race any
        execute-time write of the current step); swap-out demotions
        batch the same copy at the SwapOut action's place in execute
        order instead."""
        if self.faults.enabled:
            # cache-demotion copies may fail (HostCopyError): the
            # allocator falls back to dropping the prefix entry — a
            # performance loss only, the content is a refcount-0 cache
            self.faults.on_demote_copy(self)
        entry = {}
        for name, sd in self.cache["slots"].items():
            if "kv" in sd:
                kv = sd["kv"]
                entry[name] = (np.asarray(kv.k[:, dev]),
                               np.asarray(kv.v[:, dev]))
        self.host_pool[host] = entry

    def _host_drop_block(self, host: int):
        """Free a dropped host block's storage — the allocator's
        `host_drop` hook (cache-pressure drops and superseded swap
        copies).  A drop can arrive before the storage exists: a
        same-plan swap-out -> re-admit retires superseded host ids at
        plan time while the SwapOut that would write them is still
        pending in execute order — flag those so the write is skipped."""
        if host in self.host_pool:
            del self.host_pool[host]
        else:
            self._host_dead_on_arrival.add(host)

    def _promote_blocks(self, moves):
        """Execute ordered (host_id, device_id) promote pairs: write each
        host block's rows into its fresh device pool row, then release
        the host storage (the allocator already retired the host ids)."""
        hids = [h for h, _ in moves]
        idx = jnp.asarray([d for _, d in moves], jnp.int32)
        slots = {}
        for name, sd in self.cache["slots"].items():
            merged = dict(sd)
            if "kv" in sd and all(name in self.host_pool[h] for h in hids):
                kv = sd["kv"]
                ks = np.stack([self.host_pool[h][name][0] for h in hids],
                              axis=1)
                vs = np.stack([self.host_pool[h][name][1] for h in hids],
                              axis=1)
                merged["kv"] = kv._replace(
                    k=kv.k.at[:, idx].set(jnp.asarray(ks)),
                    v=kv.v.at[:, idx].set(jnp.asarray(vs)))
            slots[name] = merged
        self.cache = dict(self.cache, slots=slots)
        for h in hids:
            self.host_pool.pop(h, None)
        self.stats["promoted_blocks"] += len(moves)

    def _exec_swap_out(self, act: SwapOut):
        """Execute the device half of an allocator demote: copy the
        victim's blocks into their host-tier ids.  The allocator already
        moved the request to the host tier at plan time (table = host
        ids, device blocks freed); refcount-aware demote means blocks
        shared with an active request never left the pool, and no action
        ordered after this one can have overwritten the rows being
        copied."""
        req = act.req
        # a same-plan re-admit may have already retired some of these
        # host ids (superseded by device prefix hits) — don't write
        # storage nobody will ever read
        moves = [(d, h) for d, h in act.moves
                 if h not in self._host_dead_on_arrival]
        self._host_dead_on_arrival.difference_update(
            h for _, h in act.moves)
        if moves:
            idx = jnp.asarray([d for d, _ in moves], jnp.int32)
            per_layer = {}
            for name, sd in self.cache["slots"].items():
                if "kv" in sd:
                    kv = sd["kv"]
                    per_layer[name] = (np.asarray(kv.k[:, idx]),
                                       np.asarray(kv.v[:, idx]))
            for j, (_, h) in enumerate(moves):
                self.host_pool[h] = {
                    name: (k[:, j], v[:, j])
                    for name, (k, v) in per_layer.items()}
        # Non-KV slot state rides along as tier-tagged per-request state:
        # SSM h/conv and cross-attention K/V live slot-indexed (not in
        # the paged pool), so a swap that only saved blocks would let the
        # next occupant of this slot clobber them — resume would then
        # decode from garbage state.  Snapshotting happens HERE, at this
        # action's place in the execution order: when this victim was
        # swap-admitted earlier in the SAME step, `pending_tok[slot]`
        # only became correct when that restore ran (and that Admit
        # consumed the previous `_host_state` entry).
        state = self._snapshot_slot_state(act.slot)
        self._host_state[req.rid] = {
            "state": state or None,
            "pending": int(self.pending_tok[act.slot])
            if req.prefilled >= len(req.prompt) else 0,
        }
        req.preemptions += 1
        self.stats["preemptions"] += 1
        self.stats["swap_outs"] += 1
        self.stats["demoted_blocks"] += len(act.moves)
        self._clear_slot(act.slot)

    def _swap_in(self, slot: int, req: Request, act: Admit) -> int:
        """Execute the device half of an allocator promote: copy the
        host-tier blocks back into fresh pool rows; no recompute.
        Returns the restore traffic in tokens (the `wasted` charge).

        The leading `n_shared` table entries came from a prefix-index hit
        at re-admission: those pool rows already hold the prompt's KV
        (content-keyed, bit-identical), so the allocator dropped their
        host copies without a move — only the tail (`act.moves`) crosses
        the link, and only the restored tokens (plus the slot-state
        block-equivalents for SSM/cross models) count as `wasted` (the
        swap tax of the preemption)."""
        if act.moves:
            self._promote_blocks(act.moves)
        hs = self._host_state.pop(req.rid, None) or {}
        state = hs.get("state")
        if state:
            # restore the victim's recurrent/cross rows into the (possibly
            # different) slot it resumes in
            host = state

            def restore_ssm(name, st):
                entry = host.get(name, {})
                if "ssm" not in entry:
                    return st
                return jax.tree.map(
                    lambda big, small: big.at[:, slot:slot + 1].set(
                        jnp.asarray(small)),
                    st, entry["ssm"])

            def restore_cross(name, cr):
                entry = host.get(name, {})
                if "cross" not in entry:
                    return cr
                host_k, host_v = entry["cross"]
                return cr._replace(
                    k=cr.k.at[:, slot:slot + 1].set(jnp.asarray(host_k)),
                    v=cr.v.at[:, slot:slot + 1].set(jnp.asarray(host_v)))

            self._update_slot_state(ssm=restore_ssm, cross=restore_cross)
        if self.cfg.is_encdec:
            self.cache["src_lengths"] = \
                self.cache["src_lengths"].at[slot].set(req.frames.shape[0])
        retained = act.retained
        s = min(act.n_shared, self.block_mgr.blocks_for_tokens(retained))
        restored = max(retained - s * self.block_size, 0)
        if state:
            restored += self.state_swap_tokens
        req.wasted_tokens += restored
        self.stats["wasted_tokens"] += restored
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(retained)
        self.pending_tok[slot] = hs.get("pending", 0)
        req.cached_tokens = retained
        self.stats["swap_ins"] += 1
        # the restored prompt blocks can serve later same-prompt requests
        # (no-op for prefixes still indexed by another holder, and for a
        # victim resumed mid-prefill whose prompt is not fully written)
        if req.prefilled >= len(req.prompt):
            self.block_mgr.register_prefix(req.rid, req.prompt)
        return restored

    # -- copy-on-write -------------------------------------------------------
    def _copy_block(self, src: int, dst: int):
        """Duplicate pool row `src` into `dst` across every attention
        layer (the device half of CoW)."""
        slots = {}
        for name, sd in self.cache["slots"].items():
            merged = dict(sd)
            if "kv" in sd:
                merged["kv"] = paged_copy_rows(sd["kv"], [src], [dst])
            slots[name] = merged
        self.cache = dict(self.cache, slots=slots)

    # -- speculative decoding ------------------------------------------------
    def _exec_draft(self, act: Draft):
        """The ordered record of the proposal.  The n-gram proposer ran
        host-side at plan time, so this only accounts the drafts; a
        draft-model proposer would do its device work here (ordered
        before the Verify that consumes its tokens)."""
        assert self.slot_req[act.slot] is act.req, (
            "draft for a slot whose occupant changed — the scheduler "
            "must cancel Draft/Verify when it preempts the slot")
        self.stats["draft_tokens"] += len(act.tokens)

    def _exec_verify(self, act: Verify):
        """Score pending-token + drafts in one `prefill_chunk` trace,
        rejection-sample, and rewind.

        The chunk is [pending, d_1..d_k] at positions [T, T+k] (T =
        `cached_tokens`): row 0's logits are bit-identical to what a
        plain decode step of the pending token would produce (same RoPE
        positions, same quantize/scatter path, masked-out gather columns
        contribute exact zeros), and row i scores draft i's successor.
        After `rejection_sample` accepts r drafts, the KV rewind is a
        host-side truncation: `lengths[slot]` and `cached_tokens` drop
        to T+1+r, stale rows beyond are never read (every attention path
        masks by length; paged kernels also clamp to `_live_blocks`) and
        the next write overwrites them in place.
        """
        req, slot = act.req, act.slot
        assert self.slot_req[slot] is req, (
            "verify for a slot whose occupant changed — the scheduler "
            "must cancel Draft/Verify when it preempts the slot")
        assert req.cached_tokens == act.start, (req.cached_tokens, act)
        k = len(act.tokens)
        chunk = np.full((act.width,), tasks.PAD, np.int32)
        chunk[0] = self.pending_tok[slot]
        chunk[1:1 + k] = act.tokens
        prec = self.precision
        if self._scales_calibrated and prec.kv_quantized:
            prec = prec.replace(calculate_kv_scales=False)
        view = self._slot_view(slot)
        logits, new_cache = prefill_chunk(
            self.params, jnp.asarray(chunk)[None, :],
            jnp.array([act.start], jnp.int32),
            jnp.array([k + 1], jnp.int32),
            view, self.cfg, prec, use_kernel=self.kernels.prefill,
            want_all_logits=True)
        self._merge_view(new_cache, slot)
        self.key, sub = jax.random.split(self.key)
        toks, n_acc, tok_logps = rejection_sample(
            logits[0, :k + 1], act.tokens, sub, self.temperature,
            self.top_k)
        # KV rewind: keep the pending token's row + the accepted prefix
        new_len = act.start + 1 + n_acc
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(new_len)
        req.cached_tokens = new_len
        self.stats["spec_steps"] += 1
        self.stats["accepted_tokens"] += n_acc
        # commit emitted tokens in order; EOS / max_new truncation scans
        # them exactly like successive decode steps would have
        committed = 0
        for j, tok in enumerate(toks):
            self.stats["emitted"] += 1
            committed += 1
            req.generated.append(tok)
            req.token_versions.append(self.weight_version)
            if self.want_logps:
                req.token_logps.append(float(tok_logps[j]))
            self.pending_tok[slot] = tok
            if tok == self.eos_id or len(req.generated) >= req.max_new:
                self.done.append(req)
                self.slot_req[slot] = None
                self.block_mgr.free(req.rid)
                self._clear_slot(slot)
                if self.tracer.enabled:
                    self.tracer.record_finish(self, req)
                break
        return n_acc, committed

    # -- decode --------------------------------------------------------------
    def _exec_decode(self, decode_slots: List[int]):
        """One fused decode step over `decode_slots`.  Mid-prefill slots
        are masked to the trash block for the duration: the batch-wide KV
        scatter writes one row per slot, and a garbage row must never
        land in a real (possibly shared) block.  Their SSM state rows get
        the same treatment by write-back — the fused recurrence advances
        every batch row, and a mid-prefill slot's h/conv must not absorb
        a garbage decode token between its chunks."""
        # a slot whose request finished at this step's final prefill
        # chunk (max_new=1: the sampled first token exhausted the
        # budget) was freed mid-step; its cleared row already points at
        # the trash table, so just don't decode or commit for it
        decode_slots = [i for i in decode_slots
                        if self.slot_req[i] is not None]
        if not decode_slots:
            return
        if self.tracer.enabled:
            # contexts are priced pre-decode (cached rows + the row being
            # written), matching the benchmarks' decode-bytes convention
            self.tracer.record_decode(
                self, decode_slots,
                [self.slot_req[i].rid for i in decode_slots],
                [self.slot_req[i].cached_tokens + 1 for i in decode_slots])
        masked = [i for i, r in enumerate(self.slot_req)
                  if r is not None and i not in decode_slots]
        if masked and self.has_paged_kv:
            saved = self.cache["block_tables"]
            self.cache["block_tables"] = saved.at[jnp.asarray(masked)].set(-1)
        old_slots = self.cache["slots"]
        saved_lengths = self.cache["lengths"]
        toks = jnp.asarray(self.pending_tok)
        logits, self.cache, _ = decode_step(
            self.params, toks, self.cache, self.cfg, self.precision,
            use_kernel=self.kernels.decode)
        if masked:
            idx = jnp.asarray(masked)
            if self.has_paged_kv:
                self.cache["block_tables"] = \
                    self.cache["block_tables"].at[idx].set(saved[idx])
            self._update_slot_state(
                ssm=lambda name, st: jax.tree.map(
                    lambda new, old: new.at[:, idx].set(old[:, idx]),
                    st, old_slots[name]["ssm"]))
            # decode_step bumps EVERY row's length; masked slots didn't
            # decode, so restore theirs.  Mid-prefill slots would have
            # overwritten the bogus +1 at their next chunk anyway, but a
            # speculating slot is length-authoritative after its rewind —
            # a stray +1 would un-truncate a rejected KV row.
            self.cache["lengths"] = \
                self.cache["lengths"].at[idx].set(saved_lengths[idx])
        self.key, k = jax.random.split(self.key)
        next_toks, next_logps = sample(logits, k, self.temperature,
                                       self.top_k,
                                       want_logp=self.want_logps)
        next_toks = np.asarray(next_toks)
        if next_logps is not None:
            next_logps = np.asarray(next_logps)
        self.stats["steps"] += 1
        self.stats["occupancy"] += len(decode_slots) / self.max_slots
        for i in decode_slots:
            req = self.slot_req[i]
            tok = int(next_toks[i])
            self.stats["emitted"] += 1
            req.generated.append(tok)
            req.token_versions.append(self.weight_version)
            if next_logps is not None:
                req.token_logps.append(float(next_logps[i]))
            req.cached_tokens += 1
            self.pending_tok[i] = tok
            if tok == self.eos_id or len(req.generated) >= req.max_new:
                self.done.append(req)
                self.slot_req[i] = None
                self.block_mgr.free(req.rid)
                self._clear_slot(i)
                if self.tracer.enabled:
                    self.tracer.record_finish(self, req)

    # -- main loop ---------------------------------------------------------
    def run(self, max_steps: int = 1000) -> ServeReport:
        # chunk-only scheduler steps don't count against max_steps (it
        # bounds decode steps, the old contract), so keep a generous
        # runaway guard for capacity-stuck chunk loops
        guard = 16 * max_steps + 256
        stalled = False
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and self.stats["steps"] < max_steps and guard > 0:
            guard -= 1
            self._apply_staged_weights()
            decision = self.scheduler.step(self)
            if decision.is_empty:
                # nothing schedulable but work remains: capacity-stuck
                # (e.g. a request that can never be admitted) — surface
                # it instead of returning a partial report that looks
                # like success
                stalled = True
                break
            self.execute(decision)
        if guard <= 0 and (self.queue
                           or any(r is not None for r in self.slot_req)):
            stalled = True          # runaway guard tripped mid-work
        steps = max(self.stats["steps"], 1)
        return ServeReport(
            completed=self.done,
            steps=self.stats["steps"],
            preemptions=self.stats["preemptions"],
            wasted_tokens=self.stats["wasted_tokens"],
            emitted_tokens=self.stats["emitted"],
            mean_occupancy=self.stats["occupancy"] / steps,
            budget_tokens=self.budget_tokens,
            swap_outs=self.stats["swap_outs"],
            swap_ins=self.stats["swap_ins"],
            peak_blocks_in_use=self.stats["peak_blocks"],
            prefix_hit_blocks=self.stats["prefix_hits"],
            cow_copies=self.stats["cow_copies"],
            prefill_chunks=self.stats["prefill_chunks"],
            spec_steps=self.stats["spec_steps"],
            draft_tokens=self.stats["draft_tokens"],
            accepted_tokens=self.stats["accepted_tokens"],
            stalled=stalled,
            kv_pressure=self.kv_pressure,
            latency=(self.tracer.latency_summary()
                     if self.tracer.enabled else None),
            gauges=self.gauge_snapshot(),
        )
