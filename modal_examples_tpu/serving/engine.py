"""Continuous-batching LLM engine — the vLLM-engine replacement, TPU-first.

Implements the serving core behind the reference's north-star example
(vllm_inference.py: an OpenAI-compatible server wrapping an engine with
continuous batching, paged KV, streaming; SURVEY.md §3.2's HOT LOOP).

TPU-first architecture (vs vLLM's CUDA design):
- **static shapes everywhere**: the decode step is ONE jitted program over a
  fixed slot count; requests come and go by flipping an ``active`` mask and
  rewriting page tables — XLA never recompiles as batch composition changes.
- **prefill buckets**: prompts pad to the next bucket (128/256/.../max) so
  prefill compiles once per bucket, not per length (the retrace-thrash
  killer; SURVEY.md §7 hard part #5).
- **sampling fused into the decode program**: only the sampled token ids
  (max_slots x int32) cross the device->host boundary per step.
- **page cache donated** through the step so XLA updates KV in place.
- host side: admission (claim slot + pages), stop handling, incremental
  detokenization, per-request output queues. The scheduler favors admitting
  prefills as slots free up — the same continuous-batching policy vLLM's
  scheduler applies.
- **stall-free admission** (docs/scheduling.md): an optional per-tick
  prefill token budget slices chunked prefills across scheduler ticks and
  defers every prefill's first-token read until after the next decode
  dispatch, so a long-prompt arrival can never stall in-flight streams by
  more than ~one prefill chunk — prefill/decode interference becomes a
  scheduled property instead of an accident of arrival order.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import queue
import threading
import time
import uuid
from concurrent.futures import Future, ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from ..models import deepseek_v2, glm_dsa, granite_hybrid, lfm2, llama, smallthinker
from ..models.layers import refuse as _refuse
from ..models.moe import expert_dtype as _expert_dtype
from ..observability import incident as _incident
from ..observability import metrics as _obs
from ..observability import profiler as _profiler
from ..observability import reqtrace as _rt
from ..observability import timeseries as _ts
from ..observability import usage as _usage
from ..scheduling.admission import AdmissionController, ShedError
from ..scheduling.policy import (
    DEFAULT_CLASS,
    FairSharePolicy,
    ScheduledRequest,
    SchedulerPolicy,
    validate_class,
)
from ..faults import inject as _inject
from ..faults.inject import FaultError as _FaultError
from ..observability.canary import CANARY_TENANT as _CANARY_TENANT
from ..ops.paged_attention import (
    decode_chunk_pages,
    decode_chunk_trips,
    ragged_pages_read,
    window_decode_span,
)
from ..utils.log import get_logger
from .health import EngineWatermarks
from .kv_cache import OutOfPages, PagedKVCache
from .sampling import SamplingParams, sample
from . import spec_runtime as _spec_rt
from ..utils.tokenizer import load_tokenizer

_log = get_logger("engine")


def _tm(tick, phase: str):
    """Enter ``phase``: the scheduler thread's time from here to the next
    ``_tm`` belongs to it, as a span of the hot-path profiler and, under a
    profiler session, a ``mtpu.tick/<phase>`` event in the device trace —
    THE way scheduler code feeds the profiler (docs/observability.md).
    Returns the seconds of the span this closes. ``tick`` is None whenever
    profiling is off or the engine is idle, so that path is one branch: no
    timestamp, no allocation (the faults-gate zero-cost contract;
    tests/test_profiler.py pins this shape at the AST level, and
    tests/test_static.py pins the phase names to catalog.TICK_PHASES)."""
    if tick is not None:
        return tick.enter(phase)


def _tm_device(tick, phase: str):
    """`_tm`, additionally counting the span as DEVICE-blocked time (a
    blocking read of a device array) — the device half of the profiler's
    host-vs-device split behind ``mtpu_host_overhead_ratio``."""
    if tick is not None:
        return tick.enter(phase, device=True)


def _tm_inner(tick, phase: str):
    """`_tm` for a split made once per TOKEN: the accounting switches to
    ``phase``, the open trace annotation stays — two clock reads, nothing
    written into a trace."""
    if tick is not None:
        return tick.enter(phase, annotate=False)


@dataclasses.dataclass
class Request:
    prompt: str
    params: SamplingParams
    request_id: str = dataclasses.field(
        default_factory=lambda: f"req-{uuid.uuid4().hex[:12]}"
    )
    prompt_tokens: list[int] | None = None
    out_queue: queue.Queue = dataclasses.field(default_factory=queue.Queue)
    created: float = dataclasses.field(default_factory=time.monotonic)
    aborted: bool = False
    finish_reason: str | None = None  # set when the terminal marker arrives
    # token-level telemetry (monotonic clock): TTFT = first_token_at -
    # created; inter-token gaps feed the TPOT histogram. n_generated is the
    # request's own generated-token count (streaming usage reporting).
    # admitted_at: slot and pages claimed — created..admitted_at is the
    # request trace's queue span, admitted_at..first_token_at the
    # first-token wait histogram; request-trace spans take their ends from
    # these. (mtpu_engine_queue_wait_seconds ends a little later, at the
    # start of the tick's prefill call.)
    admitted_at: float | None = None
    first_token_at: float | None = None
    last_token_at: float | None = None
    n_generated: int = 0
    # failover state (serving/failover.py, docs/failover.md): the request
    # carries its OWN accepted-token history — the slot's ``generated``
    # list is this very object — so a decode checkpoint can be built from
    # the request alone after its replica died (the slot is recycled; the
    # request survives). ``emitted_len`` mirrors the slot's emitted-text
    # cursor for the same reason: a resumed stream continues emission from
    # exactly here, so the client never sees a duplicated or missing char.
    generated_tokens: list = dataclasses.field(default_factory=list)
    emitted_len: int = 0
    # engine-assigned when params.seed is None: sampling is derived from
    # (auto_seed, position) so outputs never depend on scheduler timing —
    # how many blocks/keys the engine happened to burn before this request.
    # Speculative mode included: temperature>0 lanes never speculate (the
    # fused round's γ=0 classic lane samples them with this very key;
    # docs/speculative.md#exactness).
    auto_seed: int | None = None
    # multimodal: preprocessed [S, S, 3] float image (models.vlm); its
    # n_image_tokens placeholder ids lead prompt_tokens
    image: object | None = None
    # prefix-cache keying sequence when it must differ from prompt_tokens:
    # multimodal requests key image positions by CONTENT-hash ids (outside
    # the vocab) so identical images share KV and different ones never do
    cache_key_tokens: list | None = None
    # scheduling (modal_examples_tpu/scheduling): priority class + tenant
    # drive the fair-share policy; deadline is ABSOLUTE in the engine's
    # clock domain (params.deadline_s resolved at submit). deadline_expired
    # marks an abort as a deadline miss so the stream finishes with
    # finish_reason="deadline" instead of "stop".
    priority: str = DEFAULT_CLASS
    tenant: str = "default"
    deadline: float | None = None
    deadline_expired: bool = False
    # prefix-cache accounting (observability/usage.py + the OpenAI usage
    # contract's prompt_tokens_details.cached_tokens): prompt tokens whose
    # KV came from already-cached pages (trie hits + tier promotions)
    # instead of being recomputed — set at page claim
    cached_prompt_tokens: int = 0
    # distributed request tracing (observability/reqtrace.py): the
    # RequestTraceContext minted at the entry point, or None when tracing
    # is disabled/sampled out — every trace touch point is None-safe
    trace: object | None = None


@dataclasses.dataclass
class _Slot:
    request: Request | None = None
    pages: list[int] = dataclasses.field(default_factory=list)
    trie_pages: list[int] = dataclasses.field(default_factory=list)  # release()
    private_pages: list[int] = dataclasses.field(default_factory=list)  # free()
    position: int = 0  # position of the NEXT token to decode
    last_token: int = 0
    fresh: bool = False  # just prefilled: first token rides the override lane
    generated: list[int] = dataclasses.field(default_factory=list)
    emitted_text_len: int = 0
    ngram: "_NgramIndex | None" = None  # prompt-lookup spec mode only
    #: pin this tenancy's speculation depth to 0 (draft mode only): set for
    #: failover-resumed/adopted installs whose draft cache has a
    #: generated-prefix KV hole — proposing against it would collapse
    #: acceptance. The lane rides the fused round's classic γ=0 path, so
    #: the stream stays token-identical either way (docs/speculative.md).
    spec_hold: bool = False
    #: resumable chunked-prefill state (stall-free admission): set while the
    #: slot's prompt KV is still being filled chunk-by-chunk across ticks
    prefill: "_PendingPrefill | None" = None
    #: prefill dispatched, first sampled token not yet harvested (it sits on
    #: the engine's pending-harvest queue as a device array)
    pending_first: bool = False
    #: monotonically increasing per-install id: in-flight block/harvest
    #: snapshots pin (request, tenancy), not request identity alone — a
    #: failover-resumed request is the SAME object re-admitted, and a stale
    #: block from its previous tenancy must not feed the new one
    tenancy: int = 0
    #: engine-clock timestamp of this tenancy's install — the usage meter
    #: charges the occupancy interval (device-seconds, KV page-seconds) to
    #: the tenant when the slot's pages release (observability/usage.py)
    claimed_at: float = 0.0

    @property
    def free(self) -> bool:
        return self.request is None

    @property
    def decodable(self) -> bool:
        """Admitted AND holding a first token to feed decode: slots whose
        prefill is mid-flight (sliced chunks pending, or first token not
        yet harvested) are excluded from decode dispatch."""
        return (
            self.request is not None
            and self.prefill is None
            and not self.pending_first
        )


@dataclasses.dataclass
class _PendingPrefill:
    """Per-slot resumable chunked-prefill state (stall-free admission):
    ``_admit`` advances at most a budget's worth of chunks per tick, so a
    decode dispatch always lands between chunks and the inter-token stall
    other streams see is bounded by ONE chunk, not the whole prompt."""

    req: Request
    table: object  # np page-table row shared with self._page_tables
    offset: int = 0  # token offset of the NEXT chunk to dispatch
    ticks: int = 0  # scheduler ticks that dispatched at least one chunk
    suspensions: int = 0  # times the budget paused this prefill mid-prompt
    first_token: object | None = None  # last dispatched chunk's sampled token (device)


class _NgramIndex:
    """Incremental per-slot n-gram index for prompt-lookup speculation.

    Replaces the per-tick O(window x n) rescan of each slot's full history:
    the index is built ONCE per request from the prompt (O(prompt), off the
    decode hot path) and updated in O(1) per accepted token, so a proposal
    tick costs O(gamma) per slot. Semantics match the rescan exactly: the
    proposal is the continuation of the MOST RECENT occurrence of the
    trailing n-gram strictly before the tail itself, with the match start
    confined to the last ``lookback`` tokens (vLLM's prompt_lookup_max
    analog).
    """

    __slots__ = ("n", "lookback", "hist", "occ")

    def __init__(self, n: int, prompt: list[int], lookback: int):
        self.n = n
        self.lookback = lookback
        self.hist: list[int] = []
        #: n-gram tuple -> ascending start positions of its occurrences
        self.occ: dict[tuple, list[int]] = {}
        for tok in prompt:
            self.push(tok)

    def push(self, token: int) -> None:
        """Append one accepted token; records the n-gram it completes."""
        self.hist.append(token)
        start = len(self.hist) - self.n
        if start >= 0:
            gram = tuple(self.hist[start:])
            self.occ.setdefault(gram, []).append(start)

    def propose(self, gamma: int) -> list[int]:
        """Up to ``gamma`` continuation tokens after the most recent
        earlier occurrence of the current tail n-gram ([] = no proposal,
        which degrades that slot to one plain verify step)."""
        hist, n = self.hist, self.n
        if len(hist) <= n:
            return []
        tail_start = len(hist) - n
        occs = self.occ.get(tuple(hist[tail_start:]))
        if not occs:
            return []
        lo = max(0, len(hist) - self.lookback)
        # occs is ascending; the last entry is the tail itself (pushed when
        # its final token arrived), so scan backwards for the first start
        # strictly before it — and inside the lookback window
        for j in reversed(occs):
            if j < tail_start:
                if j < lo:
                    return []  # every earlier occurrence is older still
                return hist[j + n : j + n + gamma]
        return []


@dataclasses.dataclass
class EngineStats:
    prompt_tokens: int = 0
    generated_tokens: int = 0
    steps: int = 0
    spec_proposed: int = 0  # draft tokens proposed (speculative mode)
    spec_accepted: int = 0  # draft tokens accepted by the target
    started: float = dataclasses.field(default_factory=time.monotonic)

    def tokens_per_second(self) -> float:
        dt = time.monotonic() - self.started
        return self.generated_tokens / dt if dt > 0 else 0.0

    def acceptance_rate(self) -> float:
        return self.spec_accepted / self.spec_proposed if self.spec_proposed else 0.0


def _unstable_tail(text: str) -> bool:
    """True when the last char may still change as more tokens arrive: the
    replacement char (HF tokenizers mid-codepoint) or a surrogate-escaped
    byte (ByteTokenizer mid-codepoint) — either way, emitting it now would
    stream a char that the next token's re-decode replaces."""
    if not text:
        return False
    c = ord(text[-1])
    return c == 0xFFFD or 0xDC80 <= c <= 0xDCFF


def _stop_safe_len(text: str, stop: tuple[str, ...]) -> int:
    """Longest prefix of ``text`` that cannot be the start of a pending stop
    match: anything past it must be withheld until the stop either completes
    (then truncated) or can no longer match (then flushed)."""
    safe = len(text)
    for stop_s in stop:
        lo = max(0, len(text) - len(stop_s) + 1)
        for start in range(lo, len(text)):
            if stop_s.startswith(text[start:]):
                safe = min(safe, start)
                break
    return safe


class _Finish:
    """Terminal stream marker carrying the OpenAI finish_reason."""

    __slots__ = ("reason",)

    def __init__(self, reason: str = "stop"):
        self.reason = reason


_FINISH = _Finish("stop")

#: how long the engine has to have been without a request before helper
#: threads start building queued chunk programs: the last request's response
#: is still on its way out then, and a lowering holds the interpreter in
#: long stretches (see ``LLMEngine._start_chunk_builds``)
_CHUNK_BUILD_IDLE_S = 0.1

#: prefix lengths a chunk program that takes its offset as an argument is
#: built for, at most (``LLMEngine._chunk_key``)
_PREFIX_BUCKETS = 3


def _req_seed(req: "Request") -> int:
    """The seed sample() uses for this request's rows: the user's, else the
    engine-assigned auto_seed (-1 only if neither exists, e.g. warmup)."""
    if req.params.seed is not None:
        return req.params.seed
    return req.auto_seed if req.auto_seed is not None else -1


def _program_outputs(out, stateful: bool):
    """``(logits, k_pages, v_pages, state, counts)`` of what one of a model's
    programs returned. The order is the seam's (docs/mla.md): logits and the
    two paged leaves; then the per-slot state, from a program that was handed
    ``state=``, and only from one; then whatever it counts (a list, empty for
    most). A program with neither returns the three, as it always has."""
    logits, k_pages, v_pages, *rest = out
    state = rest.pop(0) if stateful else ()
    return logits, k_pages, v_pages, state, rest


def _shard_params(params, cfg, mesh):
    """Place a llama param tree with its Megatron partition specs — one
    implementation for target and draft so the paths can't drift.

    Quantized trees shard too (vLLM serves quantized TP the same way):
    the int payload takes the weight's spec; the per-output-channel scale
    keeps the OUTPUT dim's sharding but never the contraction dim's (its
    contraction axis has size 1). layers.mm multiplies the scale after the
    dot, so row-parallel partial sums are all-reduced before rescaling —
    the math is exact under auto-partitioning.
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..models.quantize import QuantizedWeight

    specs = cfg.model.partition_specs(cfg)

    def place(p, s):
        if isinstance(p, QuantizedWeight):
            scale_spec = (
                P(*(tuple(s[:-2]) + (None, s[-1]))) if len(s) >= 2 else s
            )
            return QuantizedWeight(
                q=jax.device_put(p.q, NamedSharding(mesh, s)),
                scale=jax.device_put(p.scale, NamedSharding(mesh, scale_spec)),
            )
        return jax.device_put(p, NamedSharding(mesh, s))

    return jax.tree.map(
        place,
        params,
        specs,
        is_leaf=lambda x: isinstance(x, (P, QuantizedWeight)),
    )


#: the MODEL_NAME surface (vllm_inference.py:54-58) — shared by build_engine
#: and the speculative draft resolver so the two can never drift
MODEL_PRESETS = {
    "llama2-7b": llama.LlamaConfig.llama2_7b,
    "llama3-8b": llama.LlamaConfig.llama3_8b,
    "llama3.1-8b": llama.LlamaConfig.llama31_8b,
    "llama3.2-1b": llama.LlamaConfig.llama32_1b,
    "mistral-7b": llama.LlamaConfig.mistral_7b,
    "mixtral-8x7b": llama.LlamaConfig.mixtral_8x7b,
    "tiny": llama.LlamaConfig.tiny,
    "tiny-moe": llama.LlamaConfig.tiny_moe,
    "tiny-deepseek-v2": deepseek_v2.DeepseekV2Config.tiny,
    "tiny-granite-hybrid": granite_hybrid.GraniteHybridConfig.tiny,
    "tiny-granite-moe": granite_hybrid.GraniteHybridConfig.tiny_moe,
    "tiny-glm-dsa": glm_dsa.GlmDsaConfig.tiny,
    "tiny-lfm2": lfm2.Lfm2Config.tiny,
    "tiny-smallthinker": smallthinker.SmallThinkerConfig.tiny,
}


class LLMEngine:
    #: every scheduler-loop traceback from ANY engine in this process,
    #: recorded eagerly (survives engine GC) — the test suite's session-end
    #: sentinel asserts this stays empty, so a swallowed scheduler
    #: exception anywhere is a loud failure. Capped at 50.
    _error_reports: list = []
    #: whether the chunk programs take the chunk's offset as an argument (a
    #: configuration's ``chunk_offset_runtime``; ``__init__`` reads it)
    _runtime_offset = False

    @_profiler.boot_mark("engine_init")  # a container's boot names it
    def __init__(
        self,
        cfg,  # a model's configuration object: llama.LlamaConfig, deepseek_v2.DeepseekV2Config
        params=None,
        *,
        model_dir: str | None = None,
        max_slots: int = 16,
        page_size: int = 16,
        max_model_len: int = 1024,
        n_pages: int | None = None,
        # the page budget of a model's sliding-window page group
        # (``cfg.window_group``; docs/kv_cache.md): unset, a ring of pages
        # for every slot. A model with no such group takes none
        n_window_pages: int | None = None,
        prefill_buckets: tuple[int, ...] = (128, 256, 512, 1024, 2048),
        prefill_batch: int = 4,  # the one compiled prefill batch shape
        enable_prefix_cache: bool = True,
        quantization: str | None = None,  # "int8": weight-only quant serving
        seed: int = 0,
        # page-cache dtype: "int8" = quantized KV (half the decode HBM
        # traffic + residency; tolerance-based accuracy, docs/kv_cache.md),
        # a jnp dtype, or None -> MTPU_KV_DTYPE env -> bfloat16
        kv_dtype=None,
        speculative: tuple | None = None,  # (draft preset|LlamaConfig, gamma)
        draft_params=None,
        draft_model_dir: str | None = None,
        # adaptive speculation depth (docs/speculative.md#gamma-schedule):
        # the per-request EWMA/pressure controller that shrinks γ toward 0
        # when acceptance drops or the batch fills. None resolves
        # MTPU_SPEC_ADAPTIVE once (the knob rule); True/False override.
        # Runtime-mutable (self.spec_adaptive, like self.spec_depth), so
        # benches A/B fixed-vs-adaptive on a live engine.
        spec_adaptive: bool | None = None,
        decode_block: int = 8,  # decode steps rolled into one dispatch
        # stall-free admission (docs/scheduling.md): max prompt tokens the
        # scheduler may convert into prefill work per tick. None resolves
        # through MTPU_PREFILL_BUDGET (empty env = unlimited); an explicit
        # 0 forces UNLIMITED, env ignored — the classic admit-everything
        # behavior, and what bench children pass. With a budget, chunked
        # prefills slice across ticks and short-prompt admissions stop once
        # the budget is spent, so a decode dispatch lands between chunks
        # and in-flight streams never stall behind a whole long prompt.
        # Disagg prefill-role replicas run unbudgeted by construction:
        # prefill_sync never takes the budgeted _admit path, and
        # EngineReplica(role="prefill") zeroes the budget explicitly.
        max_prefill_tokens_per_tick: int | None = None,
        mesh=None,  # jax Mesh with a "tensor" axis: tensor-parallel serving
        paged_impl: str | None = None,  # decode attention; None: env, else the plan's choice
        scatter_impl: str | None = None,  # KV scatter; None: env/default
        vision: tuple | None = None,  # (models.vlm.VLMConfig, vision_params)
        policy: SchedulerPolicy | None = None,  # waiting-set ordering
        admission: AdmissionController | None = None,  # shed/deadline gate
        clock=None,  # injectable monotonic clock (fake-clock scheduling tests)
        # hot-path profiler (observability/profiler.py): None resolves
        # MTPU_PROFILE once (the MTPU_KV_DTYPE rule: unset = on, 0 = off);
        # True/False override. Off = self.profiler stays None and the
        # scheduler tick takes ZERO timestamps of its own.
        profile=None,
        # tiered prefix cache (docs/disagg.md): True for env-default sizing,
        # or a dict of TieredPrefixCache kwargs (host_bytes=, volume=);
        # evicted prefix pages spill HBM -> host RAM -> Volume and promote
        # back on the next shared-prefix prompt
        tiered_prefix=None,
        # request tracing: where THIS replica's spans land (default: the
        # process-wide store). A per-replica store still stitches — the
        # trace id is the request id, and reqtrace.read_trace merges
        trace_store=None,
    ):
        import os as _os

        # resolved ONCE here and passed explicitly into every jitted decode:
        # the env vars are not part of any jit cache key (ADVICE r3). Left
        # unset (None) the model's paged_impl_plan picks from the backend and
        # the shapes; "xla" / "pallas" force the loop / the ragged kernel
        self.paged_impl = paged_impl or _os.environ.get("MTPU_PAGED_IMPL") or None
        _known_impls = ("xla", "pallas")
        if self.paged_impl is not None and self.paged_impl not in _known_impls:
            raise ValueError(
                f"unknown paged_impl {self.paged_impl!r}; known: {_known_impls}"
            )
        self.scatter_impl = scatter_impl or _os.environ.get(
            "MTPU_SCATTER_IMPL", "xla"
        )
        if self.scatter_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown scatter_impl {self.scatter_impl!r} "
                "(arg or MTPU_SCATTER_IMPL); known: xla, pallas"
            )
        # per-tick prefill token budget, same resolve-once rule: explicit
        # arg beats MTPU_PREFILL_BUDGET beats unlimited (0). Mutable at
        # runtime (an int read once per _admit) so benches can A/B it.
        if max_prefill_tokens_per_tick is None:
            _raw_budget = _os.environ.get("MTPU_PREFILL_BUDGET", "")
            max_prefill_tokens_per_tick = int(_raw_budget) if _raw_budget else 0
        self.prefill_budget = max(0, int(max_prefill_tokens_per_tick))
        # cache dtype, same resolve-once rule as the impls: explicit arg
        # beats MTPU_KV_DTYPE beats the bf16 default ("int8" = quantized
        # pages + scale arrays, the 4-leaf cache)
        from ..ops.kv_quant import resolve_kv_dtype

        if kv_dtype is None:
            kv_dtype = _os.environ.get("MTPU_KV_DTYPE") or jnp.bfloat16
        kv_dtype = resolve_kv_dtype(kv_dtype)
        self.kv_dtype = "int8" if kv_dtype == "int8" else str(kv_dtype)
        self.cfg = cfg
        # the model seam (docs/mla.md): the configuration object names the
        # module that holds its programs and declares its cache leaves;
        # what a model's programs do not implement yet is refused here, by
        # name, never silently
        self._model = cfg.model
        # what the model counts on the device in its decode steps and hands
        # back beside the logits, [2] int32 each, in decode_step's order
        self._block_counts = tuple(
            kind for kind in ("routed_pairs", "expert_tile_rows")
            if getattr(cfg, f"counts_{kind}", False)
        )
        # a model whose chunk program takes the chunk's offset as an argument
        # (one program a prefix bucket and width, not one an offset)
        self._runtime_offset = bool(getattr(cfg, "chunk_offset_runtime", False))
        for asked, feature in (
            (self.kv_dtype == "int8", "int8 KV cache"),
            (speculative is not None, "speculative decoding"),
            (mesh is not None, "tensor parallelism"),
            (vision is not None, "vision"),
            (bool(tiered_prefix), "disaggregated transfer"),
            (bool(enable_prefix_cache), "prefix caching"),
            (
                self.paged_impl == "pallas" or self.scatter_impl != "xla",
                "a Pallas paged_impl or scatter_impl",
            ),
            (self.scatter_impl != "xla", "a Pallas scatter_impl"),
        ):
            if asked:
                _refuse(cfg, feature)
        self.tokenizer = load_tokenizer(model_dir)
        from ..models.quantize import SUPPORTED as _QUANT_MODES

        if quantization not in _QUANT_MODES:
            raise ValueError(
                f"unknown quantization {quantization!r}; "
                f"supported: {_QUANT_MODES}"
            )
        if params is None:
            if model_dir is not None:
                # checkpoint loads quantize on the HOST (the bf16 tensors
                # never reach the device: ~7 GB HBM for a 7B int8 model,
                # ~3.5 GB int4)
                params = self._model.load_hf_weights(
                    model_dir, cfg, quantization=quantization
                )
            elif quantization is not None:
                # init+quantize fused into ONE program so the bf16 tree is
                # an XLA-internal temporary, not a 13.5 GB resident peak
                from ..models.quantize import bits_of, init_quantized_llama

                params = init_quantized_llama(
                    jax.random.PRNGKey(seed), cfg, bits=bits_of(quantization)
                )
            else:
                params = self._model.init_params(jax.random.PRNGKey(seed), cfg)
        elif quantization is not None:
            from ..models.quantize import bits_of, quantize_llama

            params = quantize_llama(
                params, cfg.quant_targets, bits=bits_of(quantization)
            )

        # tensor parallelism is ONE ENGINE FLAG, not a separate code path
        # (matching vllm_inference.py:180's --tensor-parallel-size): weights
        # get the Megatron partition specs, the paged KV cache shards by kv
        # head, and the same jitted prefill/decode/spec programs run under
        # auto-partitioning — XLA inserts the ICI all-reduces. The Pallas
        # fast paths (flash prefill, ragged decode, scatter) keep running:
        # each kernel is dispatched through ops.sharded's shard_map wrappers
        # over the kv-head axis, so every device runs the unmodified Mosaic
        # kernel on its local head shard (the old mesh×pallas ValueError is
        # gone — round 7, ROADMAP open item #2).
        from ..ops import mesh_tp_degree

        self.mesh = mesh
        self.tp = mesh_tp_degree(mesh)
        self._attn_impl = "flash"
        if mesh is not None:
            if self.tp > 1 and (
                cfg.n_kv_heads % self.tp or cfg.n_heads % self.tp
            ):
                # the KV cache itself shards on the kv-head axis
                # (_shard_cache): a non-divisible head count cannot even be
                # placed, so fail with the real constraint up front
                raise ValueError(
                    f"n_kv_heads={cfg.n_kv_heads} / n_heads={cfg.n_heads} "
                    f"must be divisible by the tensor axis size {self.tp} "
                    "for kv-head-sharded TP serving"
                )
            params = _shard_params(params, cfg, mesh)
        self.params = params
        self.max_slots = max_slots
        self.max_model_len = max_model_len
        self.pages_per_slot = (max_model_len + page_size - 1) // page_size
        if n_pages is None:
            n_pages = 1 + max_slots * self.pages_per_slot
        # the paged leaves cover the layers that keep K/V per token (all of
        # them unless the model says otherwise); a model with per-sequence
        # state declares per-slot leaves beside them (docs/recurrent_state.md)
        with _profiler.boot_mark("kv_alloc"):
            self.cache = PagedKVCache.create(
                n_layers=getattr(cfg, "n_cache_layers", cfg.n_layers),
                leaf_shapes=cfg.cache_leaf_shapes,
                leaf_layers=getattr(cfg, "cache_leaf_layers", None),
                n_pages=n_pages,
                page_size=page_size,
                kv_dtype=kv_dtype,
                state_leaves=getattr(cfg, "state_leaves", ()),
                max_slots=max_slots,
                window_group=getattr(cfg, "window_group", None),
                n_window_pages=n_window_pages,
            )
        if n_window_pages is not None and self.cache.window is None:
            raise ValueError(
                f"n_window_pages: {type(cfg).__name__} declares no window group"
            )
        _obs.set_state_bytes(self.cache.state_bytes())
        if mesh is not None:
            self._shard_cache(self.cache)
        # what will ACTUALLY run for these shapes on this backend — a
        # requested pallas impl can be shape-downgraded (sub-128 head_dim /
        # unaligned page_size; GQA runs the "grouped" ragged variant since
        # round 5), and the kv dtype changes the flat-variant legality —
        # record it so benches/metrics report the real path instead of the
        # requested one (ADVICE r4)
        expert_dtype = _expert_dtype(self.params)  # None: no routed layer
        self.impl_plan = {
            **self._model.paged_impl_plan(
                cfg, page_size, self.paged_impl, self.scatter_impl,
                kv_dtype=self.kv_dtype, mesh=mesh,
                **({} if expert_dtype is None else {"expert_dtype": expert_dtype}),
            ),
            "allocator": self.cache.allocator_impl,
        }
        _obs.set_decode_impl(self.impl_plan)
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= max_model_len
        ) or (max_model_len,)
        self.prefill_batch = max(1, min(prefill_batch, max_slots))
        from .prefix_cache import PrefixCache

        self.prefix_cache = (
            PrefixCache(self.cache.allocator, page_size)
            if enable_prefix_cache
            else None
        )
        # tiered prefix cache: wraps the trie with host-RAM/Volume spill
        # tiers riding the disagg page-(de)serialization machinery
        self.tiered = None
        if tiered_prefix and self.prefix_cache is not None:
            from .disagg.tiered_cache import TieredPrefixCache

            kw = dict(tiered_prefix) if isinstance(tiered_prefix, dict) else {}
            self.tiered = TieredPrefixCache(
                self.cache, self.prefix_cache, **kw
            )
            self.prefix_cache.spill = self.tiered.spill_pages

        # multimodal serving (models.vlm; the reference's sglang_vlm.py
        # workload): image requests prefill with the vision tower's
        # projected patch embeddings as the first n_image_tokens positions.
        self.vision_cfg = None
        self.vision_params = None
        if vision is not None:
            self.vision_cfg, self.vision_params = vision
            if self.vision_cfg.llm_dim != cfg.dim:
                raise ValueError(
                    f"vision projector dim {self.vision_cfg.llm_dim} != "
                    f"model dim {cfg.dim}"
                )
            if self.vision_cfg.n_image_tokens >= self.prefill_buckets[-1]:
                raise ValueError(
                    f"n_image_tokens {self.vision_cfg.n_image_tokens} must "
                    f"be < the largest prefill bucket "
                    f"{self.prefill_buckets[-1]} (multimodal prompts do not "
                    "chunk)"
                )
            if mesh is not None:
                # TP × vision (sglang_vlm.py serves VLMs with --tp-size):
                # image tokens are ordinary KV entries, so decode needs
                # nothing; the ViT tower is einsum-only (partitionable) and
                # small, so its weights replicate over the mesh and every
                # chip encodes the (shared) image — the LLM prefill behind
                # it runs sharded exactly like the text path.
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                rep = NamedSharding(mesh, P())
                self.vision_params = jax.tree.map(
                    lambda x: jax.device_put(x, rep), self.vision_params
                )
            if speculative is not None:
                raise ValueError(
                    "vision= with speculative= is not supported: the draft "
                    "model's cache would miss the image-token KV"
                )
        self._prefill_mm_jits: dict[object, object] = {}

        self.slots = [_Slot() for _ in range(max_slots)]
        # per-install tenancy ids (see _Slot.tenancy); bumped only on the
        # scheduler thread, where every install happens
        self._tenancy_seq = 0
        # scheduling: the waiting set is a pluggable SchedulerPolicy (PR 4;
        # replaces the single unbounded FIFO queue) — priority classes +
        # tenant fair share by default — gated by cost-aware admission
        # control (bounded per-class queues, KV-pressure shedding,
        # deadlines). A plain FIFO is one `policy=FIFOPolicy()` away.
        self._clock = clock or time.monotonic
        # progress watermarks (serving/health.py, docs/health.md): the
        # scheduler thread notes ticks/dispatches/accepts for free; the
        # fleet watchdog classifies gray failures from their ages. Shares
        # the engine's injectable clock so fake-clock tests see real ages.
        self.watermarks = EngineWatermarks(clock=self._clock)
        # hot-path profiler (docs/observability.md#hot-path-profiling):
        # resolved ONCE — explicit arg beats MTPU_PROFILE, unset is on. The
        # lazy name callable picks up the fleet's trace_name assignment;
        # the annotation factory puts the profiler's spans into the device
        # trace of whoever has a profiler session open, and JAX's
        # monitoring events split a build into trace, lowering, XLA's
        # compile and a cache read (observability/ itself never imports
        # JAX).
        self.profiler = (
            _profiler.HotPathProfiler(
                clock=self._clock, name=lambda: self.trace_name,
                annotate=jax.profiler.TraceAnnotation,
                monitoring=jax.monitoring,
            )
            if _profiler.profiling_enabled(profile)
            else None
        )
        self._tick = None  # the in-flight TickProfile (None = off/idle)
        # flight recorder (docs/observability.md#metrics-history): MTPU_TSDB=1
        # starts the process-wide tsdb sampler ONCE (idempotent; its whole
        # cost is one locked registry pass per interval off the hot path —
        # the same zero-cost-when-off rule as the profiler above), and the
        # incident collector learns about this engine so a capture can
        # snapshot its watermarks / impl plan / open requests
        _ts.ensure_sampler()
        _incident.register_engine(self)
        self.policy: SchedulerPolicy = policy or FairSharePolicy(
            clock=self._clock
        )
        self.admission = admission or AdmissionController(clock=self._clock)
        # replica identity on request-trace spans ("engine" until an
        # EngineReplica adopts this engine under its fleet name)
        self.trace_name = "engine"
        self._trace_store = (
            trace_store if trace_store is not None else _rt.default_store
        )
        if trace_store is not None:
            _rt.register_store(self._trace_store)
        self.stats = EngineStats()
        # hardware-utilization accounting (observability/usage.py,
        # docs/observability.md#roofline-and-usage-accounting): the
        # analytic work model is frozen HERE — parameter count from the
        # config, true weight HBM bytes from the loaded tree, dtype-aware
        # KV bytes/token from the cache's own accounting — and the meter
        # shares the engine's injectable clock, so fake-clock runs meter
        # bit-reproducible MFU/MBU. The per-token cost is a few integer
        # adds; the device seconds under MFU/MBU are the profiler's harvest
        # spans (none under MTPU_PROFILE=0).
        from ..models.quantize import param_bytes

        self.usage = _usage.EngineUsage(
            _usage.WorkModel.from_engine(
                cfg, cache=self.cache,
                weight_bytes=param_bytes(self.params),
            ),
            clock=self._clock,
            name=lambda: self.trace_name,
            chips=int(self.impl_plan.get("tp", 1) or 1),
        )
        # admission sheds are charged to the shedding tenant/class
        self.admission.usage = self.usage
        self.error_log: list[str] = []  # recent scheduler tracebacks
        self.error_count = 0  # monotonic (error_log is capped at 20)
        # MTPU_ENGINE_STRICT=1 (the test suite's default, conftest.py): a
        # scheduler-loop exception STOPS the engine and releases callers
        # with finish_reason="error" instead of being swallowed — closing
        # the round-2 "intermittent flake consistent with a swallowed
        # scheduler exception" loop (NOTES.md). Production default keeps
        # the loop alive (availability) but still records + counts.
        self.strict = _os.environ.get("MTPU_ENGINE_STRICT", "") not in ("", "0")
        self._stopped_on_error = False
        self._metrics_wall = 0.0  # last gauge refresh (throttled in step())
        # last stats totals flushed into the prometheus token counters
        # (counters take deltas; EngineStats holds the running totals)
        self._counter_flush = {"prompt": 0, "generated": 0, "steps": 0}
        self._key = jax.random.PRNGKey(seed)
        # the sampler's arguments, less the key, of a chunk call whose token
        # nobody reads (_dispatch_prefill_chunk): greedy and unfiltered, so
        # sample()'s sorts stay off at run time. Put on the device once, from
        # numpy: no program is built for them
        self._unread_sampler_args = jax.device_put((
            np.zeros((1,), np.float32), np.ones((1,), np.float32),
            np.zeros((1,), np.int32), np.full((1,), -1, np.int32),
            np.zeros((1,), np.int32),
        ))
        self._seed_base = int(seed)
        self._submit_seq = 0  # feeds auto_seed: deterministic per submission
        self._lock = threading.Lock()
        # serializes slot-free prefill_sync callers (disagg prefill role):
        # the prefill jits donate the cache arrays, so two server threads
        # must never run them concurrently. The pending count is the
        # prefill replica's load signal (EngineReplica.outstanding).
        self._prefill_sync_lock = threading.Lock()
        self._prefill_sync_pending = 0
        self._running = False
        self._thread: threading.Thread | None = None

        # host mirrors of device slot state
        self._page_tables = np.zeros((max_slots, self.pages_per_slot), np.int32)
        self._positions = np.zeros((max_slots,), np.int32)
        self._active = np.zeros((max_slots,), bool)
        self._tokens = np.zeros((max_slots,), np.int32)
        self._temps = np.ones((max_slots,), np.float32)
        self._top_ps = np.ones((max_slots,), np.float32)
        self._top_ks = np.zeros((max_slots,), np.int32)
        self._seeds = np.full((max_slots,), -1, np.int32)

        # pipelined multi-step decode (the dispatch-latency killer: one
        # blocking read per `decode_block` tokens, and the next block is
        # already queued on-device while the host reads the previous one;
        # vLLM's async scheduling solves the same problem)
        self.decode_block = max(1, int(decode_block))
        self._device_tokens = None  # [max_slots] device int32: last sampled
        self._opt_positions = np.zeros((max_slots,), np.int32)  # dispatch-side
        self._override = np.zeros((max_slots,), np.int32)
        self._override_mask = np.zeros((max_slots,), bool)
        import collections

        # (tokens [K, B] device, valid, snapshot, spec_meta, dispatch number)
        self._inflight = collections.deque()
        # stall-free admission state: finished prefills whose sampled first
        # token is still a device array — the blocking read is deferred
        # until AFTER the decode block for already-running slots has been
        # dispatched (entries: (tokens, rows, meta); rows pin request
        # identity like _inflight's snapshots)
        self._pending_harvest = collections.deque()
        # scheduler-thread control queue (serving/failover.py): operations
        # that must run next to the decode jits — live-migration checkpoint
        # extraction releases slot pages the in-flight blocks still
        # reference — enqueue (fn, result_queue) here and step() services
        # them at the top of each tick (_run_on_scheduler)
        self._ctrl = collections.deque()
        # last decode-block dispatch (engine clock); None while no decodable
        # slot exists — feeds mtpu_decode_stall_seconds
        self._last_dispatch_at: float | None = None

        self._block_jit = jax.jit(
            self._decode_block_fn, donate_argnums=(1, 2), donate_argnames=("state",)
        )
        self._detok = None  # lazy DetokWorker (a speculative round's first token)
        self._prefill_jits: dict[int, object] = {}
        self._chunk_jits: dict[int, object] = {}  # keyed by chunk q_offset
        # the compiled chunk programs, keyed (q_offset, width, is_draft): a
        # whole offset is queued at its first chunk (_build_chunk_programs),
        # a program is a future while a helper thread of _chunk_builder is
        # at it (_start_chunk_builds)
        self._chunk_programs: dict[tuple[int, int, bool], object] = {}
        self._chunk_queued: dict[tuple[int, int, bool], object] = {}
        self._chunk_builder = None  # ThreadPoolExecutor, at the first start
        self._idle_since = None  # clock of the first of the idle ticks in a row
        # one thread traces at a time: a helper lowering a chunk program, or
        # the scheduler's tick (which takes it again to build its own)
        self._chunk_lowering = threading.RLock()

        # speculative decoding (the engine-side flag the reference exposes:
        # vllm_inference.py:196-205), as a first-class scheduler decode
        # mode (docs/speculative.md): one fused round program per dispatch
        # — draft-propose(γ) on masked_scan + one ragged target verify +
        # accept in-graph (serving/spec_runtime/runtime.py) — emitting the
        # harvest plane (tokens + validity mask), so spec rounds and decode
        # blocks share ONE harvest site. The draft keeps its own paged KV cache
        # ADDRESSED BY THE SAME page ids/tables as the target's, so
        # allocation, prefix sharing, and slot recycling are managed once.
        self.spec_gamma = 0
        self.spec_mode: str | None = None  # "draft" | "ngram"
        self.draft_cfg = None
        if speculative is not None:
            draft, gamma = speculative
            self.spec_gamma = int(gamma)
            if self.spec_gamma < 1:
                raise ValueError("speculative gamma must be >= 1")
            if draft == "ngram":
                # prompt-lookup decoding (vLLM's --speculative-model
                # [ngram] analog): proposals come from matching the
                # sequence's trailing n-gram against its OWN history — no
                # second model, no draft HBM, no draft cache. The target
                # verifies the proposed continuation in one pass exactly
                # like draft-model mode.
                if draft_model_dir is not None or draft_params is not None:
                    raise ValueError(
                        "draft_model_dir/draft_params are incompatible with "
                        "speculative=('ngram', ...): prompt lookup uses no "
                        "draft model — drop them or pick a draft preset"
                    )
                self.spec_mode = "ngram"
                self.ngram_n = 2  # trailing-bigram lookup (prompt-lookup)
                self._ngram_jit = jax.jit(
                    _spec_rt.build_ngram_round_fn(
                        cfg, gamma=self.spec_gamma, mesh=mesh
                    ),
                    donate_argnums=(1, 2),
                )
            else:
                if isinstance(draft, str):
                    if draft not in MODEL_PRESETS:
                        raise ValueError(
                            f"unknown draft preset {draft!r}; "
                            f"known: {sorted(MODEL_PRESETS)} (or 'ngram')"
                        )
                    draft = MODEL_PRESETS[draft]()
                if draft_model_dir is not None:
                    # the checkout's own config describes the draft weights
                    # (the preset name is then just a default for when no
                    # dir is given)
                    draft = llama.LlamaConfig.from_hf_config(
                        f"{draft_model_dir}/config.json"
                    )
                self.spec_mode = "draft"
                self.draft_cfg = draft
                if draft.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab_size {draft.vocab_size} != target "
                        f"{cfg.vocab_size}: speculative accept/reject "
                        "compares token distributions and requires a shared "
                        "vocabulary"
                    )
                if draft_params is None:
                    if draft_model_dir is not None:
                        draft_params = llama.load_hf_weights(
                            model_dir=draft_model_dir, cfg=draft
                        )
                    else:
                        draft_params = llama.init_params(
                            jax.random.PRNGKey(seed + 1), draft
                        )
                if mesh is not None:
                    draft_params = _shard_params(draft_params, draft, mesh)
                self.draft_params = draft_params
                self.draft_cache = PagedKVCache.create(
                    n_layers=draft.n_layers,
                    n_kv_heads=draft.n_kv_heads,
                    head_dim=draft.head_dim,
                    n_pages=n_pages,
                    page_size=page_size,
                    kv_dtype=kv_dtype,
                    prefer_native=False,  # page ids from the target's allocator
                )
                if mesh is not None:
                    self._shard_cache(self.draft_cache)
                self._spec_jit = jax.jit(
                    _spec_rt.build_spec_round_fn(
                        cfg,
                        draft,
                        paged_impl=self.paged_impl,
                        scatter_impl=self.scatter_impl,
                        mesh=mesh,
                        gamma=self.spec_gamma,
                    ),
                    donate_argnums=(2, 3, 4, 5),
                )
                self._draft_prefill_jits: dict[object, object] = {}
        # adaptive γ (docs/speculative.md#gamma-schedule): both knobs are
        # runtime-mutable — spec_depth caps per-round proposal budgets
        # (0 = spec fully off, every round falls through to the classic
        # block program), spec_adaptive switches the per-request controller
        # on/off — so benches A/B off/fixed/adaptive on one live engine.
        self.spec_depth = self.spec_gamma
        self.spec_adaptive = _spec_rt.resolve_spec_adaptive(spec_adaptive)
        self._spec_ctrl = (
            _spec_rt.AdaptiveGammaController(self.spec_gamma)
            if self.spec_gamma
            else None
        )
        # spec round accounting (harvest-side; feeds the SPEC_* gauges
        # through _refresh_gauges' throttle, as deltas since the last flush)
        self._spec_rounds = 0
        self._spec_round_tokens = 0
        self._spec_fallbacks = 0
        self._spec_flush = {"rounds": 0, "tokens": 0, "fallbacks": 0}
        self._spec_tpd = 0.0
        self._spec_gamma_window: list[int] = []  # dispatched per-slot γs
        self._spec_gamma_p50 = 0.0

    def _shard_cache(self, cache) -> None:
        """Shard page arrays [L, P, ps, Hkv, D] by kv head over ``tensor`` —
        every cache byte and its attention math stay on the chip owning the
        head; page tables/ids remain host-global. int8 caches shard the
        [L, P, ps, Hkv] f32 scale arrays WITH their pages on the same Hkv
        axis, so dequant never crosses chips. The placement rule itself
        lives in ops.sharded.shard_cache_pages (shared with the TP
        microbench)."""
        from ..ops import shard_cache_pages

        cache.k_pages, cache.v_pages = shard_cache_pages(
            self.mesh, cache.k_pages, cache.v_pages
        )

    # -- jitted programs ----------------------------------------------------

    def _profiled(self, program: str, shape_key, fn):
        """THE dispatch chokepoint (docs/observability.md): every jitted-
        program dispatch site wraps its callable here. Profiling off:
        returns ``fn`` untouched — no wrapper, no allocation (the zero-cost
        gate, AST-pinned in tests/test_profiler.py). On:
        ``HotPathProfiler.dispatch`` runs it under the
        ``mtpu.dispatch/<program>`` trace annotation, numbers it for the
        device-starvation account, and times a dispatch that BUILT its
        program into ``mtpu_compile_seconds{program}`` and the
        compiles.jsonl ledger (begin event BEFORE a first build, so a
        mid-compile crash/hang still names its program — the ≥40-slot
        ceiling diagnosis); the others count as
        ``mtpu_compiles_total{cache="hit"}``."""
        prof = self.profiler
        if prof is None:
            return fn

        def run(*args, **kwargs):
            return prof.dispatch(program, shape_key, fn, args, kwargs)

        return run

    def _harvest_begin(self) -> float | None:
        """Enter the ``harvest`` span: a blocking read of a device array
        follows. With no tick open (profiling off, or a stopped engine's
        ``prefill_sync``) returns the engine clock instead, for
        ``_harvested``: the roofline meter's device seconds do not depend
        on the profiler's switch."""
        tick = self._tick
        _tm_device(tick, "harvest")
        return self._clock() if tick is None else None

    def _harvested(self, seq: int, kind: str, t0: float | None) -> None:
        """A blocking read of dispatch number ``seq``'s output just
        returned (``t0``: what ``_harvest_begin`` gave): close the
        ``harvest`` span into ``accept``, tell the starvation account how
        far the device has come, and hand the span's seconds to the
        roofline meter as ``kind`` ("prefill" | "decode") device time."""
        tick = self._tick
        waited = _tm(tick, "accept")
        if t0 is not None:
            waited = self._clock() - t0
        prof = self.profiler
        if prof is not None:
            prof.note_harvest(seq, tick)
        if waited:
            self.usage.note_phase_seconds(kind, waited)

    def _dispatch_seq(self) -> int:
        """The number of the program dispatched last (0 unprofiled)."""
        prof = self.profiler
        return 0 if prof is None else prof.dispatched

    def _has_demand(self) -> bool:
        """A request is queued or holds a slot, or a control command
        waits: the engine is not idle."""
        return (
            bool(self._ctrl)
            or self.policy.total_depth() > 0
            or any(s.request is not None for s in self.slots)
        )

    def _decode_block_fn(
        self, params, k_pages, v_pages, prev_tokens, override, override_mask,
        positions, page_tables, active, key, temps, top_ps, top_ks, seeds,
        state=(), window_tables=None,
    ):
        """`decode_block` decode+sample steps in one program: tokens feed
        forward in-graph (lax.scan), so nothing crosses the host boundary
        between steps. ``prev_tokens`` is the previous block's device-resident
        output; freshly prefilled slots merge their host-known first token via
        (override, override_mask). ``state``: the cache's per-slot leaves
        (``()`` for a model with none: no argument of the program), row b of
        each the slot b of the batch, carried through the steps beside the
        pages. Returns (tokens [K, B], last [B], caches, state).
        """
        tok0 = jnp.where(override_mask, override, prev_tokens)
        # what a model counts (its routed pairs, its expert tiles' rows) it
        # hands back beside the logits; summed over the block's steps each
        # count leaves it as two more rows of the token matrix
        # (_process_block splits them off), so the harvest's one read of
        # the tokens brings them
        counted = {"return_counts": True} if self._block_counts else {}
        windowed = {} if window_tables is None else {"window_tables": window_tables}

        def body(carry, k_i):
            tok, pos, kp, vp, st = carry
            stateful = {"state": st, **windowed} if st else {}
            logits, kp, vp, st, counts = _program_outputs(
                self._model.decode_step(
                    params, tok, pos, kp, vp, page_tables, active, self.cfg,
                    impl=self.paged_impl, scatter_impl=self.scatter_impl,
                    mesh=self.mesh, **counted, **stateful,
                ),
                bool(st),
            )
            nxt = sample(
                logits, k_i, temps, top_ps, top_ks, seeds=seeds, step_ids=pos
            )
            nxt = jnp.where(active, nxt, tok)  # dead slots hold steady
            return (nxt, pos + 1, kp, vp, st), (nxt, *counts)

        (last, _, k_pages, v_pages, state), (toks, *counts) = jax.lax.scan(
            body,
            (tok0, positions, k_pages, v_pages, state),
            jax.random.split(key, self.decode_block),
        )
        for c in counts:
            rows = jnp.broadcast_to(c.sum(axis=0)[:, None], (2, toks.shape[1]))
            toks = jnp.concatenate([toks, rows.astype(toks.dtype)], axis=0)
        return toks, last, k_pages, v_pages, state

    def _state_args(self, slots: list[int] | None = None, rows: int = 0) -> dict:
        """The keyword arguments that hand a program the cache's leaves
        beside the first two (``PagedKVCache.beside``: further paged leaves,
        then per-slot ones): ``state`` and, for a prefill call of ``rows``
        rows whose first ones fill ``slots``, the rows' ``slot_ids`` (a row
        with no slot gets ``max_slots``: written nowhere). None for a model
        with no such leaves: its programs are called as they always were.
        A model with a window page group also gets ``window_tables``, that
        group's page table: every slot's row for a decode block, the rows'
        slots' for a prefill call (a row with no slot: the trash page)."""
        if not self.cache.beside:
            return {}
        window = self.cache.window
        if slots is None:
            args = {"state": self.cache.beside}
            if window is not None:
                args["window_tables"] = jnp.asarray(window.tables.copy())
            return args
        ids = np.full((rows,), self.max_slots, np.int32)
        ids[: len(slots)] = slots
        args = {"state": self.cache.beside, "slot_ids": jnp.asarray(ids)}
        if window is not None:
            tables = np.zeros((rows, window.ring), np.int32)
            tables[: len(slots)] = window.tables[slots]
            args["window_tables"] = jnp.asarray(tables)
        return args

    def _count_sparse(self, queries, phase: str) -> None:
        """A dispatch of a model with an indexer (``cfg.sparse_positions``):
        what it scores, selects and attends to, from the positions of the
        queries ``queries()`` gives. No other model's dispatch builds them."""
        count = getattr(self.cfg, "sparse_positions", None)
        if count is not None:
            _obs.record_sparse_positions(count(queries(), phase))

    def _count_decode_kv(self, positions, active, steps: int) -> None:
        """What the ``steps`` decode steps of one dispatch read of the KV
        cache, from the positions the host hands the program: step j sees
        every live slot j tokens further on. The chunked loop
        (ops.paged_decode_attention_chunked) makes as many trips as that
        step's longest context needs, each over every slot; the ragged
        kernel (ops.paged_decode_attention_ragged) DMAs each live slot's own
        live pages and nothing for a dead one (a window layer's ring from
        the first page its window reaches: the plan's ``window_attention``
        says which of the two read the second group)."""
        self._count_sparse(
            lambda: positions[active].astype(np.int64)[:, None] + np.arange(steps), "decode"
        )
        live = positions[active].astype(np.int64)
        ps, pp = self.cache.page_size, self.pages_per_slot
        read = 0
        if live.size and self.impl_plan["attention"] == "ragged":
            read = int(
                ragged_pages_read(live[:, None] + np.arange(steps), ps).sum()
            ) * ps
        elif live.size:
            trips = decode_chunk_trips(live.max() + np.arange(steps), ps, pp)
            read = (
                int(trips.sum()) * decode_chunk_pages(ps, pp) * ps
                * self.max_slots
            )
        window = self.cache.window
        _obs.record_decode_kv_positions(
            read=read,
            live=int(live.sum()) * steps + live.size * steps * (steps - 1) // 2,
            table=self.max_slots * pp * ps * steps,
            **({} if window is None else {"layers": "global"}),
        )
        if window is None:
            return
        # the window group's layers: a slot's ring from the oldest page its
        # window reaches; the kernel reads each live slot's own live pages
        # from there, the loop as far as the longest needs and over every slot
        read = held = 0
        if live.size:
            at = live[:, None] + np.arange(steps)  # [live, steps]
            _, lens, starts = window_decode_span(at.reshape(-1), window.window, ps, window.ring)
            lens = lens.reshape(at.shape)
            if self.impl_plan["window_attention"] == "ragged-ring":
                read = int(ragged_pages_read(lens, ps).sum()) * ps
            else:
                trips = decode_chunk_trips(lens.max(axis=0), ps, window.ring)
                read = (
                    int(trips.sum()) * decode_chunk_pages(ps, window.ring) * ps
                    * self.max_slots
                )
            held = int((lens - starts.reshape(at.shape)).sum())
            _obs.record_kv_window_pages_recycled(window.recycled(live, live + steps))
        _obs.record_decode_kv_positions(
            read=read, live=held, table=self.max_slots * window.ring * ps * steps,
            layers="window",
        )

    def _ensure_detok(self):
        """The lazy detokenization worker (serving/spec_runtime/detok.py). A
        dead worker is replaced — owned streams re-register from their
        ``req.emitted_len`` cursor on the next accepted token."""
        w = self._detok
        if w is None or not w.alive:
            from .spec_runtime.detok import DetokWorker

            w = DetokWorker(
                tokenizer=self.tokenizer,
                deliver=self._deliver_finish,
                safe_len=_stop_safe_len,
                unstable_tail=_unstable_tail,
                name=self.trace_name,
            )
            self._detok = w
        return w

    def _prefill_and_sample(
        self, params, k_pages, v_pages, tokens, page_tables, seq_lens, key,
        temps, top_ps, top_ks, seeds, state=(), slot_ids=None, window_tables=None,
    ):
        stateful = {"state": state, "slot_ids": slot_ids} if state else {}
        if window_tables is not None:
            stateful["window_tables"] = window_tables
        logits, k_pages, v_pages, state, _ = _program_outputs(
            self._model.prefill(
                params, tokens, k_pages, v_pages, page_tables, seq_lens, self.cfg,
                attn_impl=self._attn_impl, mesh=self.mesh, **stateful,
            ),
            bool(state),
        )
        next_tokens = sample(
            logits, key, temps, top_ps, top_ks, seeds=seeds, step_ids=seq_lens
        )
        return next_tokens, k_pages, v_pages, state

    def _prefill_jit(self, bucket: int):
        fn = self._prefill_jits.get(bucket)
        if fn is None:
            fn = jax.jit(
                self._prefill_and_sample, donate_argnums=(1, 2),
                donate_argnames=("state",),
            )
            self._prefill_jits[bucket] = fn
        return fn

    def _chunk_key(self, offset: int) -> int:
        """What the chunk programs of a chunk at ``offset`` are keyed and
        named by. The offset itself, static in the program. Or, for a model
        whose chunk program takes the offset as an argument
        (``cfg.chunk_offset_runtime``: contexts long enough that a program an
        offset is too many), the static length of the cached prefix the
        program gathers: 0 for the first chunk, else one of at most
        ``_PREFIX_BUCKETS`` buckets, each the largest of its offsets."""
        if not self._runtime_offset or not offset:
            return offset
        C = self.prefill_buckets[-1]
        offsets = range(C, self.max_model_len - 1, C)
        per = -(-len(offsets) // _PREFIX_BUCKETS)
        last = min(len(offsets), ((offset // C - 1) // per + 1) * per) - 1
        return offsets[last]

    def _chunk_jit(self, offset: int):
        """The chunked-prefill function for chunks whose ``_chunk_key`` is
        ``offset`` (static: it sizes the gather of the cached prefix). It
        builds one program a chunk width, all named
        ``jit_prefill_chunk_off<offset>`` (``..._pre<prefix bucket>`` where
        the offset is an argument)."""
        fn = self._chunk_jits.get(offset)
        if fn is None:
            attn_impl, mesh = self._attn_impl, self.mesh
            runtime = self._runtime_offset

            def prefill_chunk(
                params, toks, k_pages, v_pages, tables, lens, key, temps,
                top_ps, top_ks, seeds, step_ids, state=(), slot_ids=None,
                q_offset=None, window_tables=None, *, cfg,
            ):
                # cfg is the target's or the draft's: each names its module
                stateful = {"state": state, "slot_ids": slot_ids} if state else {}
                if window_tables is not None:
                    stateful["window_tables"] = window_tables
                at = (
                    {"q_offset": q_offset, "prefix_len": offset} if runtime
                    else {"q_offset": offset}
                )
                logits, k_pages, v_pages, state, _ = _program_outputs(
                    cfg.model.prefill_chunk(
                        params, toks, k_pages, v_pages, tables, lens, cfg=cfg,
                        **at, attn_impl=attn_impl, mesh=mesh, **stateful,
                    ),
                    bool(state),
                )
                # every chunk samples, as _prefill_and_sample does: the
                # token of a prompt's last chunk is its first token, any
                # other chunk's is dropped (one [1, V] row)
                next_tokens = sample(
                    logits, key, temps, top_ps, top_ks, seeds=seeds,
                    step_ids=step_ids,
                )
                return next_tokens, k_pages, v_pages, state

            # the compiled program's name in a device trace: one per offset
            prefill_chunk.__name__ = (
                f"prefill_chunk_pre{offset}" if runtime else f"prefill_chunk_off{offset}"
            )
            fn = jax.jit(
                prefill_chunk, static_argnames=("cfg",), donate_argnums=(2, 3),
                donate_argnames=("state",),
            )
            self._chunk_jits[offset] = fn
        return fn

    def _chunk_widths(self, offset: int) -> list[int]:
        """The widths a chunk that starts at ``offset`` can take: the largest
        bucket while more than that is left of the prompt, else the smallest
        bucket that holds what is left (``_bucket_for``). Offset 0 only ever
        sees a whole chunk (a shorter prompt is not chunked), and so does a
        model whose chunk programs take the offset as an argument."""
        buckets = self.prefill_buckets
        if not offset or self._runtime_offset:
            # a program over a prefix bucket is long to build (10 s cold on a
            # v5e, and too large for the compile cache the machine keeps):
            # one a bucket, its tail chunk padded to the whole width
            return [buckets[-1]]
        most_left = self.max_model_len - 1 - offset  # submit() admits no more
        return [b for b, below in zip(buckets, (0, *buckets)) if below < most_left]

    def _build_chunk_programs(self, offset: int, first: int | None = None) -> None:
        """Build (lower and compile, nothing run) the programs of ``first``,
        the chunk width the caller is about to dispatch at ``offset``, on its
        thread, and queue every other width of ``_chunk_widths`` for
        ``_start_chunk_builds``: a whole offset at its first chunk, the
        draft's programs beside the target's, because a tail width first met
        later would be built under traffic. Each program lands in the compile
        ledger under its dispatch's own (program, shape_key)."""
        fn = self._chunk_jit(offset)
        prof = self.profiler

        def spec(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                tree,
            )

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)

        sampler = [
            jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in (self._key, *self._unread_sampler_args)
        ]
        at = {"q_offset": i32()} if self._runtime_offset else {}
        models = [(
            False, "prefill_chunk", self._chunk_shape_key(offset), self.cfg,
            self.params, spec((self.cache.k_pages, self.cache.v_pages)),
            {**spec(self._state_args([0], 1)), **at},
        )]
        if self.spec_mode == "draft":
            models.append((
                True, "draft_prefill", f"chunk-off{offset}", self.draft_cfg,
                self.draft_params,
                spec((self.draft_cache.k_pages, self.draft_cache.v_pages)), {},
            ))

        def build(ahead, width, program, key, cfg, params, pages, state):
            def lower_and_compile():
                # lowering is the interpreter's work, which the scheduler
                # thread wants too: one helper at a time; the compiles (or
                # the compile cache's loads) run side by side
                with self._chunk_lowering:
                    lowered = fn.lower(
                        params, i32(1, width), *pages,
                        i32(1, self.pages_per_slot), i32(1), *sampler,
                        **state, cfg=cfg,
                    )
                return lowered.compile()

            if prof is None:
                return lower_and_compile()
            return prof.build(
                program, f"{key}w{width}", lower_and_compile, ahead=ahead
            )

        for width in self._chunk_widths(offset):
            for draft, *model in models:
                key = (offset, width, draft)
                if width == first:
                    self._chunk_queued.pop(key, None)
                    self._chunk_programs[key] = build(False, width, *model)
                elif key not in self._chunk_programs:
                    self._chunk_queued[key] = functools.partial(
                        build, True, width, *model
                    )

    def _start_chunk_builds_when_idle(self, worked: bool) -> None:
        """A tick's end, with chunk programs queued (they are queued by a
        request in flight, so the clock below starts after it): hand them to
        the helpers once no request has been here for
        ``_CHUNK_BUILD_IDLE_S``."""
        if worked or self._has_demand():
            self._idle_since = None
        elif self._idle_since is None:
            self._idle_since = self._clock()
        elif self._clock() - self._idle_since >= _CHUNK_BUILD_IDLE_S:
            self._idle_since = None
            self._start_chunk_builds()

    def _start_chunk_builds(self) -> None:
        """Hand the queued chunk programs to helper threads: once the engine
        has been without a request for a while
        (``_start_chunk_builds_when_idle``), or from ``warmup()``. Their
        lowering would otherwise take the interpreter from a request in
        flight, or from the threads that are still writing the last one's
        response. Nobody waits for them: until one is built,
        ``_chunk_width`` gives its chunks the next wider program that is."""
        if self._chunk_queued and self._chunk_builder is None:
            self._chunk_builder = ThreadPoolExecutor(
                thread_name_prefix="mtpu-chunk-build"
            )
        while self._chunk_queued:
            key, job = self._chunk_queued.popitem()
            self._chunk_programs[key] = self._chunk_builder.submit(job)

    def _chunk_shape_key(self, key: int) -> str:
        """The compile ledger's shape key of the chunk programs of ``key``
        (``_chunk_key``), less the width."""
        return f"pre{key}" if self._runtime_offset else f"off{key}"

    def _chunk_width(self, offset: int, left: int) -> int:
        """The width of the chunk call at ``offset`` of a prompt with ``left``
        tokens to go: the bucket that holds them (``_bucket_for``: the
        largest while more than that is left, so offsets stay its multiples),
        or, while a helper thread is still building that one, the narrowest
        wider bucket whose programs are built. Where none is, the caller
        builds its own now."""
        want = self.prefill_buckets[-1] if self._runtime_offset else self._bucket_for(left)
        drafts = (False, True) if self.spec_mode == "draft" else (False,)

        def built(key):
            program = self._chunk_programs.get(key)
            if isinstance(program, Future):
                if not program.done():
                    return False
                if (error := program.exception()) is not None:
                    # a helper's build failed: the width is unbuilt again,
                    # and a caller that finds no wider one builds its own
                    # and raises what that raises
                    _log.warning(
                        "chunk program %s failed to build ahead: %r", key, error
                    )
                    del self._chunk_programs[key]
                    return False
            return program is not None

        for width in self._chunk_widths(offset):
            if width >= want and all(built((offset, width, d)) for d in drafts):
                return width
        self._build_chunk_programs(offset, first=want)
        return want

    def _chunk_program(self, offset: int, width: int, draft: bool = False):
        """The compiled program ``_chunk_width`` found built."""
        program = self._chunk_programs[offset, width, draft]
        if isinstance(program, Future):
            program = self._chunk_programs[offset, width, draft] = program.result()
        return program

    def _prefill_and_sample_mm(
        self, params, vparams, k_pages, v_pages, images, tokens, page_tables,
        seq_lens, key, temps, top_ps, top_ks, seeds,
    ):
        """Multimodal prefill: vision encode fused into the prefill program
        (one dispatch); projected patch embeddings occupy the first
        n_image_tokens positions via llama.prefill(input_embeds=...)."""
        from ..models import vlm

        embeds = vlm.encode_image(vparams, images, self.vision_cfg)
        logits, k_pages, v_pages = self._model.prefill(
            params, tokens, k_pages, v_pages, page_tables, seq_lens, self.cfg,
            attn_impl=self._attn_impl, input_embeds=embeds, mesh=self.mesh,
        )
        next_tokens = sample(
            logits, key, temps, top_ps, top_ks, seeds=seeds, step_ids=seq_lens
        )
        return next_tokens, k_pages, v_pages

    def _prefill_mm_jit(self, bucket_key):
        fn = self._prefill_mm_jits.get(bucket_key)
        if fn is None:
            fn = jax.jit(self._prefill_and_sample_mm, donate_argnums=(2, 3))
            self._prefill_mm_jits[bucket_key] = fn
        return fn

    def _draft_prefill_jit(self, key):
        fn = self._draft_prefill_jits.get(key)
        if fn is None:
            dcfg = self.draft_cfg

            def draft_prefill(params, k_pages, v_pages, tokens, tables, seq_lens):
                return llama.prefill(
                    params, tokens, k_pages, v_pages, tables, seq_lens, dcfg,
                    attn_impl=self._attn_impl, mesh=self.mesh,
                )

            fn = jax.jit(draft_prefill, donate_argnums=(1, 2))
            self._draft_prefill_jits[key] = fn
        return fn

    # the fused speculative round programs (propose+verify+accept and the
    # shared accept/reject math) live in serving/spec_runtime/runtime.py —
    # built per-config in __init__ and dispatched from _spec_round

    #: host-side lookup window per tick (prompt_lookup_max analog)
    NGRAM_LOOKBACK = 1024

    def _ngram_proposals(self, gammas):
        """Host-side prompt lookup: match each slot's trailing n-gram
        against its own prompt+generation history; propose the tokens that
        followed the MOST RECENT earlier occurrence. Each slot's
        ``_NgramIndex`` (built at prefill, pushed per accepted token) makes
        this O(gamma) per slot per tick — the old full-history rescan was
        O(window x n) on the host critical path every tick. ``gammas``
        carries the per-slot proposal budgets (the adaptive controller's
        output): a 0-budget lane proposes nothing and takes the classic
        lane inside the fused round."""
        gamma = self.spec_gamma
        props = np.zeros((self.max_slots, gamma), np.int32)
        n_prop = np.zeros((self.max_slots,), np.int32)
        for i, s in enumerate(self.slots):
            if s.free or s.ngram is None:
                continue
            budget = min(int(gammas[i]), gamma)
            if budget <= 0:
                continue
            cont = s.ngram.propose(budget)
            if cont:
                props[i, : len(cont)] = cont
                n_prop[i] = len(cont)
        return props, n_prop

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- public API ---------------------------------------------------------

    def validate_params(self, params: SamplingParams) -> None:
        """Raise ValueError for parameter combinations this engine rejects —
        servers call this up front so a bad request becomes a 400, not a
        dropped connection. Speculative engines now accept the FULL
        sampling surface (docs/speculative.md#exactness): temperature>0 /
        top_p / top_k / seed= lanes never speculate — they ride the fused
        round's γ=0 classic lane, whose token is drawn by the very same
        (seed, position)-keyed ``sample`` call the block program makes —
        so nothing is rejected engine-wide today."""
        del params

    def make_request(
        self,
        prompt: str,
        params: SamplingParams | None = None,
        image=None,  # PIL image or [H, W, 3] array: multimodal request
        *,
        priority: str = DEFAULT_CLASS,
        tenant: str = "default",
        # entry-minted RequestTraceContext; None = the entry point already
        # SAMPLED THIS REQUEST OUT (don't re-roll); UNSET = no entry point
        # upstream, mint here
        trace=_rt.UNSET,
    ) -> Request:
        """Build (but do not enqueue) one validated, tokenized request.

        The first half of :meth:`submit`, exposed so the disaggregation
        coordinator can hold a request OBJECT through prefill + page
        migration before it ever enters this engine's admission path — the
        deadline arms here, so migration time counts against it."""
        req = Request(
            prompt=prompt,
            params=params or SamplingParams(),
            priority=validate_class(priority),
            tenant=tenant,
        )
        self.validate_params(req.params)
        if trace is _rt.UNSET:
            req.trace = _rt.start_request_trace(
                req.request_id, entry=self.trace_name,
                store=self._trace_store,
                priority=req.priority, tenant=req.tenant,
            )
        elif trace is not None:
            # entry-point-minted context: the request ADOPTS the trace id
            # as its id, so trace id == request id holds fleet-wide
            req.request_id = trace.trace_id
            req.trace = trace
        # else: the entry point decided (sampled out) — stay untraced
        if req.params.seed is None:
            with self._lock:
                self._submit_seq += 1
                req.auto_seed = (
                    self._seed_base * 1_000_003 + self._submit_seq
                ) % (2**31 - 1)
        if image is not None:
            if self.vision_cfg is None:
                raise ValueError(
                    "engine was built without vision=; cannot take images"
                )
            from ..models import vlm

            req.image = vlm.preprocess_image(
                image, self.vision_cfg.vision.image_size
            )
            n_img = self.vision_cfg.n_image_tokens
            # image tokens lead; text budget = largest bucket minus them
            # (multimodal prompts do not take the chunked-prefill path)
            text_budget = min(
                self.prefill_buckets[-1] - n_img, self.max_model_len - 1 - n_img
            )
            text = self.tokenizer.encode(prompt)[:text_budget]
            pad = self.tokenizer.pad_id % self.cfg.vocab_size
            req.prompt_tokens = [pad] * n_img + text
            if self.prefix_cache is not None:
                # content-derived trie key for the image positions: one id
                # repeated (trie depth already encodes position), offset by
                # vocab_size so it can never collide with text keys
                import hashlib as _hashlib

                digest = _hashlib.sha256(
                    np.asarray(req.image).tobytes()
                ).digest()
                base = self.cfg.vocab_size + int.from_bytes(
                    digest[:8], "little"
                )
                req.cache_key_tokens = [base] * n_img + text
        else:
            # prompts longer than the largest bucket prefill in chunks; the
            # hard cap is the model length (minus >=1 decode slot)
            req.prompt_tokens = self.tokenizer.encode(prompt)[
                : self.max_model_len - 1
            ]
        if req.params.deadline_s is not None:
            req.deadline = self._clock() + float(req.params.deadline_s)
        return req

    def request_cost(self, req: Request) -> int:
        """Estimated KV-page cost of ``req`` on THIS engine (admission's
        reservation unit): pages for the full prompt + generation budget."""
        max_total = min(
            len(req.prompt_tokens) + req.params.max_tokens, self.max_model_len
        )
        return self.cache.pages_for(max_total)

    def submit_request(self, req: Request) -> Request:
        """Enqueue a :meth:`make_request`-built request through admission
        control (the second half of :meth:`submit`)."""
        now = self._clock()
        entry = ScheduledRequest(
            payload=req,
            priority=req.priority,
            tenant=req.tenant,
            cost=self.request_cost(req),
            deadline=req.deadline,
            enqueued_at=now,
        )
        occ = self.cache.occupancy()
        # admit-then-enqueue (raises ShedError; reservation taken on admit):
        # the depth read and the enqueue are not one atomic step, so bounds
        # are approximate by up to the number of racing submitters — fine
        # for overload control, which only needs to stop unbounded growth
        try:
            self.admission.admit(
                entry,
                depths=self.policy.depths(),
                pages_used=occ["pages_used"],
                pages_total=occ["pages_total"],
            )
        except ShedError as e:
            _rt.event(
                req.trace, "shed", store=self._trace_store,
                replica=self.trace_name, reason=e.reason,
            )
            _rt.finish_root(
                req.trace, "shed", store=self._trace_store,
                finish_reason="shed",
            )
            raise
        req._sched_entry = entry
        req._queue_span = _rt.begin(
            req.trace, "queue", replica=self.trace_name,
            priority=req.priority, tenant=req.tenant,
        )
        self.policy.submit(entry)
        return req

    def submit(
        self,
        prompt: str,
        params: SamplingParams | None = None,
        image=None,  # PIL image or [H, W, 3] array: multimodal request
        *,
        priority: str = DEFAULT_CLASS,
        tenant: str = "default",
        trace=_rt.UNSET,
    ) -> Request:
        """Enqueue one request through admission control.

        ``priority`` (interactive|default|batch) and ``tenant`` drive the
        fair-share policy; ``params.deadline_s`` arms a deadline. Raises
        :class:`~modal_examples_tpu.scheduling.admission.ShedError` when
        admission rejects the request (servers surface it as HTTP 429)."""
        req = self.make_request(
            prompt, params, image, priority=priority, tenant=tenant,
            trace=trace,
        )
        return self.submit_request(req)

    def generate(self, prompt: str, params: SamplingParams | None = None) -> str:
        """Blocking convenience: submit and collect the full completion."""
        req = self.submit(prompt, params)
        out = []
        for piece in self.stream(req):
            out.append(piece)
        return "".join(out)

    def stream(self, req: Request):
        """Yield text pieces as they decode (SSE-shaped; streaming.py:38-45)."""
        if not self._running:
            self.start()
        while True:
            item = req.out_queue.get()
            if isinstance(item, _Finish):
                req.finish_reason = item.reason
                return
            yield item

    def warmup(self, buckets: tuple[int, ...] | None = None) -> float:
        """Pre-compile the decode step and prefill buckets against trash
        pages (no allocator state touched), and start building the chunk
        programs of prompts beyond the largest bucket — the FAST_BOOT-style
        cold-start control (vllm_inference.py:85-101): pay compiles at boot,
        not on the first user request. Returns seconds spent."""
        if self._running:
            # the scheduler thread donates the same cache buffers; racing it
            # would pass deleted arrays. Warmup is a boot-time API.
            raise RuntimeError("call warmup() before start()")
        t0 = time.monotonic()
        for bucket in buckets or self.prefill_buckets:
            B = self.prefill_batch
            # warmup shares the dispatch sites' (program, shape_key) space:
            # boot-time builds land in the compile ledger once, and the
            # live path then records cache hits instead of re-timing
            (
                _tok, self.cache.k_pages, self.cache.v_pages, self.cache.beside,
            ) = self._profiled(
                "prefill", f"b{bucket}x{B}", self._prefill_jit((bucket, B))
            )(
                self.params,
                self.cache.k_pages,
                self.cache.v_pages,
                jnp.zeros((B, bucket), jnp.int32),
                jnp.zeros((B, self.pages_per_slot), jnp.int32),
                jnp.ones((B,), jnp.int32),
                self._next_key(),
                jnp.ones((B,), jnp.float32),
                jnp.ones((B,), jnp.float32),
                jnp.zeros((B,), jnp.int32),
                jnp.full((B,), -1, jnp.int32),
                **self._state_args([], B),
            )
        if self.vision_cfg is not None:
            # one compiled multimodal prefill shape: the bucket that fits
            # image tokens + text (bigger buckets compile on first use)
            S = self.vision_cfg.vision.image_size
            B = self.prefill_batch
            mm_bucket = self._bucket_for(self.vision_cfg.n_image_tokens + 1)
            _tok, self.cache.k_pages, self.cache.v_pages = self._profiled(
                "prefill_mm", f"b{mm_bucket}x{B}",
                self._prefill_mm_jit((mm_bucket, B)),
            )(
                self.params,
                self.vision_params,
                self.cache.k_pages,
                self.cache.v_pages,
                jnp.zeros((B, S, S, 3), jnp.float32),
                jnp.zeros((B, mm_bucket), jnp.int32),
                jnp.zeros((B, self.pages_per_slot), jnp.int32),
                jnp.full((B,), self.vision_cfg.n_image_tokens + 1, jnp.int32),
                self._next_key(),
                jnp.ones((B,), jnp.float32),
                jnp.ones((B,), jnp.float32),
                jnp.zeros((B,), jnp.int32),
                jnp.full((B,), -1, jnp.int32),
            )
        B = self.max_slots
        # the block program warms for EVERY engine: spec engines run it
        # too — whole-round γ=0 fallbacks (pressure/collapse) and the
        # failover replay path both dispatch it
        (
            _toks, _last, self.cache.k_pages, self.cache.v_pages,
            self.cache.beside,
        ) = self._profiled(
            "block", f"s{self.max_slots}k{self.decode_block}",
            self._block_jit,
        )(
            self.params,
            self.cache.k_pages,
            self.cache.v_pages,
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,), bool),
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B, self.pages_per_slot), jnp.int32),
            jnp.zeros((B,), bool),
            self._next_key(),
            jnp.ones((B,), jnp.float32),
            jnp.ones((B,), jnp.float32),
            jnp.zeros((B,), jnp.int32),
            jnp.full((B,), -1, jnp.int32),
            **self._state_args(),
        )
        if self.spec_mode == "ngram":
            B = self.max_slots
            (
                _, _, _, self.cache.k_pages, self.cache.v_pages,
            ) = self._profiled(
                "ngram_verify", f"s{self.max_slots}g{self.spec_gamma}",
                self._ngram_jit,
            )(
                self.params,
                self.cache.k_pages,
                self.cache.v_pages,
                jnp.zeros((B, self.spec_gamma), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B, self.pages_per_slot), jnp.int32),
                jnp.zeros((B,), bool),
                self._next_key(),
                jnp.ones((B,), jnp.float32),
                jnp.ones((B,), jnp.float32),
                jnp.zeros((B,), jnp.int32),
                jnp.full((B,), -1, jnp.int32),
            )
        if self.spec_mode == "draft":
            for bucket in buckets or self.prefill_buckets:
                B = self.prefill_batch
                _, self.draft_cache.k_pages, self.draft_cache.v_pages = (
                    self._profiled(
                        "draft_prefill", f"b{bucket}x{B}",
                        self._draft_prefill_jit((bucket, B)),
                    )(
                        self.draft_params,
                        self.draft_cache.k_pages,
                        self.draft_cache.v_pages,
                        jnp.zeros((B, bucket), jnp.int32),
                        jnp.zeros((B, self.pages_per_slot), jnp.int32),
                        jnp.ones((B,), jnp.int32),
                    )
                )
            B = self.max_slots
            (
                _,
                _,
                _,
                self.cache.k_pages,
                self.cache.v_pages,
                self.draft_cache.k_pages,
                self.draft_cache.v_pages,
            ) = self._profiled(
                "spec_verify", f"s{self.max_slots}g{self.spec_gamma}",
                self._spec_jit,
            )(
                self.params,
                self.draft_params,
                self.cache.k_pages,
                self.cache.v_pages,
                self.draft_cache.k_pages,
                self.draft_cache.v_pages,
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), jnp.int32),
                jnp.zeros((B, self.pages_per_slot), jnp.int32),
                jnp.zeros((B,), bool),
                jnp.zeros((B,), jnp.int32),
                self._next_key(),
                jnp.ones((B,), jnp.float32),
                jnp.ones((B,), jnp.float32),
                jnp.zeros((B,), jnp.int32),
                jnp.full((B,), -1, jnp.int32),
            )
        # the chunk programs of every offset a prompt can reach: built, not
        # run, on helper threads that nobody waits for
        longest = self.max_model_len - 1
        if longest > self.prefill_buckets[-1]:
            offsets = range(0, longest, self.prefill_buckets[-1])
            for key in dict.fromkeys(map(self._chunk_key, offsets)):
                self._build_chunk_programs(key)
            self._start_chunk_builds()
        jax.block_until_ready(self.cache.k_pages)
        if self.profiler is not None:
            self.profiler.note_drained()
        return time.monotonic() - t0

    def _finish_stream(self, req: Request, marker: "_Finish") -> None:
        """THE terminal routing point: every ``_Finish`` put in this
        engine goes through here. Streams the detok worker owns get their
        marker enqueued BEHIND any pending text (the FIFO ordering
        contract, docs/speculative.md#the-harvest-boundary) — the worker then runs
        :meth:`_deliver_finish`; everything else delivers directly."""
        w = self._detok
        if w is not None and w.alive and w.owns(req):
            w.finish(req, marker)
            return
        self._deliver_finish(req, marker)

    def _deliver_finish(self, req: Request, marker: "_Finish") -> None:
        """THE terminal delivery: close the request's trace (sweeping any
        still-open spans — queue, decode — so no failure path can leak a
        dangling span) and only then release the caller's stream."""
        sp = getattr(req, "_decode_span", None)
        if sp is not None and req.last_token_at is not None:
            sp.end = _rt.wall(req.last_token_at)  # the sweep keeps a set end
        _rt.finish_request(req, marker.reason, store=self._trace_store)
        # per-request usage record (usage.jsonl): journaled at the SAME
        # terminal point that releases the stream, with the ACCOUNTED
        # token counts — Σ journal == the engine's counters by structure
        self.usage.note_finish(req, marker.reason)
        req.out_queue.put(marker)

    def _close_queue_span(self, req: Request) -> None:
        """Close the admission-queue span when the scheduler pops the
        request for a slot (the one non-terminal close; terminal paths
        sweep it in ``_finish_stream`` instead). ``wait_s`` comes from the
        span's OWN start — for adopted (disagg) requests the sched entry's
        ``enqueued_at`` predates the whole migration, which is the migrate
        span's story, not this queue's."""
        sp = getattr(req, "_queue_span", None)
        if sp is not None:
            req._queue_span = None
            end = _rt.wall(req.admitted_at)  # now, for one never admitted
            _rt.finish(
                req.trace, sp, store=self._trace_store, end=end,
                wait_s=round(max(0.0, end - sp.start), 6),
            )

    def abort(self, request: Request) -> None:
        """Cancel a request (the engine-abort surface vLLM exposes for
        client disconnects). Queued (never-scheduled) ones are removed from
        the policy HERE — releasing their admission page reservation and
        per-class depth immediately, and finishing the caller's stream even
        if the scheduler thread never runs. Active ones finish at the next
        scheduler tick and free their slot/pages."""
        request.aborted = True
        entry = getattr(request, "_sched_entry", None)
        if entry is not None and self.policy.remove(entry):
            # was still queued: nothing on a slot, nothing in flight —
            # reservation back to the pool, caller released now
            self.admission.release(entry)
            _obs.set_sched_queue_depths(self.policy.depths())
            self._finish_stream(request, _FINISH)

    # -- disaggregated prefill/decode (serving/disagg, docs/disagg.md) -------

    def prefill_sync(self, req: Request) -> dict:
        """Run ``req``'s prefill WITHOUT taking a decode slot: claim pages,
        fill their KV (bucketed or chunked path), sample the first token,
        and return the claim + sampler state for page extraction — the
        prefill-replica half of disaggregated serving.

        Only legal while the scheduler loop is NOT running: the loop and
        this method donate the same cache buffers through their jits, and
        racing that donation would pass deleted arrays. Prefill-role
        replicas never ``start()`` their engine; concurrent server threads
        serialize on an internal lock."""
        _refuse(self.cfg, "disaggregated transfer")
        if req.image is not None:
            raise ValueError(
                "multimodal requests do not take the disagg prefill path "
                "(image-token KV keys by content hash, not position)"
            )
        self._prefill_sync_pending += 1
        try:
            return self._prefill_sync_locked(req)
        finally:
            self._prefill_sync_pending -= 1

    def _prefill_sync_locked(self, req: Request) -> dict:
        with self._prefill_sync_lock:
            if self._running:
                raise RuntimeError(
                    "prefill_sync requires a stopped engine: prefill-role "
                    "replicas never start their scheduler loop"
                )
            claim = self._claim_pages(req)
            if claim is None:
                raise OutOfPages(
                    f"prefill replica out of KV pages for {req.request_id}"
                )
            req.admitted_at = time.monotonic()
            # no scheduler loop here: the call is one tick of its own
            # (prefill_dispatch, then the blocking read as harvest)
            prof = self.profiler
            tick = None if prof is None else prof.begin_tick()
            self._tick = tick
            _tm(tick, "prefill_dispatch")
            try:
                first = self._prefill_pages(req, claim)
            except Exception:
                # same contract as _fail_claims: a failed prefill must not
                # leak the claim or poison the trie with unwritten pages
                self.release_claim(claim, valid=False)
                raise
            finally:
                if tick is not None:
                    self._tick = None
                    prof.end_tick(tick, worked=True, demand=False)
            self.stats.prompt_tokens += claim["n_prompt"]
            self.usage.note_prompt(req, claim["n_prompt"])
            _rt.record_span(
                req.trace, "prefill", start=_rt.wall(req.admitted_at),
                parent=getattr(req, "_trace_parent", None),
                store=self._trace_store, replica=self.trace_name,
                n_prompt=claim["n_prompt"],
            )
            return {
                "claim": claim,
                "position": claim["n_prompt"],
                "first_token": first,
                # only pages holding real prompt KV ship; decode growth
                # pages are allocated (empty) on the decode side
                "n_kv_pages": self.cache.pages_for(claim["n_prompt"]),
            }

    def release_claim(self, claim: dict, *, valid: bool = True) -> None:
        """Free a slot-less page claim (the disagg mirror of
        ``_release_slot_pages``/``_fail_claims``). ``valid=True``: the pages
        hold real KV — trie refs release but stay cached, keeping the
        prefill replica's prefix cache warm for the next shared-prefix
        prompt; private pages free. ``valid=False``: the prefill never
        completed — trie pages invalidate so no later request shares
        never-written KV."""
        if valid and self.prefix_cache is not None:
            self.prefix_cache.release(claim["trie_pages"])
            self.cache.allocator.free(claim["private_pages"])
        elif valid:
            self.cache.allocator.free(claim["pages"])
        else:
            self._unwind_claim(claim)

    def _unwind_claim(self, claim: dict) -> None:
        """Invalidate + free a claim whose pages never received valid KV —
        the ONE ownership rule shared by the slot failure path
        (``_fail_claims``) and the slot-free one (``release_claim``): trie
        pages another live request still holds stay theirs; everything this
        claim exclusively owns goes back to the allocator."""
        if self.prefix_cache is not None:
            self.prefix_cache.invalidate(claim["trie_pages"])
            owned = list(claim["private_pages"]) + [
                p for p in claim["trie_pages"]
                if p not in self.prefix_cache._by_page
            ]
            self.cache.allocator.free(owned)
        else:
            self.cache.allocator.free(claim["pages"])

    def extract_request_pages(self, req: Request, state: dict):
        """Pull the prefilled pages of a :meth:`prefill_sync` result off the
        device as a wire-ready :class:`~.disagg.transport.PageBlock` (page
        data + every other cache leaf, block hashes, sampler meta)."""
        _refuse(self.cfg, "disaggregated transfer")
        from .disagg.transport import chain_hashes, extract_pages

        claim = state["claim"]
        used = claim["pages"][: state["n_kv_pages"]]
        return extract_pages(
            self.cache,
            used,
            block_hashes=chain_hashes(
                req.cache_key_tokens or req.prompt_tokens,
                self.cache.page_size,
            ),
            meta={
                "request_id": req.request_id,
                "prompt_tokens": [int(t) for t in req.prompt_tokens],
                "position": int(state["position"]),
                "first_token": int(state["first_token"]),
                "auto_seed": req.auto_seed,
                # the trace context rides the MTKV1 envelope: a decode
                # replica in ANOTHER process reconstructs it from here
                # (reqtrace.from_wire) and keeps stitching the same trace
                "trace": _rt.wire(
                    req.trace, parent=getattr(req, "_trace_parent", None)
                ),
            },
        )

    def submit_adopted(self, req: Request, entry, block) -> Request:
        """Enqueue a request whose prompt KV was prefilled elsewhere.

        ``block`` (a deserialized ``PageBlock``) is adopted into this cache
        at admission ON the scheduler thread — the only thread that may
        touch the cache arrays alongside the decode jits — and decode
        continues from the migrated position with the migrated first token
        riding the fresh-slot override lane, exactly like a local prefill's
        first sample. ``entry`` is the migration's admission reservation,
        taken by the coordinator BEFORE any byte moved so decode-side KV
        headroom was guaranteed while the transfer was in flight."""
        _refuse(self.cfg, "disaggregated transfer")
        if block.kv_dtype != self.cache.kv_dtype:
            raise ValueError(
                f"migrated block is {block.kv_dtype}, this cache is "
                f"{self.cache.kv_dtype}: disagg peers must share a kv_dtype"
            )
        req._adopted_state = {
            "block": block,
            "position": int(block.meta["position"]),
            "first_token": int(block.meta["first_token"]),
            # decode-state leg (docs/failover.md): present on live-migrated
            # mid-decode blocks, absent on plain PR-6 first-token blocks —
            # the envelope extension is purely additive meta, so either
            # side of the wire may predate the other
            "resume": block.meta.get("resume"),
        }
        req._sched_entry = entry
        req._queue_span = _rt.begin(
            req.trace, "queue", replica=self.trace_name,
            priority=req.priority, tenant=req.tenant,
        )
        self.policy.submit(entry)
        return req

    # -- in-flight request failover (serving/failover.py, docs/failover.md) --

    def submit_resumed(
        self, req: Request, *, prompt_tokens, generated, emitted_len: int = 0
    ) -> Request:
        """Enqueue a request resumed from a decode checkpoint: ``req``'s
        stream continues on THIS engine, token-identical to the
        uninterrupted run.

        ``prompt_tokens`` is the ORIGINAL prompt's token ids, ``generated``
        the tokens accepted before the failure. The engine re-prefills the
        ORIGINAL prompt (the same bucket/path — bitwise the original
        prompt KV, and cheap when the prefix cache still holds the
        blocks), teacher-forces ``generated[:-1]`` through THE decode
        block program (``_replay_decode_prefix`` — the same compiled body
        the dead replica ran, so the rebuilt KV is bit-identical; a
        prefill recompute of those positions drifts by a bf16 rounding
        asymmetry and flips greedy argmaxes), then feeds ``generated[-1]``
        — the last token the client already has — at its original
        position through the fresh-slot override lane. Sampling is keyed
        ``(seed, position)`` (the resumed request keeps its original
        seed/auto_seed), so every token from there on reproduces the
        uninterrupted stream exactly; the emitted-text cursor resumes at
        ``emitted_len`` so no char is duplicated or lost. Empty
        ``generated`` degrades to a plain resubmission. The same ``req``
        object (same id, same out_queue, same trace id) rides through, so
        a blocked ``stream()`` consumer continues without reconnecting."""
        _refuse(self.cfg, "disaggregated transfer")
        if req.image is not None:
            raise ValueError(
                "multimodal requests do not take the failover resume path"
            )
        if req.aborted:
            # a client abort landed during the failover window: honor it —
            # resurrecting an abandoned request would decode to max_tokens
            # for nobody (the abort flag is never reset here)
            self._finish_stream(
                req,
                _Finish("deadline" if req.deadline_expired else "stop"),
            )
            return req
        req.finish_reason = None
        base = [int(t) for t in prompt_tokens]
        gen = [int(t) for t in generated]
        # pin the ORIGINAL prompt for any later checkpoint: resumption
        # must never compound (prompt_tokens is reset to the base here,
        # but the explicit record keeps that invariant checkable)
        req._orig_prompt_tokens = base
        req.generated_tokens = gen
        req.emitted_len = int(emitted_len)
        req.n_generated = max(req.n_generated, len(gen))
        req.cache_key_tokens = None
        req.created = time.monotonic()
        if gen and (
            len(gen) >= req.params.max_tokens
            or len(base) + len(gen) >= self.max_model_len
        ):
            # nothing left to decode (the failure landed on the final
            # token): deliver the terminal marker without taking a slot
            self._finish_stream(req, _Finish("length"))
            return req
        req.prompt_tokens = list(base)
        # the generated prefix is REPLAYED through the decode program at
        # harvest, not re-prefilled: same compiled body, same inputs, same
        # bits (the prompt claim is therefore identical to the original
        # request's — same pages, same trie sharing)
        req._resume_state = {"replay": gen} if gen else None
        return self.submit_request(req)

    def migrate_out(self, req: Request, *, timeout: float = 30.0):
        """Detach ``req`` from this engine for a proactive live migration
        (fleet drain / coordinator rebalancing — docs/failover.md). Runs on
        the scheduler thread (the only one that may read cache arrays next
        to the decode jits). Returns one of:

        - ``("block", PageBlock)`` — the request was mid-decode: its KV
          pages ([0, position)) are extracted with the decode-state leg in
          the MTKV1 meta, the slot is released (trie pages stay cached),
          and the caller adopts the block on the target via
          :meth:`submit_adopted`;
        - ``("requeue", None)`` — still queued, or mid-prefill with no
          token accepted yet: nothing to ship, the caller resubmits the
          prompt fresh on the target (token-identical — the stream never
          emitted);
        - ``("gone", None)`` — already finished or aborted; nothing to do.

        Raises when the scheduler loop is stopped or unresponsive — the
        caller falls back to the reactive (checkpoint-only) resume."""
        _refuse(self.cfg, "disaggregated transfer")
        return self._run_on_scheduler(
            lambda: self._migrate_out_on_sched(req), timeout
        )

    def _migrate_out_on_sched(self, req: Request):
        from .disagg.transport import chain_hashes, extract_pages

        entry = getattr(req, "_sched_entry", None)
        if entry is not None and self.policy.remove(entry):
            # still queued: reservation back, caller resubmits elsewhere
            self.admission.release(entry)
            _obs.set_sched_queue_depths(self.policy.depths())
            self._close_queue_span(req)
            return ("requeue", None)
        for i, s in enumerate(self.slots):
            if s.request is not req:
                continue
            if req.aborted:
                return ("gone", None)
            if s.prefill is not None or s.pending_first:
                # mid-prefill: partial KV must not ship or stay cached —
                # unwind; nothing was emitted, so a fresh resubmission on
                # the target is token-identical
                self._unwind_slot(s)
                s.request = None
                self._active[i] = False
                return ("requeue", None)
            # mid-decode: KV for [0, position) is complete (every accepted
            # token's predecessor was fed through a finished block); later
            # positions an in-flight block may have written are masked by
            # position-bounded attention and overwritten on resume. The
            # same harvest-boundary argument covers a block or a round in
            # flight (docs/speculative.md#the-harvest-boundary): un-harvested
            # device tokens are simply never accepted — the checkpoint carries only
            # committed state, and the peer regenerates the rest
            # token-identically from the (seed, position) keying.
            if self._detok is not None and self._detok.owns(req):
                # drain pending text first: req.emitted_len below must be
                # the FINAL emitted cursor or the resumed stream would
                # duplicate/lose chars
                self._detok.flush(timeout=5.0)
            n_kv = self.cache.pages_for(s.position)
            # the ORIGINAL prompt (explicit on resumed requests); the
            # pages hold KV for base + generated[:-1], which keys their
            # chained hashes
            base = getattr(req, "_orig_prompt_tokens", None)
            if base is None:
                base = req.prompt_tokens
            covered = list(base) + [int(t) for t in req.generated_tokens[:-1]]
            block = extract_pages(
                self.cache,
                s.pages[:n_kv],
                block_hashes=chain_hashes(covered, self.cache.page_size),
                meta={
                    "request_id": req.request_id,
                    "prompt_tokens": [int(t) for t in base],
                    "position": int(s.position),
                    "first_token": int(s.last_token),
                    "auto_seed": req.auto_seed,
                    # the decode-state leg: everything past first-token
                    # adoption that a mid-decode takeover needs
                    "resume": {
                        "generated": [int(t) for t in req.generated_tokens],
                        "emitted_len": int(req.emitted_len),
                    },
                    "trace": _rt.wire(req.trace),
                },
            )
            sp = getattr(req, "_decode_span", None)
            if sp is not None:
                req._decode_span = None
                _rt.finish(req.trace, sp, store=self._trace_store)
            # valid KV: trie pages stay cached (warm for a later reactive
            # re-prefill), private pages free — the normal-finish release
            self._release_slot_pages(s)
            s.request = None
            self._active[i] = False
            return ("block", block)
        return ("gone", None)

    def start(self) -> "LLMEngine":
        with self._lock:
            if self._stopped_on_error:
                raise RuntimeError(
                    "engine stopped after a scheduler error (strict mode); "
                    f"last traceback:\n{(self.error_log or ['?'])[-1]}"
                )
            if self._running:
                return self
            # starting IS progress: a revived engine must not present its
            # previous life's stale watermark ages to the watchdog in the
            # window before its first tick (serving/health.py)
            self.watermarks.note_start()
            self._running = True
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()
        return self

    def revive(self) -> "LLMEngine":
        """Clear the stopped-on-error poison so :meth:`start` may run again
        — the router's re-probe re-admission path (docs/faults.md;
        ``EngineReplica.probe``). Safe because stopping on error already
        released every caller and freed every slot (``_release_all``): a
        revived engine starts empty. ``error_log`` survives for diagnosis;
        without an explicit revive, one scheduler error removed a replica
        from the fleet forever."""
        with self._lock:
            self._stopped_on_error = False
        return self

    def stop(self, *, reason: str = "stop") -> None:
        """Stop the scheduler and release every caller: in-flight and queued
        requests get their terminal marker so stream()/generate() return
        (partial output for in-flight ones) instead of blocking forever.
        ``reason="error"`` marks the release as a failure — the fleet's
        forced reap and the gray-failure watchdog use it so still-live
        streams take the router-level reactive failover instead of ending
        as a silently truncated "stop" (docs/failover.md). An error-stop
        also POISONS the engine like a strict-mode scheduler crash: the
        router must not place new work on it until ``probe()`` revives and
        restarts it (the watchdog's stop -> revive -> re-probe ladder leg,
        docs/health.md)."""
        if reason == "error":
            self._stopped_on_error = True
        self._running = False
        if self._thread and self._thread is not threading.current_thread():
            self._thread.join(timeout=10)
        if self._chunk_builder is not None:
            # the helpers finish what was handed to them, unwaited, and
            # end; a restarted engine finds those programs built
            self._chunk_builder.shutdown(wait=False)
            self._chunk_builder = None
        if self._detok is not None:
            # drain held text BEFORE the release sweep: its direct markers
            # must land behind every chunk the worker still owes
            self._detok.stop()
        self._release_all(_FINISH if reason == "stop" else _Finish(reason))
        self._flush_token_counters()
        self.usage.flush()  # unthrottled: the final window reaches pushes
        if self.profiler is not None:
            self.profiler.flush()

    # -- scheduler loop ------------------------------------------------------

    def _loop(self) -> None:
        import traceback

        try:
            while self._running:
                try:
                    worked = self.step()
                except _FaultError:
                    # Injected scheduler-thread crash (faults/inject.py):
                    # fail in-flight AND queued requests LOUDLY — every
                    # caller's stream terminates with finish_reason="error"
                    # instead of wedging — then keep the loop alive. An
                    # injected fault is not a scheduler-logic bug, so it
                    # neither poisons the engine (strict mode) nor trips
                    # the _error_reports session sentinel.
                    _log.warning(
                        "injected scheduler crash: releasing all callers"
                    )
                    # the crash hits every in-flight request: mark each
                    # traced one before the release sweep closes its spans
                    for s in self.slots:
                        if s.request is not None:
                            _rt.event(
                                s.request.trace, "fault",
                                store=self._trace_store,
                                replica=self.trace_name,
                                point="engine.scheduler_crash",
                            )
                    self._release_all(_Finish("error"))
                    worked = False
                except Exception:
                    # Per-REQUEST failures never reach here: bad params are
                    # rejected at submit() and failed prefills unwind their
                    # claims inside _admit (_fail_claims). Anything caught
                    # here is a scheduler-logic error. Keep the traceback on
                    # the engine so it is diagnosable after the fact
                    # (surfaced in /metrics as mtpu_scheduler_errors_total).
                    tb = traceback.format_exc()
                    self.error_log.append(tb)
                    self.error_count += 1
                    del self.error_log[:-20]
                    LLMEngine._error_reports.append(tb[-800:])
                    del LLMEngine._error_reports[:-50]
                    _obs.record_scheduler_error()
                    _log.error("scheduler-loop exception:\n%s", tb)
                    if self.strict:
                        # tests must fail loudly, not generate corrupt
                        # output: poison the engine (start() refuses to
                        # resurrect it — a racing stream() would otherwise
                        # spawn a second scheduler thread mid-teardown),
                        # then release callers
                        self._stopped_on_error = True
                        self._running = False
                        # capture BEFORE the release sweep frees the slots:
                        # the bundle's open-request traces are the victims
                        _incident.capture(
                            "scheduler_crash",
                            reason=tb.strip().splitlines()[-1] if tb else "",
                            replica=self.trace_name,
                        )
                        self._release_all(_Finish("error"))
                        return
                    worked = False
                if not worked:
                    time.sleep(0.002)
        finally:
            if self._running:
                # The thread is dying WITHOUT stop() — a BaseException, or
                # a bug in the error handling above. Before this guard,
                # every in-flight stream() would block forever on a queue
                # nothing will ever feed; now the crash is loud: callers
                # get finish_reason="error" and the engine is poisoned
                # until revive() (docs/faults.md: no request may wedge).
                self._running = False
                self._stopped_on_error = True
                _incident.capture(
                    "scheduler_crash",
                    reason="scheduler thread died without stop()",
                    replica=self.trace_name,
                )
                self._release_all(_Finish("error"))

    def _drain_ctrl(self) -> None:
        """Service scheduler-thread control commands (live-migration
        checkpoint extraction — serving/failover.py). Each command's
        result/exception goes back to the waiting caller thread."""
        while self._ctrl:
            fn, out_q = self._ctrl.popleft()
            try:
                out_q.put(("ok", fn()))
            except Exception as e:  # the caller re-raises; the loop lives
                out_q.put(("err", e))

    def _run_on_scheduler(self, fn, timeout: float = 30.0):
        """Run ``fn`` on the scheduler thread (the only thread that may
        touch cache arrays next to the decode jits) and return its result.
        Raises RuntimeError when the loop is not running and TimeoutError
        when it stops servicing commands — callers fall back to the
        reactive (checkpoint-only) path either way."""
        if not self._running:
            raise RuntimeError("engine scheduler is not running")
        out_q: queue.Queue = queue.Queue()
        self._ctrl.append((fn, out_q))
        try:
            status, val = out_q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"scheduler did not service the control command in {timeout}s"
            ) from None
        if status == "err":
            raise val
        return val

    def _release_all(self, marker: "_Finish") -> None:
        while self._ctrl:
            # a stopping/crashed engine must not wedge a migration caller
            fn, out_q = self._ctrl.popleft()
            out_q.put(("err", RuntimeError("engine released all requests")))
        self._inflight.clear()
        self._pending_harvest.clear()
        if self.profiler is not None:
            self.profiler.note_drained()
        self._device_tokens = None
        self._last_dispatch_at = None
        # queue BEFORE slots: delivering an in-flight marker wakes that
        # caller, and a caller that immediately resubmits must not have
        # its fresh request reaped by the tail of this same sweep (the
        # surviving-loop crash path keeps serving — a post-release
        # submission stays queued for the next tick instead)
        for entry in self.policy.drain():
            self.admission.release(entry)
            self._finish_stream(entry.payload, marker)
        for slot in self.slots:
            if not slot.free:
                self._finish_stream(slot.request, marker)
                if slot.prefill is not None or slot.pending_first:
                    # stopping mid-prefill: pages may hold partial KV —
                    # invalidate, don't cache (a revived engine must not
                    # share them)
                    self._unwind_slot(slot)
                else:
                    self._release_slot_pages(slot)
                slot.request = None

    def step(self) -> bool:
        """One scheduler tick: expire deadlines -> admit -> decode -> emit.
        Returns True if any work happened.

        Tick anatomy (docs/observability.md#hot-path-profiling): with the
        profiler on, the tick's host time is partitioned into the
        catalog.TICK_PHASES by the ``_tm`` phase entries here and in the
        helpers this calls; an idle engine's ticks are not profiled."""
        # fault point (docs/faults.md): a scheduler-thread crash. _loop
        # catches the FaultError, fails every caller loudly, and survives.
        _inject.check("engine.scheduler_crash")
        # fault point (docs/health.md): a SILENT scheduler freeze — the
        # thread stays alive, healthy() stays true, but no tick, dispatch,
        # or accept ever lands again. Nothing inside the engine ends it;
        # only stop() (the watchdog's wedged-scheduler recovery, or an
        # operator) lifts the hold — exactly the gray failure the
        # progress-watermark watchdog exists to detect.
        if _inject.fire("engine.scheduler_freeze"):
            _log.warning("injected scheduler freeze: holding the loop")
            for s in self.slots:
                if s.request is not None:
                    _rt.event(
                        s.request.trace, "fault", store=self._trace_store,
                        replica=self.trace_name,
                        point="engine.scheduler_freeze",
                    )
            while self._running:
                time.sleep(0.005)
            return False
        prof = self.profiler
        tick = None if prof is None else prof.begin_tick(self._has_demand())
        self._tick = tick
        worked = False
        try:
            # a tick traces wherever it dispatches a program or an eager
            # operation at a new shape; the helper threads lower their chunk
            # programs between ticks and never beside one (two threads
            # tracing at once crashed inside jax's WeakrefLRUCache: PERF.md
            # section 7, after PR 34)
            with self._chunk_lowering:
                _tm(tick, "ctrl")
                self.watermarks.note_tick()
                self._drain_ctrl()
                _tm(tick, "policy")
                self._expire_deadlines()
                admitted = self._admit()
                decoded = self._decode_tick()
                _tm(tick, "policy")
                self._refresh_gauges()
            worked = admitted or decoded
            if self._chunk_queued:
                self._start_chunk_builds_when_idle(worked)
        finally:
            # also on a scheduler error: the open span and its trace
            # annotation close with the tick they belong to
            if tick is not None:
                self._tick = None
                prof.end_tick(tick, worked, demand=self._has_demand())
        return worked

    def _expire_deadlines(self) -> None:
        """Deadline enforcement, both stages: queued work past its deadline
        is cancelled before ever taking a slot (its page reservation goes
        back to the pool); in-flight work is aborted so the next decode
        tick reaps the slot and frees its pages."""
        now = self._clock()
        for entry in self.policy.expired(now):
            self.admission.release(entry)
            req = entry.payload
            req.deadline_expired = True
            _obs.record_deadline_miss("queued")
            self._finish_stream(req, _Finish("deadline"))
        for s in self.slots:
            req = s.request
            if (
                req is not None
                and req.deadline is not None
                and not req.aborted
                and now >= req.deadline
            ):
                req.deadline_expired = True
                req.aborted = True  # reaped (pages freed) in _decode_tick
                _obs.record_deadline_miss(
                    # a sliced prefill can now outlive a deadline mid-fill:
                    # its own stage label (the reap unwinds the claim)
                    "prefill"
                    if s.prefill is not None or s.pending_first
                    else "inflight"
                )

    def _refresh_gauges(self) -> None:
        """Engine-load gauges (queue depth, active slots, tokens/s), KV/
        prefix-cache occupancy, and prefill-vs-decode token-counter deltas
        into the process registry — throttled so the hot loop never pays
        more than a few dict writes per second."""
        now = time.monotonic()
        if now - self._metrics_wall < 0.25:
            return
        self._metrics_wall = now
        depths = self.policy.depths()
        _obs.set_engine_gauges(
            waiting=sum(depths.values()),
            active_slots=sum(1 for s in self.slots if not s.free),
            tokens_per_second=self.stats.tokens_per_second(),
        )
        _obs.set_sched_queue_depths(depths)
        # occupancy via the cache helper: covers the native allocator, which
        # has no gauge hooks of its own (the python allocator's alloc/free
        # hooks write the same series — idempotent, last-writer-wins)
        occ = self.cache.occupancy()
        _obs.set_kv_occupancy(
            used=occ["pages_used"],
            free=occ["pages_free"],
            total_usable=occ["pages_total"],
        )
        # dtype-aware footprint: the same page count pins half the HBM at
        # kv_dtype="int8", and this gauge is where that shows up
        _obs.set_kv_cache_bytes(occ["bytes_total"], self.cache.kv_dtype)
        if self.prefix_cache is not None:
            _obs.set_prefix_cache_pages(self.prefix_cache.cached_pages)
        # sliced-prefill remainder: tokens admitted to slots whose chunked
        # prefill the budget is still metering out
        backlog = 0
        for s in self.slots:
            if s.prefill is not None and s.request is not None:
                backlog += max(
                    0, len(s.request.prompt_tokens) - s.prefill.offset
                )
        _obs.set_prefill_backlog(backlog)
        # speculative gauges (docs/speculative.md#series): dispatched-γ
        # p50 over the window since the last refresh, harvested tokens per
        # spec round (held when idle), lifetime acceptance, and the
        # fallback-round counter delta
        if self.spec_gamma:
            d = self._spec_rounds - self._spec_flush["rounds"]
            if d > 0:
                self._spec_tpd = (
                    self._spec_round_tokens - self._spec_flush["tokens"]
                ) / d
            fb = self._spec_fallbacks - self._spec_flush["fallbacks"]
            if d > 0 or fb > 0:
                self._spec_flush = {
                    "rounds": self._spec_rounds,
                    "tokens": self._spec_round_tokens,
                    "fallbacks": self._spec_fallbacks,
                }
            gw = self._spec_gamma_window
            if gw:
                self._spec_gamma_p50 = float(np.median(gw))
                del gw[:]
            _obs.set_spec_gauges(
                gamma=self._spec_gamma_p50,
                tokens_per_dispatch=self._spec_tpd,
                acceptance_rate=self.stats.acceptance_rate(),
            )
            if fb > 0:
                _obs.record_spec_fallback(fb)
        self._flush_token_counters()
        # per-tenant usage deltas + roofline MFU/MBU gauges ride the same
        # throttle (the flight recorder's tsdb sampler sees them for free)
        self.usage.flush()

    def _flush_token_counters(self) -> None:
        """Push the stats deltas accumulated since the last flush into the
        prometheus token counters (also called unthrottled from stop(), so
        the final sub-throttle window is never lost from a pushed
        exposition)."""
        s, last = self.stats, self._counter_flush
        _obs.record_token_totals(
            prompt=s.prompt_tokens - last["prompt"],
            generated=s.generated_tokens - last["generated"],
            steps=s.steps - last["steps"],
        )
        self._counter_flush = {
            "prompt": s.prompt_tokens,
            "generated": s.generated_tokens,
            "steps": s.steps,
        }

    def _admit(self) -> bool:
        """Claim slots+pages for policy-selected requests, then prefill each
        bucket's admissions as ONE batched jitted call (compile shapes:
        bucket x pow2-padded batch — continuous batching on the prefill side
        too). The pop order is the SchedulerPolicy's (priority classes +
        tenant fair share by default), not submission order.

        Stall-free admission (docs/scheduling.md): ``prefill_budget`` caps
        the prompt tokens converted into prefill work per tick (0 =
        unlimited). In-flight sliced prefills resume FIRST — their pages
        are already held, and finishing them frees capacity — then new
        entries convert while budget remains; the remainder goes back to
        the front of its queues through the preemption-safe requeue, its
        reservations untouched. Every prefill dispatched here is ASYNC:
        the sampled first tokens park on the pending-harvest queue and are
        read only after ``_decode_tick`` has dispatched the next decode
        block, so in-flight streams never wait on a prefill round trip."""
        tick = self._tick
        budget = self.prefill_budget or None  # None/0 = unlimited
        _tm(tick, "prefill_resume")
        spent = self._advance_pending_prefills(budget, 0)
        _tm(tick, "admit")
        assignments: list[tuple[int, "Request", dict]] = []  # (slot, req, claim)
        free_slots = [i for i, s in enumerate(self.slots) if s.free]
        entries = (
            self.policy.next_batch(len(free_slots))
            if free_slots and (budget is None or spent < budget)
            else []
        )
        now = self._clock()
        taken = 0  # free_slots consumed (grouped prefills + adoptions)
        adopted_any = False
        for pos, entry in enumerate(entries):
            req: Request = entry.payload
            if (
                budget is not None
                and spent >= budget
                and not req.aborted
                and getattr(req, "_adopted_state", None) is None
            ):
                # budget spent: stop converting queue entries. This entry
                # and the not-yet-examined rest still hold their admission
                # reservations (nothing was released for them), so the
                # preemption-safe front-requeue is all that's needed.
                # Aborted entries still drain (they cost no prefill) and
                # adopted blocks ship ready-made KV — cost 0 tokens.
                self.policy.requeue(entries[pos:])
                break
            # popped = the reservation converts into a real page claim (or
            # is dropped with the request); either way it's off the books
            self.admission.release(entry)
            if req.aborted:
                self._finish_stream(
                    req,
                    _Finish("deadline") if req.deadline_expired else _FINISH,
                )
                continue
            adopted = getattr(req, "_adopted_state", None)
            if adopted is not None:
                # migrated request (disagg): its prompt KV arrives as a wire
                # block, not a prompt to prefill — adopt on THIS thread, the
                # only one that may write cache arrays next to the decode jits
                status = self._admit_adopted(
                    free_slots[taken], req, adopted, entry, now
                )
                if status == "retry":
                    self.admission.reserve(entry)
                    self.policy.requeue(entries[pos:])
                    break
                if status == "ok":
                    taken += 1
                    adopted_any = True
                continue
            claim = self._claim_pages(req)
            if claim is None:
                # no KV room: preemption-safe requeue — this entry and every
                # not-yet-examined one go back to the FRONT of their queues
                # in original order (reservations re-taken), and admission
                # waits for a completion to free pages
                rest = entries[pos:]
                # only THIS entry's reservation was released above; the
                # not-yet-examined rest still hold theirs
                self.admission.reserve(entry)
                self.policy.requeue(rest)
                break
            _obs.record_sched_queue_wait(
                entry.priority, max(0.0, now - entry.enqueued_at)
            )
            req.admitted_at = time.monotonic()
            self._close_queue_span(req)
            assignments.append((free_slots[taken], req, claim))
            taken += 1
            if (
                claim["n_prompt"] <= self.prefill_buckets[-1]
                or req.image is not None
            ):
                # short (bucketed) prompts prefill atomically, so they
                # charge the budget up front; long ones charge per chunk
                # as their state machine advances below
                spent += claim["n_prompt"]

        _tm(tick, "prefill_dispatch")
        long_ones: list[tuple] = []
        grouped: list[tuple] = []
        for a in assignments:
            # one-pass split on the prompt-length predicate (the old
            # `a not in long_ones` filter re-scanned a list of tuples
            # holding dict claims — O(n^2) equality over page lists)
            if (
                a[2]["n_prompt"] > self.prefill_buckets[-1]
                and a[1].image is None  # mm prompts are capped at submit()
            ):
                long_ones.append(a)
            else:
                grouped.append(a)
        by_bucket: dict[tuple, list] = {}
        for a in grouped:
            key = (self._bucket_for(a[2]["n_prompt"]), a[1].image is not None)
            by_bucket.setdefault(key, []).append(a)
        for (bucket, is_mm), group in by_bucket.items():
            # chunk to the ONE compiled batch shape per bucket
            for i in range(0, len(group), self.prefill_batch):
                chunk = group[i : i + self.prefill_batch]
                try:
                    self._prefill_group(bucket, chunk, is_mm=is_mm)
                except Exception:
                    # a failed prefill must not leak claims, hang callers, or
                    # leave never-written KV pages in the prefix trie
                    import traceback

                    traceback.print_exc()
                    self._fail_claims(chunk)
        for a in long_ones:
            try:
                self._prefill_long(*a)
            except Exception:
                # same contract as the grouped path: a failed chunked prefill
                # must not leave a half-initialized slot (next decode tick
                # would read uninitialized KV), leak its page claim, or poison
                # the prefix trie with partially-written pages
                import traceback

                traceback.print_exc()
                self._fail_claims([a])
        if long_ones:
            # newly admitted long prompts advance with what remains of this
            # tick's budget (at least one chunk fires when nothing else
            # did: the progress guarantee)
            _tm(tick, "prefill_resume")
            spent = self._advance_pending_prefills(budget, spent)
        return bool(assignments) or adopted_any or spent > 0

    def _admit_adopted(
        self, slot_idx: int, req: Request, state: dict, entry, now: float
    ) -> str:
        """Install a migrated request into a slot: allocate its full page
        budget, adopt the shipped KV block into the leading pages, and
        start decode from the migrated position. Returns ``"ok"``,
        ``"retry"`` (no pages free — caller requeues, preemption-safe), or
        ``"failed"`` (corrupt/incompatible block — the caller's stream ends
        with finish_reason="error" and no slot is consumed)."""
        from .disagg.transport import TransportError, adopt_pages

        block = state["block"]
        n_pages = self.request_cost(req)
        try:
            pages = self.cache.allocator.alloc(n_pages)
        except OutOfPages:
            if self.prefix_cache is not None:
                self.prefix_cache.evict(n_pages)
                try:
                    pages = self.cache.allocator.alloc(n_pages)
                except OutOfPages:
                    return "retry"
            else:
                return "retry"
        if req.trace is None:
            # cross-process migration: the context rides the MTKV1 meta —
            # reconstruct it so decode-side spans keep stitching
            req.trace = _rt.from_wire(
                block.meta.get("trace"), store=self._trace_store
            )
        req.admitted_at = time.monotonic()
        t_wall = _rt.wall(req.admitted_at)
        try:
            adopt_pages(self.cache, block, pages[: block.n_pages])
        except TransportError as e:
            self.cache.allocator.free(pages)
            _log.error(
                "adopting migrated pages for %s failed: %s", req.request_id, e
            )
            _rt.record_span(
                req.trace, "adopt", start=t_wall, status="error",
                parent=getattr(req, "_trace_parent", None),
                store=self._trace_store, replica=self.trace_name,
            )
            self._finish_stream(req, _Finish("error"))
            return "failed"
        _rt.record_span(
            req.trace, "adopt", start=t_wall,
            parent=getattr(req, "_trace_parent", None),
            store=self._trace_store, replica=self.trace_name,
            pages=block.n_pages,
        )
        slot = self.slots[slot_idx]
        slot.request = req
        self._tenancy_seq += 1
        slot.tenancy = self._tenancy_seq
        slot.claimed_at = self._clock()
        # adopted pages are all privately owned: this replica's prefix trie
        # never saw them (tier/trie integration is the PREFILL side's job)
        slot.pages = list(pages)
        slot.trie_pages = []
        slot.private_pages = list(pages)
        # mid-decode adoption (the decode-state leg of the MTKV1 envelope,
        # docs/failover.md): a live-migrated request arrives with its
        # accepted-token history and emitted-text cursor — seed both so
        # detokenization, stop handling, and max_tokens continue exactly
        # where the source replica left off. Absent (a plain first-token
        # block) everything below degrades to the PR-6 behavior.
        resume = state.get("resume")
        if resume:
            req.generated_tokens = [int(t) for t in resume["generated"]]
            req.emitted_len = int(resume.get("emitted_len", 0))
            req.n_generated = max(req.n_generated, len(req.generated_tokens))
        slot.generated = req.generated_tokens
        slot.emitted_text_len = req.emitted_len
        slot.prefill = None
        slot.pending_first = False
        table = np.zeros((self.pages_per_slot,), np.int32)
        table[: len(pages)] = pages
        self._page_tables[slot_idx] = table
        slot.position = state["position"]
        slot.last_token = state["first_token"]
        slot.fresh = True  # first token rides the override lane, like prefill
        # speculative engines adopt migrated work too
        # (docs/speculative.md#failure-boundaries): ngram mode rebuilds its
        # prompt-lookup index from the history that rode the wire; draft
        # mode pins γ=0 for this tenancy (spec_hold) — the draft cache's KV
        # never crossed the wire, and the classic lane inside the fused
        # round keeps the stream token-identical regardless
        slot.spec_hold = self.spec_mode == "draft"
        if self.spec_mode == "ngram":
            slot.ngram = _NgramIndex(
                self.ngram_n,
                list(req.prompt_tokens or [])
                + [int(t) for t in req.generated_tokens],
                self.NGRAM_LOOKBACK,
            )
        _obs.record_sched_queue_wait(
            entry.priority, max(0.0, now - entry.enqueued_at)
        )
        self._close_queue_span(req)
        req._decode_span = _rt.begin(
            req.trace, "decode", replica=self.trace_name,
            spec_mode=self.spec_mode or "-",
        )
        if resume:
            # the migrated token was accepted (and its text emitted) on the
            # source replica before the checkpoint: feed it through the
            # override lane without re-accepting — decode continues with
            # the NEXT sampled token, token-identical to no migration
            pass
        else:
            self._accept_token(slot_idx, state["first_token"])
            _tm(self._tick, "admit")  # _accept_token left the accounting in accept
        return "ok"

    def _fail_claims(self, chunk: list) -> None:
        """Unwind failed prefill claims: invalidate trie pages, free privately
        owned pages, clear the slot, and release the caller."""
        for slot_idx, req, claim in chunk:
            self._unwind_claim(claim)
            window = self.cache.window
            if window is not None and window.held(slot_idx):
                window.release(slot_idx)  # the slot had been given them
            elif window is not None:
                window.free(claim["window_pages"])
            slot = self.slots[slot_idx]
            slot.request = None
            slot.pages = slot.trie_pages = slot.private_pages = []
            slot.ngram = None
            slot.prefill = None
            slot.pending_first = False
            self._active[slot_idx] = False
            self._finish_stream(req, _Finish("error"))

    def _claim_pages(self, req: Request) -> dict | None:
        """Slot page claim with prefix-cache sharing + eviction pressure.
        Runs under the request's ambient trace frame so fault firings in
        here (allocator exhaustion, tier corruption) land as ``fault``
        events on this request."""
        with _rt.active(req.trace, replica=self.trace_name):
            return self._claim_pages_traced(req)

    def _claim_pages_traced(self, req: Request) -> dict | None:
        # fault point (docs/faults.md): allocator exhaustion. The slot path
        # takes the preemption-safe requeue; the disagg prefill_sync path
        # raises OutOfPages and the coordinator falls back to unified.
        if _inject.fire("engine.out_of_pages"):
            return None
        n_prompt = len(req.prompt_tokens)
        max_total = min(n_prompt + req.params.max_tokens, self.max_model_len)
        n_pages = self.cache.pages_for(max_total)
        # multimodal requests key the trie by image-CONTENT hash ids
        # (req.cache_key_tokens) instead of their placeholder prompt ids —
        # identical images share their KV pages, different images land in
        # different trie branches (round 5; vLLM's mm prefix caching works
        # the same way: content-addressed image keys)
        pc = self.prefix_cache
        key_tokens = req.cache_key_tokens or req.prompt_tokens
        shared: list[int] = []
        promoted: list[int] = []
        if pc is not None:
            shared, _ = pc.acquire(key_tokens)
            if self.tiered is not None and shared:
                # per-PAGE units, matching the host/volume counts promote
                # records — the three tiers' hit counters are comparable
                _obs.record_tier_hit("hbm", n=len(shared))
            if self.tiered is not None:
                # lower-tier promotion: consecutive full-prompt pages past
                # the HBM trie hit, restored from host RAM / Volume with
                # their content pre-written — they join the trie as fresh
                # inserts below (refcount 1 via insert)
                promoted = self.tiered.promote(
                    key_tokens, n_have=len(shared)
                )
        need = n_pages - len(shared) - len(promoted)
        try:
            fresh = self.cache.allocator.alloc(need)
        except OutOfPages:
            if pc is not None:
                pc.evict(need)  # reclaim zero-ref cached pages and retry
                try:
                    fresh = self.cache.allocator.alloc(need)
                except OutOfPages:
                    pc.release(shared)
                    self.cache.allocator.free(promoted)
                    return None
            else:
                return None
        # the second budget, where the model keeps its sliding-window layers
        # in a page group of their own: a window's pages and their slack, or
        # fewer for a context that never fills them. Short of it, nothing
        # is held (a model with such a group refuses the prefix cache, so
        # ``fresh`` is the whole claim)
        window_pages: list[int] = []
        if self.cache.window is not None:
            try:
                window_pages = self.cache.window.claim(max_total)
            except OutOfPages:
                self.cache.allocator.free(fresh)
                return None
        pages = shared + promoted + fresh
        # prefix-cache usage accounting (the OpenAI contract's
        # prompt_tokens_details.cached_tokens): prompt tokens served from
        # already-cached KV — trie hits + tier promotions — clamped to the
        # prompt (the last shared page may cover growth positions too)
        req.cached_prompt_tokens = min(
            n_prompt, (len(shared) + len(promoted)) * self.cache.page_size
        )
        trie_pages, private_pages = list(shared), list(promoted) + list(fresh)
        if pc is not None:
            pc.hits += bool(shared)
            pc.misses += not shared
            n_full = n_prompt // self.cache.page_size
            final, displaced = pc.insert(
                key_tokens, pages[:n_full], len(shared)
            )
            self.cache.allocator.free(displaced)
            trie_pages = list(final)
            private_pages = pages[n_full:]  # everything past the full-prompt
            pages = final + private_pages   # pages is trie-tracked
            if self.tiered is not None:
                self.tiered.register(key_tokens, final)
        return {
            "pages": pages,
            "trie_pages": trie_pages,
            "private_pages": private_pages,
            "n_prompt": n_prompt,
            "window_pages": window_pages,
        }

    def _charge_slot_usage(self, slot: _Slot) -> None:
        """Charge the ending tenancy's occupancy interval to its tenant
        (device-seconds + KV page-seconds) — from BOTH release paths, with
        ``claimed_at`` zeroed so no path can double-charge."""
        req = slot.request
        if req is not None and slot.claimed_at > 0:
            self.usage.note_slot_release(
                req,
                pages=len(slot.pages),
                held_s=self._clock() - slot.claimed_at,
            )
        slot.claimed_at = 0.0

    def _install_window_pages(self, slot_idx: int, claim: dict) -> None:
        """The slot's row of the window group's page table: the claim's
        pages (a model with no such group: nothing)."""
        if self.cache.window is not None:
            self.cache.window.install(slot_idx, claim["window_pages"])

    def _release_window_pages(self, slot: _Slot) -> None:
        if self.cache.window is not None:
            # by identity: two slots with equal fields are two slots
            index = next(i for i, s in enumerate(self.slots) if s is slot)
            self.cache.window.release(index)

    def _release_slot_pages(self, slot: _Slot) -> None:
        self._charge_slot_usage(slot)
        if self.prefix_cache is not None:
            self.prefix_cache.release(slot.trie_pages)
            self.cache.allocator.free(slot.private_pages)
        else:
            self.cache.allocator.free(slot.pages)
        self._release_window_pages(slot)
        slot.pages, slot.trie_pages, slot.private_pages = [], [], []
        slot.ngram = None
        slot.prefill = None
        slot.pending_first = False
        slot.spec_hold = False
        if self._spec_ctrl is not None and slot.request is not None:
            # the controller's acceptance EWMA is per-request state: drop
            # it with the tenancy (both release paths call here or
            # _unwind_slot, so nothing leaks)
            self._spec_ctrl.forget(slot.request.request_id)

    def _dispatch_prefill_chunk(
        self, req: Request, table, offset: int, slot_idx: int | None = None,
    ) -> "jax.Array":
        """Dispatch ONE prefill chunk of ``req``'s prompt (async — the
        sampled token comes back as a device future, nothing blocks the
        host): the unit both the atomic loop (``_run_prefill_chunks``) and
        the budgeted state machine (``_advance_pending_prefills``) advance
        by, so the two paths can never drift. The chunk is as wide as the
        bucket that holds what is left of the prompt from ``offset``
        (``_chunk_width``), the draft model's beside it. The program samples
        (``_chunk_jit``): the prompt's last chunk with the request's
        parameters, its (seed, position) and the one key a chunked prompt
        draws from the engine's stream, so its token is the request's first
        however the budget sliced the prompt; an earlier chunk with
        ``_unread_sampler_args`` and the stream's head unsplit, and its token
        is nobody's. ``req.cached_prompt_tokens`` leading prompt tokens sit
        on cached pages (computed again all the same: the count at the
        prefill boundary says so). ``slot_idx``: the slot the prompt fills; a
        model with per-slot state starts the chunk at offset 0 from zeros and
        a later one from what the chunk before it left in that slot."""
        prompt_tokens, cached = req.prompt_tokens, req.cached_prompt_tokens
        key = self._chunk_key(offset)
        width = self._chunk_width(key, len(prompt_tokens) - offset)
        pad_tok = self.tokenizer.pad_id % self.cfg.vocab_size
        chunk = prompt_tokens[offset : offset + width]
        toks = np.full((1, width), pad_tok, np.int32)
        toks[0, : len(chunk)] = chunk
        _obs.record_prefill_positions(
            computed=width,
            needed=max(0, offset + len(chunk) - max(offset, cached)),
        )
        self._count_sparse(lambda: offset + np.arange(len(chunk)), "prefill")
        if self.cache.window is not None:
            _obs.record_kv_window_pages_recycled(
                self.cache.window.recycled(offset, offset + len(chunk))
            )
        unread = (self._key, *self._unread_sampler_args)
        if offset + len(chunk) < len(prompt_tokens):
            sampler = unread
        else:
            p = req.params
            # numpy arrays of the program's own types: a list handed to
            # jnp.asarray is converted by a program of its own on the device
            sampler = (
                self._next_key(),
                np.asarray([p.temperature], np.float32),
                np.asarray([p.top_p], np.float32),
                np.asarray([p.top_k], np.int32),
                np.asarray([_req_seed(req)], np.int32),
                np.asarray([len(prompt_tokens)], np.int32),
            )
        (
            token, self.cache.k_pages, self.cache.v_pages, self.cache.beside,
        ) = self._profiled(
            "prefill_chunk", f"{self._chunk_shape_key(key)}w{width}",
            self._chunk_program(key, width),
        )(
            self.params,
            jnp.asarray(toks),
            self.cache.k_pages,
            self.cache.v_pages,
            jnp.asarray(table[None, :]),
            np.asarray([len(chunk)], np.int32),
            *sampler,
            **self._state_args([] if slot_idx is None else [slot_idx], 1),
            **({"q_offset": np.int32(offset)} if self._runtime_offset else {}),
        )
        if self.spec_mode == "draft":
            (
                _, self.draft_cache.k_pages, self.draft_cache.v_pages, _,
            ) = self._profiled(
                "draft_prefill", f"chunk-off{offset}w{width}",
                self._chunk_program(offset, width, draft=True),
            )(
                self.draft_params,
                jnp.asarray(toks),
                self.draft_cache.k_pages,
                self.draft_cache.v_pages,
                jnp.asarray(table[None, :]),
                np.asarray([len(chunk)], np.int32),
                *unread,  # the draft proposes from the target's first token
            )
        return token

    def _run_prefill_chunks(self, req: Request, table) -> "jax.Array":
        """The atomic chunked-prefill loop (every chunk in one call), used
        by the slot-free disagg path (``_prefill_pages``) — the slot path
        runs the same chunks through the resumable state machine instead.
        Returns the final chunk's sampled token, the request's first."""
        C = self.prefill_buckets[-1]
        token = None
        for offset in range(0, len(req.prompt_tokens), C):
            token = self._dispatch_prefill_chunk(req, table, offset)
        return token

    def _prefill_pages(self, req: Request, claim: dict) -> int:
        """Fill ``claim``'s pages with ``req``'s prompt KV and sample the
        first token — no slot touched (the disagg prefill path). Reuses the
        engine's compiled prefill shapes: short prompts ride row 0 of the
        ``(bucket, prefill_batch)`` program, long prompts take the chunked
        path. Returns the first sampled token."""
        pages, n_prompt = claim["pages"], claim["n_prompt"]
        table = np.zeros((self.pages_per_slot,), np.int32)
        table[: len(pages)] = pages
        p = req.params
        if n_prompt > self.prefill_buckets[-1]:
            first = self._run_prefill_chunks(req, table)
            t0 = self._harvest_begin()
            first = int(np.asarray(first)[0])
            self._harvested(self._dispatch_seq(), "prefill", t0)
            return first
        bucket = self._bucket_for(n_prompt)
        B = self.prefill_batch
        pad_tok = self.tokenizer.pad_id % self.cfg.vocab_size
        tokens = np.full((B, bucket), pad_tok, np.int32)
        tokens[0, :n_prompt] = req.prompt_tokens
        tables = np.zeros((B, self.pages_per_slot), np.int32)
        tables[0] = table
        seq_lens = np.ones((B,), np.int32)
        seq_lens[0] = n_prompt
        temps = np.ones((B,), np.float32)
        top_ps = np.ones((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        seeds = np.full((B,), -1, np.int32)
        temps[0], top_ps[0], top_ks[0] = p.temperature, p.top_p, p.top_k
        seeds[0] = _req_seed(req)
        _obs.record_prefill_positions(
            computed=B * bucket, needed=n_prompt - req.cached_prompt_tokens
        )
        self._count_sparse(lambda: np.arange(n_prompt), "prefill")
        (
            next_tok, self.cache.k_pages, self.cache.v_pages, self.cache.beside,
        ) = self._profiled(
            "prefill", f"b{bucket}x{B}", self._prefill_jit((bucket, B))
        )(
            self.params,
            self.cache.k_pages,
            self.cache.v_pages,
            jnp.asarray(tokens),
            jnp.asarray(tables),
            jnp.asarray(seq_lens),
            self._next_key(),
            jnp.asarray(temps),
            jnp.asarray(top_ps),
            jnp.asarray(top_ks),
            jnp.asarray(seeds),
            **self._state_args([], B),
        )
        t0 = self._harvest_begin()
        first = int(np.asarray(next_tok)[0])
        self._harvested(self._dispatch_seq(), "prefill", t0)
        return first

    def _prefill_long(self, slot_idx: int, req: Request, claim: dict) -> None:
        """Begin a chunked prefill (prompts beyond the largest bucket) as a
        RESUMABLE per-slot state machine: bucket-sized chunks attend to the
        cached prefix via the rectangular flash kernel (llama.prefill_chunk
        — bounded VMEM at any prompt length), and
        ``_advance_pending_prefills`` dispatches at most a budget's worth
        of chunks per tick, so the decode stall other streams see is
        bounded by ONE chunk instead of the whole prompt. Unbudgeted
        engines dispatch every chunk in one tick — but the first-token
        read still defers to the harvest queue, behind the decode
        dispatch."""
        # mtpu_engine_queue_wait_seconds ends at the start of the prefill
        # call, after the tick's whole admission loop, not at admitted_at:
        # the benchmark's queue_wait_p50_ms reads this series
        _obs.record_engine_queue_wait(time.monotonic() - req.created)
        pages = claim["pages"]
        slot = self.slots[slot_idx]
        slot.request = req
        self._tenancy_seq += 1
        slot.tenancy = self._tenancy_seq
        slot.claimed_at = self._clock()
        slot.pages = pages
        slot.trie_pages = claim["trie_pages"]
        slot.private_pages = claim["private_pages"]
        # the slot's generated list IS the request's own history (failover
        # checkpoints are built from the request after the slot is gone);
        # a resumed request arrives with both pre-seeded (docs/failover.md)
        slot.generated = req.generated_tokens
        slot.emitted_text_len = req.emitted_len
        slot.pending_first = False
        if self.spec_mode == "ngram":
            slot.ngram = _NgramIndex(
                self.ngram_n, req.prompt_tokens or [], self.NGRAM_LOOKBACK
            )
        table = np.zeros((self.pages_per_slot,), np.int32)
        table[: len(pages)] = pages
        self._page_tables[slot_idx] = table
        self._install_window_pages(slot_idx, claim)
        slot.prefill = _PendingPrefill(req=req, table=table)

    def _advance_pending_prefills(self, budget: int | None, spent: int) -> int:
        """Advance every mid-flight sliced prefill chunk by chunk until
        ``budget`` prompt tokens have been dispatched this tick (None =
        unlimited). The first chunk of an otherwise-idle tick always
        dispatches, so a budget smaller than one chunk still makes
        progress; slots advance in index order, so earlier admissions
        finish first. Returns the updated token spend."""
        C = self.prefill_buckets[-1]
        for i, s in enumerate(self.slots):
            pp = s.prefill
            if pp is None or s.request is None or s.request.aborted:
                continue  # aborted mid-prefill: the decode-tick reap unwinds
            n_prompt = len(pp.req.prompt_tokens)
            advanced = False
            try:
                while pp.offset < n_prompt and (
                    budget is None or spent == 0 or spent < budget
                ):
                    pp.first_token = self._dispatch_prefill_chunk(
                        pp.req, pp.table, pp.offset, slot_idx=i,
                    )
                    step = min(C, n_prompt - pp.offset)
                    pp.offset += step
                    spent += step
                    advanced = True
                if advanced:
                    pp.ticks += 1
                if pp.offset >= n_prompt:
                    self._finish_sliced_prefill(i, s, pp)
                elif advanced:
                    # paused mid-prompt: the next decode block dispatches
                    # BETWEEN this prompt's chunks — the slice the budget
                    # exists to cut
                    pp.suspensions += 1
                    _obs.record_prefill_sliced()
            except Exception:
                # same contract as the grouped path: a failed chunk must not
                # leave a half-initialized slot, leak its page claim, or
                # poison the trie with partially-written pages
                import traceback

                traceback.print_exc()
                self._fail_slot(i, s.request)
        return spent

    def _finish_sliced_prefill(
        self, slot_idx: int, slot: _Slot, pp: _PendingPrefill
    ) -> None:
        """Every chunk dispatched: park the first token, which the last
        chunk's program sampled (seeded by (request seed, position), so
        slicing can never change it), on the harvest queue — the blocking
        read happens after the next decode dispatch, exactly like a grouped
        prefill's."""
        req = pp.req
        n_prompt = len(req.prompt_tokens)
        slot.prefill = None
        slot.pending_first = True
        self._pending_harvest.append((
            pp.first_token,
            [(slot_idx, req, 0, n_prompt, slot.tenancy)],
            {
                "seq": self._dispatch_seq(),
                "chunks": -(-n_prompt // self.prefill_buckets[-1]),
                "ticks": pp.ticks,
            },
        ))

    def _harvest_prefills(self) -> bool:
        """Materialize parked first tokens (the ONE blocking read per
        prefill dispatch, now overlapping the decode block already queued
        on device) and light their slots up through the fresh-slot
        override lane. Slots recycled while the prefill was in flight
        (abort/deadline unwound them) are skipped by request identity,
        like ``_process_block``'s snapshots."""
        tick = self._tick
        worked = False
        while self._pending_harvest:
            next_tok, rows, meta = self._pending_harvest.popleft()
            t0 = self._harvest_begin()
            try:
                next_np = np.asarray(next_tok)
            except Exception:
                # a prefill that failed ON DEVICE (materialization error):
                # unwind every still-owned slot and release the callers —
                # the no-hang contract of _fail_claims, post-dispatch
                import traceback

                traceback.print_exc()
                _tm(tick, "accept")
                for slot_idx, req, _row, _n, tenancy in rows:
                    s = self.slots[slot_idx]
                    if s.request is req and s.tenancy == tenancy:
                        self._fail_slot(slot_idx, req)
                continue
            # roofline prefill seconds: the harvest span (the dispatch
            # itself is async; this is where the host actually waits on
            # prefill device work)
            self._harvested(meta["seq"], "prefill", t0)
            t_first = _rt.wall(time.monotonic())  # where the prefill spans end
            u_calls = 1  # one dispatched program per harvest entry
            for slot_idx, req, row, n_prompt, tenancy in rows:
                s = self.slots[slot_idx]
                if s.request is not req or s.tenancy != tenancy or req.aborted:
                    # recycled or aborted while the prefill was in flight:
                    # the reap (this tick or the next) owns the unwind —
                    # same identity rule as _process_block's snapshots
                    continue
                s.pending_first = False
                self.stats.prompt_tokens += n_prompt
                # batched admissions share ONE weight stream: the first
                # accounted row carries the program's weight-read bytes
                self.usage.note_prompt(req, n_prompt, calls=u_calls)
                u_calls = 0
                s.position = n_prompt
                # failover resume (docs/failover.md): replay the accepted
                # generated prefix through the decode block program
                # (bit-identical KV), then feed the LAST accepted token —
                # which the client already has — through the override lane
                # instead of the prefill's sampled token, so the next
                # sampled token carries the same (seed, position) key as
                # the uninterrupted run and the stream continues
                # identically
                rs = getattr(req, "_resume_state", None)
                if rs is not None:
                    replay = rs["replay"]
                    self._replay_decode_prefix(slot_idx, replay)
                    s.position = n_prompt + len(replay) - 1
                    s.last_token = int(replay[-1])
                    if self.spec_mode == "draft":
                        # the replay rebuilt TARGET KV only: the draft
                        # cache has a generated-prefix hole, so this
                        # tenancy never proposes (γ pinned 0 — the fused
                        # round's classic lane; token-identical either way)
                        s.spec_hold = True
                else:
                    s.last_token = int(next_np[row])
                s.fresh = True
                worked = True
                t_admitted = _rt.wall(req.admitted_at)
                if "chunks" in meta:
                    sliced = meta["ticks"] > 1
                    _rt.record_span(
                        req.trace, "prefill", start=t_admitted, end=t_first,
                        store=self._trace_store, replica=self.trace_name,
                        n_prompt=n_prompt, chunked=True,
                        chunks=meta["chunks"], sliced=sliced,
                        budget=self.prefill_budget,
                    )
                    if sliced:
                        _rt.record_span(
                            req.trace, "prefill_wait", start=t_admitted,
                            end=t_first,
                            store=self._trace_store, replica=self.trace_name,
                            ticks=meta["ticks"], chunks=meta["chunks"],
                        )
                else:
                    _rt.record_span(
                        req.trace, "prefill", start=t_admitted, end=t_first,
                        store=self._trace_store, replica=self.trace_name,
                        n_prompt=n_prompt, bucket=meta["bucket"],
                    )
                req._decode_span = _rt.begin(
                    req.trace, "decode", start=t_first,
                    replica=self.trace_name, spec_mode=self.spec_mode or "-",
                )
                if rs is not None:
                    # resumed: the fed token was already accepted and its
                    # text emitted before the failure (slot.generated /
                    # emitted_text_len carry the history from the install)
                    req._resume_state = None
                else:
                    self._accept_token(slot_idx, s.last_token)
        return worked

    def _replay_decode_prefix(self, slot_idx: int, replay: list) -> None:
        """Teacher-forced KV rebuild for a failover-resumed request
        (docs/failover.md): feed each already-accepted token except the
        last through THE decode block program — the override lane, only
        this slot active — one token per dispatch. Because it is the same
        compiled body the original run executed, with the same carry
        inputs (attention is position-bounded, so the block's trailing
        sampled-garbage writes at positions not yet fed are invisible and
        overwritten when those positions ARE fed), the rebuilt KV is
        BIT-IDENTICAL to what the dead replica's decode wrote — a prefill
        recompute of the same positions drifts by a bf16 rounding
        asymmetry (prefill attends over unrounded in-graph k/v; decode
        reads the rounded cache) and deterministically flips greedy
        argmaxes at unlucky margins. All dispatches are async: the replay
        queues device work without blocking the scheduler thread."""
        if len(replay) <= 1:
            return  # generated[-1] rides the override lane of live decode
        base_pos = self.slots[slot_idx].position
        B = self.max_slots
        active = np.zeros((B,), bool)
        active[slot_idx] = True
        mask = np.zeros((B,), bool)
        mask[slot_idx] = True
        override = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        prev = jnp.zeros((B,), jnp.int32)
        # sampling args are irrelevant to the KV writes (the scatter uses
        # the FED token; sampled outputs are discarded) — defaults keep
        # sample() off its expensive filter path
        ones = jnp.ones((B,), jnp.float32)
        zeros_i = jnp.zeros((B,), jnp.int32)
        no_seed = jnp.full((B,), -1, jnp.int32)
        tables = jnp.asarray(self._page_tables.copy())
        for i, tok in enumerate(replay[:-1]):
            override[slot_idx] = int(tok)
            positions[slot_idx] = base_pos + i
            self._count_decode_kv(positions, active, self.decode_block)
            (
                _toks, _last, self.cache.k_pages, self.cache.v_pages,
                self.cache.beside,
            ) = (
                self._profiled(
                    "block", f"s{self.max_slots}k{self.decode_block}",
                    self._block_jit,
                )(
                    self.params,
                    self.cache.k_pages,
                    self.cache.v_pages,
                    prev,
                    jnp.asarray(override.copy()),
                    jnp.asarray(mask.copy()),
                    jnp.asarray(positions.copy()),
                    tables,
                    jnp.asarray(active.copy()),
                    self._next_key(),
                    ones,
                    ones,
                    zeros_i,
                    no_seed,
                    **self._state_args(),
                )
            )

    def _fail_slot(self, slot_idx: int, req: Request) -> None:
        """Release one mid-prefill slot whose work failed AFTER dispatch
        (chunk advance or harvest): unwind from the slot's own page lists
        and fail the caller loudly — the one sequence shared by every
        post-dispatch prefill failure path."""
        s = self.slots[slot_idx]
        self._unwind_slot(s)
        s.request = None
        self._active[slot_idx] = False
        self._finish_stream(req, _Finish("error"))

    def _unwind_slot(self, slot: _Slot) -> None:
        """Unwind a slot whose prefill never completed (abort, deadline, or
        failure mid-chunk / pre-harvest): the ``_fail_claims`` ownership
        rule, reconstructed from the slot's own page lists — trie pages
        invalidated so no later request can share never-/partially-written
        KV, exclusively-owned pages freed."""
        self._charge_slot_usage(slot)
        self._unwind_claim({
            "pages": slot.pages,
            "trie_pages": slot.trie_pages,
            "private_pages": slot.private_pages,
        })
        self._release_window_pages(slot)
        slot.pages, slot.trie_pages, slot.private_pages = [], [], []
        slot.prefill = None
        slot.pending_first = False
        slot.ngram = None
        slot.spec_hold = False
        if self._spec_ctrl is not None and slot.request is not None:
            self._spec_ctrl.forget(slot.request.request_id)

    def _prefill_group(self, bucket: int, group: list, is_mm: bool = False) -> None:
        t_start = time.monotonic()
        for _slot_idx, req, _claim in group:
            _obs.record_engine_queue_wait(t_start - req.created)  # as _prefill_long
        B = self.prefill_batch  # fixed compile shape; short groups pad
        pad_tok = self.tokenizer.pad_id % self.cfg.vocab_size
        tokens = np.full((B, bucket), pad_tok, np.int32)
        tables = np.zeros((B, self.pages_per_slot), np.int32)  # pad rows: trash
        seq_lens = np.ones((B,), np.int32)
        temps = np.ones((B,), np.float32)
        top_ps = np.ones((B,), np.float32)
        top_ks = np.zeros((B,), np.int32)
        seeds = np.full((B,), -1, np.int32)
        images = None
        if is_mm:
            S = self.vision_cfg.vision.image_size
            images = np.zeros((B, S, S, 3), np.float32)
        for i, (slot_idx, req, claim) in enumerate(group):
            pages, n_prompt = claim["pages"], claim["n_prompt"]
            slot = self.slots[slot_idx]
            slot.request = req
            self._tenancy_seq += 1
            slot.tenancy = self._tenancy_seq
            slot.claimed_at = self._clock()
            slot.pages = pages
            slot.trie_pages = claim["trie_pages"]
            slot.private_pages = claim["private_pages"]
            slot.generated = req.generated_tokens  # request-owned history
            slot.emitted_text_len = req.emitted_len
            slot.prefill = None
            slot.spec_hold = False
            if self.spec_mode == "ngram":
                slot.ngram = _NgramIndex(
                    self.ngram_n, req.prompt_tokens or [], self.NGRAM_LOOKBACK
                )
                for t in req.generated_tokens:
                    # failover-resumed requests arrive with accepted
                    # history (replayed at harvest): the lookup index must
                    # match an uninterrupted run's
                    slot.ngram.push(int(t))
            table = np.zeros((self.pages_per_slot,), np.int32)
            table[: len(pages)] = pages
            self._page_tables[slot_idx] = table
            self._install_window_pages(slot_idx, claim)
            tokens[i, :n_prompt] = req.prompt_tokens
            tables[i] = table
            seq_lens[i] = n_prompt
            p = req.params
            temps[i], top_ps[i], top_ks[i] = p.temperature, p.top_p, p.top_k
            seeds[i] = _req_seed(req)
            if is_mm:
                images[i] = req.image
        _obs.record_prefill_positions(
            computed=B * bucket,
            needed=sum(
                claim["n_prompt"] - req.cached_prompt_tokens
                for _slot_idx, req, claim in group
            ),
        )
        self._count_sparse(
            lambda: np.concatenate([np.arange(c["n_prompt"]) for _i, _r, c in group]),
            "prefill",
        )

        if is_mm:
            next_tok, self.cache.k_pages, self.cache.v_pages = (
                self._profiled(
                    "prefill_mm", f"b{bucket}x{B}",
                    self._prefill_mm_jit((bucket, B)),
                )(
                    self.params,
                    self.vision_params,
                    self.cache.k_pages,
                    self.cache.v_pages,
                    jnp.asarray(images),
                    jnp.asarray(tokens),
                    jnp.asarray(tables),
                    jnp.asarray(seq_lens),
                    self._next_key(),
                    jnp.asarray(temps),
                    jnp.asarray(top_ps),
                    jnp.asarray(top_ks),
                    jnp.asarray(seeds),
                )
            )
        else:
            (
                next_tok, self.cache.k_pages, self.cache.v_pages,
                self.cache.beside,
            ) = self._profiled(
                "prefill", f"b{bucket}x{B}", self._prefill_jit((bucket, B))
            )(
                self.params,
                self.cache.k_pages,
                self.cache.v_pages,
                jnp.asarray(tokens),
                jnp.asarray(tables),
                jnp.asarray(seq_lens),
                self._next_key(),
                jnp.asarray(temps),
                jnp.asarray(top_ps),
                jnp.asarray(top_ks),
                jnp.asarray(seeds),
                # a row's recurrent state, from zeros, goes to its slot
                **self._state_args(
                    [slot_idx for slot_idx, _req, _claim in group], B
                ),
            )
        if self.spec_mode == "draft":
            # fill the draft model's cache over the same pages (same tables:
            # page ids are shared between the two caches)
            _, self.draft_cache.k_pages, self.draft_cache.v_pages = (
                self._profiled(
                    "draft_prefill", f"b{bucket}x{B}",
                    self._draft_prefill_jit((bucket, B)),
                )(
                    self.draft_params,
                    self.draft_cache.k_pages,
                    self.draft_cache.v_pages,
                    jnp.asarray(tokens),
                    jnp.asarray(tables),
                    jnp.asarray(seq_lens),
                )
            )
        # first tokens stay ON DEVICE: park (next_tok, group) for harvest
        # after the next decode dispatch — the host never blocks on a
        # prefill read here, so already-running streams keep their cadence
        # (this used to be a blocking np.asarray that stalled every
        # in-flight stream for the whole prefill duration)
        rows = []
        for i, (slot_idx, req, claim) in enumerate(group):
            self.slots[slot_idx].pending_first = True
            rows.append((
                slot_idx, req, i, claim["n_prompt"],
                self.slots[slot_idx].tenancy,
            ))
        self._pending_harvest.append((
            next_tok,
            rows,
            {"seq": self._dispatch_seq(), "bucket": bucket},
        ))

    def _decode_tick(self) -> bool:
        tick = self._tick
        _tm(tick, "policy")
        # fault point (docs/faults.md): one stalled decode tick — a slow
        # collective, a preempted host thread. Latency only; the tick then
        # proceeds normally and requests still terminate.
        if _inject.fire("engine.slow_decode"):
            for s in self.slots:
                if s.request is not None:
                    _rt.event(
                        s.request.trace, "fault", store=self._trace_store,
                        replica=self.trace_name, point="engine.slow_decode",
                    )
            time.sleep(0.05)
        # reap aborted slots before spending a step on them (deadline-
        # expired aborts finish with their own reason, not a fake "stop")
        for i, s in enumerate(self.slots):
            if not s.free and s.request.aborted:
                req = s.request
                self._finish_stream(
                    req,
                    _Finish("deadline") if req.deadline_expired else _FINISH,
                )
                if s.prefill is not None or s.pending_first:
                    # the abort landed mid-prefill (sliced chunks pending,
                    # or first token unharvested): pages may hold PARTIAL
                    # KV — unwind the claim (trie pages invalidated) rather
                    # than releasing them as valid, shareable prefix KV
                    self._unwind_slot(s)
                else:
                    self._release_slot_pages(s)
                s.request = None
                self._active[i] = False
        live = [i for i, s in enumerate(self.slots) if s.decodable]

        if self.spec_gamma:
            # no pipelined dispatch to protect in spec mode: harvest first
            # so freshly prefilled slots join this very tick
            worked = self._harvest_prefills()
            live = [i for i, s in enumerate(self.slots) if s.decodable]
            if not live:
                return worked
            _tm(tick, "admit")  # spec batch staging: slot-state bookkeeping
            self._active[:] = False
            # reset dead-slot sampling params (same rationale as
            # _dispatch_block: stale top_p/top_k keeps sample()'s runtime
            # lax.cond on the expensive sort path)
            self._temps[:] = 1.0
            self._top_ps[:] = 1.0
            self._top_ks[:] = 0
            self._seeds[:] = -1
            gammas = np.zeros((self.max_slots,), np.int32)
            batch_fill = len(live) / max(1, self.max_slots)
            # prefill-budget contention (docs/scheduling.md): chunked
            # prefills mid-slice or first tokens parked unharvested mean
            # admission cadence is live — long speculative rounds would
            # stretch the tick it rides on
            prefill_pressure = bool(self._pending_harvest) or any(
                s.prefill is not None for s in self.slots
            )
            for i in live:
                s = self.slots[i]
                self._active[i] = True
                self._tokens[i] = s.last_token
                self._positions[i] = s.position
                s.fresh = False  # spec rounds feed host tokens directly
                p = s.request.params
                self._temps[i] = p.temperature
                self._top_ps[i] = p.top_p
                self._top_ks[i] = p.top_k
                self._seeds[i] = _req_seed(s.request)
                gammas[i] = self._slot_gamma(s, batch_fill, prefill_pressure)
            ngram_props = None
            if self.spec_mode == "ngram":
                # proposal availability is host-known BEFORE dispatch: a
                # lane whose trailing-ngram lookup comes up empty has
                # nothing to verify, so its γ drops to 0 (the fused
                # program's classic lane) — and an all-empty round falls
                # through to the strictly-cheaper block program below
                # instead of paying a 1-token spec round. No controller
                # involvement: an empty lookup is absence of evidence,
                # not rejection evidence (docs/speculative.md#gamma-
                # schedule).
                ngram_props = self._ngram_proposals(gammas)
                gammas = np.minimum(
                    gammas, ngram_props[1].astype(np.int32)
                )
            if not any(gammas[i] for i in live):
                # whole-round fallback: nobody speculates this round
                # (pressure, collapse, or sampling lanes only) — the
                # classic block program is strictly cheaper than a
                # γ-shaped verify pass, so spec can never COST latency
                self._spec_fallbacks += 1
                for i in live:
                    # re-enter the block program through the override lane
                    # (spec rounds end on host-known tokens, not
                    # device-resident ones)
                    self.slots[i].fresh = True
                self._dispatch_block(live)
                worked = self._harvest_prefills() or True
                return self._process_block() or worked
            return self._spec_round(live, gammas, ngram_props) or worked

        # pipelined path: keep one decode block in flight ahead of the one
        # being read, so the device never waits on the host round trip
        worked = False
        if live:
            self._dispatch_block(live)
            worked = True
        else:
            # no decodable slots: a dispatch gap from here on is idleness
            # or prefill ramp-up, not a stall against live streams
            self._last_dispatch_at = None
        # harvest AFTER the dispatch: the blocking first-token reads overlap
        # the decode block already queued on device — the deferral that
        # makes admission stall-free
        worked = self._harvest_prefills() or worked
        if self._inflight and (len(self._inflight) >= 2 or not live):
            worked = self._process_block() or worked
        return worked

    def _dispatch_block(self, live: list[int]) -> None:
        """Queue one decode block (async — returns before it runs).

        Slot-state lag safety: a slot that finishes (eos/stop/length) while
        an already-dispatched block still decodes it only ever writes
        generated-position KV, i.e. its own private pages; if those pages are
        freed and reclaimed, the reclaimer's prefill is dispatched AFTER this
        block (device program order) and overwrites the stale writes. The
        per-block snapshot pins request identity so the host drops output
        rows whose slot was recycled.
        """
        _tm(self._tick, "decode_dispatch")
        now = self._clock()
        if self._last_dispatch_at is not None:
            # dispatch-to-dispatch gap while decodable slots existed the
            # whole time: the stall the prefill budget bounds to ~one chunk
            _obs.record_decode_stall(now - self._last_dispatch_at)
        self._last_dispatch_at = now
        self.watermarks.note_dispatch()
        _obs.record_engine_batch(len(live))
        self._active[:] = False
        self._override_mask[:] = False
        # reset dead-slot sampling params to the no-filter defaults: a stale
        # top_p/top_k from a finished request would keep sample()'s runtime
        # lax.cond on the expensive sort path for every later block
        self._temps[:] = 1.0
        self._top_ps[:] = 1.0
        self._top_ks[:] = 0
        self._seeds[:] = -1
        for i in live:
            s = self.slots[i]
            self._active[i] = True
            if s.fresh:
                # freshly prefilled: first token is host-known (sampled by
                # the prefill program); continuing slots feed the previous
                # block's device-resident token
                self._override[i] = s.last_token
                self._override_mask[i] = True
                self._opt_positions[i] = s.position
                s.fresh = False
            self._positions[i] = self._opt_positions[i]
            p = s.request.params
            self._temps[i] = p.temperature
            self._top_ps[i] = p.top_p
            self._top_ks[i] = p.top_k
            self._seeds[i] = _req_seed(s.request)
        prev = self._device_tokens
        if prev is None:
            prev = jnp.zeros((self.max_slots,), jnp.int32)
        (
            toks, last, self.cache.k_pages, self.cache.v_pages,
            self.cache.beside,
        ) = self._profiled(
            "block", f"s{self.max_slots}k{self.decode_block}",
            self._block_jit,
        )(
            self.params,
            self.cache.k_pages,
            self.cache.v_pages,
            prev,
            jnp.asarray(self._override.copy()),
            jnp.asarray(self._override_mask.copy()),
            jnp.asarray(self._positions.copy()),
            jnp.asarray(self._page_tables.copy()),
            jnp.asarray(self._active.copy()),
            self._next_key(),
            jnp.asarray(self._temps.copy()),
            jnp.asarray(self._top_ps.copy()),
            jnp.asarray(self._top_ks.copy()),
            jnp.asarray(self._seeds.copy()),
            **self._state_args(),
        )
        n = self.decode_block
        self._count_decode_kv(self._positions, self._active, n)
        if self.cache.state:
            # the per-slot state a block's steps read and write: every slot's
            # rows each step, those of a running sequence among them
            _obs.record_state_rows(
                stepped=self.max_slots * n, live=len(live) * n
            )
        self._device_tokens = last
        # snapshot pins (slot, request, tenancy): request identity alone is
        # not enough — a failover-resumed request is the same object back
        # in a NEW tenancy, and this block belongs to its old one
        self._inflight.append((
            toks,
            None,  # valid: a block's every row is a token
            [
                (i, self.slots[i].request, self.slots[i].tenancy)
                for i in live
            ],
            None,  # spec_meta: a block carries none
            self._dispatch_seq(),
        ))
        for i in live:
            self._opt_positions[i] += n

    def _process_block(self) -> bool:
        tick = self._tick
        toks, valid, snapshot, spec_meta, seq = self._inflight.popleft()
        t0 = self._harvest_begin()
        toks_np = np.asarray(toks)  # [K, B] — the ONE blocking read per block
        if self._block_counts and valid is None and spec_meta is None:
            # the classic block of a model that counts on the device carries
            # each count as two rows after the tokens: the same read brought them
            n = 2 * len(self._block_counts)
            counted, toks_np = toks_np[-n:, 0].reshape(-1, 2), toks_np[:-n]
            for kind, (first, second) in zip(self._block_counts, counted.tolist()):
                if kind == "routed_pairs":  # [held, all]
                    _obs.record_routed_pairs(held=first, elsewhere=second - first)
                else:  # [pairs, rows]
                    _obs.record_expert_tile_rows(pairs=first, rows=second)
        # the harvest plane (docs/speculative.md#the-harvest-boundary): a
        # speculative round's validity mask rides the SAME round trip as the
        # tokens — per-slot accept stops at the first invalid row (the lane's
        # accept cut); a block carries no mask, its every row is a token
        valid_np = None if valid is None else np.asarray(valid)
        self._harvested(seq, "decode", t0)
        n_steps = int(toks_np.shape[0])
        # only rows with a live lane executed: count the truth, not the
        # program length. A spec round is ONE verify pass regardless of
        # how many chain rows it emitted.
        executed = (
            n_steps if valid_np is None
            else int(valid_np.any(axis=1).sum())
        )
        self.stats.steps += 1 if spec_meta is not None else executed
        worked = False
        accepted = 0
        for i, req, tenancy in snapshot:
            s = self.slots[i]
            if s.request is not req or s.tenancy != tenancy or req.aborted:
                continue  # slot finished/recycled while the block was in flight
            taken = 0
            for k in range(n_steps):
                if s.request is not req or s.tenancy != tenancy:
                    break  # finished mid-block
                if valid_np is not None and not valid_np[k, i]:
                    break  # past the lane's accept cut: the tail rows are holds
                s.position += 1
                s.last_token = int(toks_np[k, i])
                self._accept_token(i, s.last_token)
                taken += 1
                worked = True
            accepted += taken
            if spec_meta is not None:
                n_p = int(spec_meta["proposed"][i])
                acc = max(0, taken - 1)
                self.stats.spec_proposed += n_p
                self.stats.spec_accepted += acc
                if req.trace is not None:
                    _rt.event(
                        req.trace, "spec_verify",
                        store=self._trace_store, replica=self.trace_name,
                        proposed=n_p, accepted=acc,
                        gamma=int(spec_meta["gammas"][i]),
                    )
                if s.request is req and s.tenancy == tenancy:
                    if self._spec_ctrl is not None and n_p > 0:
                        # the controller sees exactly what the host
                        # accepted (stop/length cuts included): its EWMA
                        # tracks USEFUL acceptance, not device acceptance
                        self._spec_ctrl.observe(req.request_id, n_p, acc)
                    # the round ended on a host-known token: the next
                    # dispatch (spec or classic fallback) re-feeds it
                    # through the fresh-slot override lane
                    s.fresh = True
        if spec_meta is not None:
            # spec rounds keep their own tokens-per-dispatch plane
            # (docs/speculative.md#series): γ=0 fallback ROUNDS are counted
            # in _decode_tick, not here — this is a dispatched spec round
            self._spec_rounds += 1
            self._spec_round_tokens += accepted
            gw = self._spec_gamma_window
            for i, _req, _tenancy in snapshot:
                gw.append(int(spec_meta["gammas"][i]))
            if len(gw) > 4096:
                del gw[: len(gw) - 4096]
        prof = self.profiler
        if prof is not None:
            prof.note_dispatch_tokens(accepted)
        return worked

    def _slot_gamma(
        self, s: _Slot, batch_fill: float, prefill_pressure: bool
    ) -> int:
        """Per-slot proposal budget for the next fused round
        (docs/speculative.md#gamma-schedule). 0 = the classic lane inside
        the same program. Sampling lanes (temperature > 0) never
        speculate — the spec accept path is not (seed, position)-keyed,
        and the classic lane keeps them token-identical to a non-spec
        engine; ``spec_hold`` pins resumed/adopted draft-mode tenancies
        whose draft cache has a KV hole."""
        p = s.request.params
        if p.temperature > 0 or s.spec_hold:
            return 0
        cap = max(0, min(int(self.spec_depth), self.spec_gamma))
        if self.spec_adaptive and self._spec_ctrl is not None:
            g = self._spec_ctrl.gamma_for(
                s.request.request_id,
                gamma_cap=cap,
                batch_fill=batch_fill,
                prefill_pressure=prefill_pressure,
            )
        else:
            g = cap
        # never propose past the request's own stopping point: tokens
        # beyond max_tokens / context length would be verified, then
        # discarded by the host accept loop — pure wasted verify flops
        room = min(
            p.max_tokens - len(s.generated) - 1,
            (self.max_model_len - 1) - s.position - 1,
        )
        return max(0, min(g, room))

    def _spec_round(self, live: list[int], gammas, ngram_props=None) -> bool:
        """One fused speculative round (docs/speculative.md#program-shape):
        propose(γ) + verify + accept in ONE dispatch, harvested through
        the SAME ``_process_block`` site as decode blocks (its [N, B]
        validity plane beside the tokens). Spec rounds never pipeline — the next round's
        positions depend on this round's acceptance — so the block is
        processed immediately after dispatch."""
        _tm(self._tick, "decode_dispatch")
        now = self._clock()
        if self._last_dispatch_at is not None:
            _obs.record_decode_stall(now - self._last_dispatch_at)
        self._last_dispatch_at = now
        self.watermarks.note_dispatch()
        _obs.record_engine_batch(len(live))
        gam = jnp.asarray(gammas)
        if self.spec_mode == "ngram":
            # _decode_tick already ran the lookup to γ-clamp empty lanes
            props, n_prop = (
                ngram_props
                if ngram_props is not None
                else self._ngram_proposals(gammas)
            )
            (
                toks, valid, last, self.cache.k_pages, self.cache.v_pages,
            ) = self._profiled(
                "ngram_verify", f"s{self.max_slots}g{self.spec_gamma}",
                self._ngram_jit,
            )(
                self.params,
                self.cache.k_pages,
                self.cache.v_pages,
                jnp.asarray(props),
                jnp.asarray(n_prop),
                gam,
                jnp.asarray(self._tokens.copy()),
                jnp.asarray(self._positions.copy()),
                jnp.asarray(self._page_tables.copy()),
                jnp.asarray(self._active.copy()),
                self._next_key(),
                jnp.asarray(self._temps.copy()),
                jnp.asarray(self._top_ps.copy()),
                jnp.asarray(self._top_ks.copy()),
                jnp.asarray(self._seeds.copy()),
            )
            proposed = n_prop
        else:
            (
                toks, valid, last,
                self.cache.k_pages, self.cache.v_pages,
                self.draft_cache.k_pages, self.draft_cache.v_pages,
            ) = self._profiled(
                "spec_verify", f"s{self.max_slots}g{self.spec_gamma}",
                self._spec_jit,
            )(
                self.params,
                self.draft_params,
                self.cache.k_pages,
                self.cache.v_pages,
                self.draft_cache.k_pages,
                self.draft_cache.v_pages,
                jnp.asarray(self._tokens.copy()),
                jnp.asarray(self._positions.copy()),
                jnp.asarray(self._page_tables.copy()),
                jnp.asarray(self._active.copy()),
                gam,
                self._next_key(),
                jnp.asarray(self._temps.copy()),
                jnp.asarray(self._top_ps.copy()),
                jnp.asarray(self._top_ks.copy()),
                jnp.asarray(self._seeds.copy()),
            )
            # the draft proposes its full budget in-graph (capacity-died
            # lanes are masked by prop_valid and never accepted, but they
            # were still paid for — count them as proposed)
            proposed = gammas
        del last  # spec rounds end on host-known tokens (fresh resync)
        self._device_tokens = None
        self._inflight.append((
            toks,
            valid,
            [
                (i, self.slots[i].request, self.slots[i].tenancy)
                for i in live
            ],
            {"gammas": gammas, "proposed": proposed},
            self._dispatch_seq(),
        ))
        return self._process_block()

    def _accept_token(self, slot_idx: int, token: int) -> None:
        slot = self.slots[slot_idx]
        req = slot.request
        # canary drift injection: deterministically flip ONE accepted token,
        # gated on the synthetic probe tenant so user-visible streams (and
        # the chaos harness's token-identity invariant) are never corrupted —
        # only the golden-set comparison sees the flip
        if req.tenant == _CANARY_TENANT and _inject.fire(
            "engine.canary_token_corrupt"
        ):
            token = (token + 1) % self.cfg.vocab_size
        self.stats.generated_tokens += 1
        # usage meter: same site as the stats counter (conservation is
        # structural); slot.position is the context the decode attended over
        self.usage.note_token(req, slot.position)
        self.watermarks.note_accept()
        # token-level latency: TTFT on the request's first token, the
        # inter-token gap (TPOT) on every later one. Honest wall-clock from
        # the client's seat: pipelined blocks emit in bursts, and the
        # histogram shows exactly that.
        now = time.monotonic()
        # canary probes keep their first/last-token bookkeeping (the prober
        # measures client-side) but must NOT feed the unlabeled TTFT/TPOT
        # histograms: those drive the SLO burn gauges and the autoscaler,
        # and synthetic probes would pollute both. Canary latency lands in
        # the dedicated canary histograms instead.
        if req.first_token_at is None:
            req.first_token_at = now
            if req.tenant != _CANARY_TENANT:
                _obs.record_ttft(now - req.created)
                if req.admitted_at is not None:
                    _obs.record_first_token_wait(now - req.admitted_at)
            if req.trace is not None:
                req.trace.root.attrs["ttft_s"] = round(now - req.created, 6)
        elif req.tenant != _CANARY_TENANT:
            _obs.record_tpot(now - req.last_token_at)
        req.last_token_at = now
        req.n_generated += 1
        finished = False
        reason = None
        appended = token != self.tokenizer.eos_id
        if not appended:
            finished, reason = True, "stop"
        else:
            slot.generated.append(token)
            if slot.ngram is not None:
                slot.ngram.push(token)  # O(1) prompt-lookup index update
            if len(slot.generated) >= req.params.max_tokens:
                finished, reason = True, "length"
            elif slot.position + 1 >= self.max_model_len:
                finished, reason = True, "length"

        # a speculating engine (docs/speculative.md#the-harvest-boundary):
        # token-level bookkeeping above stays on the scheduler thread — the
        # harvest boundary — but detokenization, stop-string scanning, and
        # emission move to the DetokWorker. A stream the worker owns keeps
        # routing through it (ordering); a dead worker falls through to the
        # inline path below.
        w = self._detok
        if self.spec_gamma > 0 or (w is not None and w.owns(req)):
            if w is None or not w.alive:
                w = self._ensure_detok()
            if w.alive:
                tick = self._tick
                _tm_inner(tick, "detokenize")
                if not w.owns(req):
                    prior = (
                        slot.generated[:-1] if appended
                        else list(slot.generated)
                    )
                    w.register(
                        req, prior,
                        max(slot.emitted_text_len, req.emitted_len),
                    )
                if appended:
                    w.feed(req, token)
                # enqueue cost only: the decode itself runs off-thread
                _tm_inner(tick, "accept")
                if finished:
                    # release BEFORE the finish marker is enqueued: the
                    # worker thread can deliver it (and wake the client)
                    # ahead of the scheduler's next bytecode, and a
                    # client-visible finish must imply pages/slot freed
                    self._release_slot_pages(slot)
                    slot.request = None
                    self._active[slot_idx] = False
                    self._finish_stream(req, _Finish(reason))
                return

        # incremental detokenization: emit the stable new suffix. Profiled
        # as its own phase (the ROADMAP #3 "move detokenization off the
        # scheduler thread" candidate needs its cost attributed first):
        # the decode call itself is detokenize, all around it accept.
        tick = self._tick
        _tm_inner(tick, "detokenize")
        text = self.tokenizer.decode(slot.generated)
        _tm_inner(tick, "accept")
        if req.params.stop:
            for stop_s in req.params.stop:
                idx = text.find(stop_s)
                if idx >= 0:
                    text = text[:idx]
                    finished, reason = True, "stop"
                    break
        # hold back any trailing text that is still a prefix of a stop string
        # (OpenAI/vLLM contract: stop='END' arriving as 'E','N','D' must not
        # leak 'EN' into the stream before the match completes)
        safe_len = (
            len(text)
            if finished
            else _stop_safe_len(text, req.params.stop)
        )
        new = text[slot.emitted_text_len : safe_len]
        if new and (finished or not _unstable_tail(new)):
            req.out_queue.put(new)
            slot.emitted_text_len = slot.emitted_text_len + len(new)
            # mirror onto the request: a failover checkpoint taken after
            # this replica dies resumes emission from exactly this cursor
            req.emitted_len = slot.emitted_text_len
        if finished:
            # same release-before-finish ordering as the worker branch
            # above: a client that wakes on the marker must observe the
            # slot and its pages already freed
            self._release_slot_pages(slot)
            slot.request = None
            self._active[slot_idx] = False
            self._finish_stream(req, _Finish(reason))


def build_engine(
    model: str = "llama2-7b",
    model_dir: str | None = None,
    **engine_kw,
) -> LLMEngine:
    """Factory mirroring the reference's MODEL_NAME/engine-flags surface
    (vllm_inference.py:54-58,168-209)."""
    if model_dir is not None:
        cfg = llama.LlamaConfig.from_hf_config(f"{model_dir}/config.json")
    else:
        if model not in MODEL_PRESETS:
            raise ValueError(
                f"unknown model preset {model!r}; known: {sorted(MODEL_PRESETS)}"
            )
        cfg = MODEL_PRESETS[model]()
    return LLMEngine(cfg, model_dir=model_dir, **engine_kw)
