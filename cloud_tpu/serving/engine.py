"""Continuous-batching serving engine over the generation path.

``models.generation`` can decode a *batch* of prompts as one compiled
program, but traffic arrives one request at a time; serving economics on
TPU hinge on the gap between those two facts (batched decode occupancy
amortizes the weight reads every decode step re-pays — arxiv 2605.25645,
arxiv 2309.08918).  :class:`ServingEngine` closes the gap in-process,
with one scheduler behind a submit/future/admission surface:

* **Continuous batching** —
  iteration-level scheduling over a persistent decode grid: a static
  ``(num_slots, max_len)`` KV cache plus per-slot ``{position,
  remaining, active}`` state lives on the device for the engine's whole
  life.  Decode runs in fixed-size token chunks (ONE compiled
  ``generation.decode_chunk_program`` scanning ``chunk_tokens`` steps
  over every slot); between chunks the scheduler retires finished slots
  — per-request ``max_new_tokens`` exhausted or eos sampled, the slot
  deactivates *mid-chunk* via the active mask — completes their futures
  immediately, and prefills queued requests into the freed slots
  (``generation.insert_slot_program``, one program per prompt bucket,
  at the request's own bucket length).  A short request never rides out
  a long neighbor's decode: occupancy is a steady-state quantity
  instead of the batch-synchronous sawtooth (Orca-style iteration
  scheduling — arxiv 2605.25645).
* **Prefix caching** (``prefix_cache_blocks > 0``) —
  requests sharing a prompt prefix (system prompts, few-shot headers)
  share its KV bytes: a radix/token-trie manager
  (``serving.prefix_cache``) keys a device pool of KV blocks by
  token-id prefixes with ref-counting and LRU leaf eviction; on
  admission the scheduler copies the longest cached prefix into the
  slot row (``generation.copy_prefix_program``) and prefills only the
  uncached suffix, then saves the prompt's new full blocks back.
  Greedy outputs stay token-identical to a cold prefill — a hit moves
  compute, never tokens.  A **host-DRAM second tier**
  (``prefix_dram_blocks > 0``) makes HBM eviction a demotion: the
  block's bytes move to a bounded host-side pool and swap back in
  asynchronously on a later hit (``serve/prefix_swapin``), with the
  match-vs-acquire revalidation extended so a swap-in that loses the
  race falls back to a cold prefill — docs/serving.md "Tiered prefix
  cache".
* **Chunked prefill** (``prefill_chunk_tokens``) —
  prompt prefill splits into bounded chunks
  (``generation.prefill_chunk_program``) the scheduler interleaves
  with decode chunks, one prefill chunk per pass: a long arrival
  stalls in-flight decode by at most one chunk dispatch instead of one
  full prefill (the TTFT/tail-latency knob).  Both knobs default OFF —
  the PR 5 one-shot insert path is the compatibility default.
* **Sharded serving** (``mesh_shape=(tp, sp)`` / ``layout="auto"``) —
  one replica spans a multi-chip slice: the whole slot-grid program
  family runs under a TP(xSP) mesh with params sharded per the rules
  table (heads/mlp/vocab over ``tp``), the slot KV cache and prefix
  block pool sharded by attention head, and logits resharded to
  replicated exactly once per forward, at the sampling boundary
  (spanned host-side as ``serve/reshard``).  The layout comes from
  ``parallel.planner.plan_serve_layout`` under ``layout="auto"``
  (model head count x slice shape x HBM budget — the AMP-style search
  already driving training); ``tp`` must divide ``num_heads`` (typed
  error).  Unset / ``(1, 1)`` keeps the single-chip path
  byte-identical, and greedy outputs on any slice are token-identical
  to single-chip ``generate()`` — docs/serving.md "Sharded serving".
* **Speculative decoding** (``draft=DraftConfig(...)``) —
  draft-and-verify on the slot grid: a small draft model
  proposes a ``spec_k``-token window per active slot
  (``generation.draft_chunk_program`` over the draft's own slot cache),
  and the target model scores every window position in ONE chunked
  dispatch (``generation.verify_chunk_program``), committing the
  greedily-accepted prefix and rewinding past the first mismatch.
  Greedy outputs stay token-identical to the non-speculative engine —
  every committed token is the target's own argmax; the draft only
  decides how many of them one dispatch commits — so the win metric is
  accepted-tokens/sec with target-dispatches-per-token < 1.
  ``draft=None`` (default) is byte-identical to the non-speculative
  path; ``spec_k=1`` is a pure-overhead test knob.  ``health()`` and
  ``stats()`` report a rolling/cumulative acceptance rate.
* **AOT warmup** — the programs are enumerable, so ``warmup=True``
  pre-compiles them through ``training.compile_cache`` (the trainer's
  AOT registry + background worker) at engine start: one insert program
  per prompt bucket plus the single chunk program (and, where they are
  on, the prefix, chunked-prefill and draft/verify programs).
* **Admission control** — the waiting set is bounded by ``max_queue``;
  ``admission="block"`` makes ``submit`` wait for space,
  ``admission="reject"`` raises :class:`QueueFullError` (typed, so a
  caller can shed load).  ``close()`` drains gracefully: admitted
  requests complete (a partially full grid decodes to the last slot),
  later submits raise :class:`EngineClosedError`, and no
  scheduler/warmup thread survives (same thread-hygiene contract as
  ``training.pipeline_io``).
* **Observability** — ``serve/queue_wait`` (recorded cross-thread via
  ``tracing.record_span``), ``serve/prefill`` spans and
  ``serve/chunk`` spans (with per-dispatch ``active``/``occupancy``
  attributes).  A scheduler pass closes: one numbered
  ``serve/pass`` span per loop iteration that did work (``inserts``
  taken off the queue with their ``prompt_tokens``/``bucket_tokens``
  and the ``computed_tokens`` of their inserts, ``active`` slots in its
  chunk,
  ``kv_rows_in_use``/``kv_rows_reserved``; recorded, never mirrored
  into a profile), and under it the leaves ``serve/launch`` (the
  host's time to enqueue a program, ``what``), ``serve/readback``
  (waiting for and copying a result, ``what``) and ``serve/commit``
  (token append, stream delivery, retires; ``tokens``/``retired``),
  each with the pass's number, as ``serve/prefill`` and
  ``serve/chunk`` carry it.  A request closes: while a collector is
  active every request has a trace id (the caller's or one ``submit``
  mints) shared by its ``serve/queue_wait``, ``serve/prefill``, the
  chunk spans' ``traces`` map and its terminal
  ``serve/request`` (``ttft_s``, ``queue_wait_s``, ``decode_s``,
  ``tokens``, ``prompt_len``, ``bucket``, ``slot``, ``passes``) and
  ``serve/ttft`` (submit's stamp to the first token on the host).
  ``stats()`` counts ``kv_row_steps_reserved`` /
  ``kv_row_steps_in_use`` / ``kv_row_steps_read`` (the rows a decode
  step fetches: every row of the grid, or, where the decode read goes
  through the paged kernel, the live slots' rows rounded up to its
  page) at every chunk dispatch and, with
  ``health()``, reports ``kv_bytes_reserved`` / ``kv_bytes_in_use``.
  A model with a recurrent state (``TransformerConfig.ssm``) keeps one
  state row a slot a layer beside the K/V rows: ``state_row_steps_*``
  and ``state_bytes_*`` count them the same way (zeros otherwise),
  ``state_row_steps_read`` the rows a decode step fetches (every
  reserved row, or the decoding slots' alone where ``ops.ssm_state``'s
  kernel advances them), and ``serve/pass`` carries
  ``state_rows_in_use``.  ``insert_rows_bucket`` /
  ``insert_rows_computed`` sum, at every insert dispatch, the prompt
  buffer's rows and the rows the insert program computes for the
  prompt in it (``generation.prefill_rows_computed``), and
  ``flash_pairs_run`` / ``flash_pairs_width`` the compute tiles the
  flash forward kernel runs for that prompt against the tiles of its
  width's whole causal triangle (``generation.prefill_flash_tiles``;
  zeros where the insert's attention is not the kernel's).
  A latent-attention model (``TransformerConfig.latent``) keeps ONE row
  a token a layer, key and value of every head at once: the
  ``kv_row_steps_*`` and ``kv_bytes_*`` count those rows (read: the
  live slots' rows rounded up to ``ops.latent_attention``'s page where
  its kernel reads).  A model with dropless experts
  (``MoeConfig.dropless``) routes on the device, so every insert and
  chunk brings its routing counts back in the same read-back as its
  tokens: ``expert_assignments`` / ``expert_assignments_here`` (the
  (token, choice) pairs made, and those that landed on an expert held
  here), ``expert_steps`` / ``expert_steps_touched`` (per decode step
  and expert layer, the held experts against those that got a token),
  ``expert_loads`` and ``expert_load_max_over_mean`` (the tokens each
  held expert got so far; the busiest over their mean); zeros without
  experts; ``serve/pass`` carries ``assignments_here``.
  ``serve/qps`` and ``serve/tokens_per_sec``
  windowed-rate gauges, the ``serve/slot_occupancy`` gauge,
  slot-churn counters
  (``serve/slot_inserts``, ``serve/slot_retires``,
  ``serve/slot_expired``, ``serve/chunks``) and a
  ``serve/latency_seconds`` distribution.  ``python -m
  cloud_tpu.monitoring.report`` renders the serve spans as a dedicated
  breakdown, with a continuous-batching section when chunk spans are
  present.

Greedy parity is the correctness contract: for any mix of
prompt lengths, arrival times, and per-request decode budgets, a
request's tokens are identical to a direct per-request
``generation.generate`` call (slot/bucket padding is masked out of
attention, greedy decode is prefix-consistent, and the chunk program
replays generate()'s exact sampling order).  Proven in
tests/unit/test_serving.py and scripts/check_serving.py under slot
churn.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import threading
import time
from concurrent.futures import Future, InvalidStateError
from typing import Dict, List, Optional, Tuple

import numpy as np

from cloud_tpu.monitoring import metrics, tracing
from cloud_tpu.serving import qos as qos_lib
from cloud_tpu.serving.qos import (
    BrownoutShedError,
    QosConfig,
    TokenStream,
)
from cloud_tpu.utils import faults

logger = logging.getLogger(__name__)

#: Scheduler-thread name (prefix match in tests' thread-leak guards).
SERVE_SCHEDULER_THREAD_NAME = "cloud-tpu-serve-scheduler"

#: Watchdog-supervised dispatch threads (``dispatch_timeout_s`` set);
#: same leak-guard prefix family as the scheduler.
SERVE_DISPATCH_THREAD_NAME = "cloud-tpu-serve-dispatch"


class QueueFullError(RuntimeError):
    """Typed rejection under ``admission="reject"``: the waiting set is at
    ``max_queue`` — shed the request or retry with backoff."""


class EngineClosedError(RuntimeError):
    """The engine is closed (or closing): the request was not admitted."""


class DeadlineExceededError(RuntimeError):
    """The request's ``deadline_s`` expired while it waited in the queue:
    it was shed before occupying a decode slot (serving the tokens late
    would waste capacity the deadline says nobody wants)."""


class DispatchTimeoutError(RuntimeError):
    """A device dispatch exceeded ``dispatch_timeout_s``: the watchdog
    failed the in-flight requests and marked the engine unhealthy
    instead of wedging the scheduler forever."""


@dataclasses.dataclass(frozen=True)
class DraftConfig:
    """The draft half of draft-and-verify speculative decoding.

    ``config`` is any ``models.transformer.TransformerConfig`` —
    typically fewer layers / narrower than the target (its vocabulary
    must match the target's: acceptance compares token ids); ``params``
    the draft model's weights.  ``spec_k`` is the verify-window width:
    the tokens the TARGET consumes — and can commit — per verify
    dispatch; the draft proposes ``spec_k - 1`` of them.  ``spec_k=1``
    degenerates to the non-speculative schedule with the draft as pure
    overhead (the parity/overhead test knob).  Speculation is
    greedy-only: the engine rejects non-zero temperature and
    repetition penalties with typed errors (token-identical non-greedy
    speculation needs rejection resampling, which the grid does not
    do).
    """

    config: object
    #: repr-suppressed: a params pytree in a logged config would dump
    #: whole weight arrays.
    params: object = dataclasses.field(repr=False, default=None)
    spec_k: int = 4

    def __post_init__(self):
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if self.params is None:
            raise ValueError(
                "DraftConfig needs the draft model's params — without "
                "them the first proposal dispatch would die deep in the "
                "scheduler thread instead of here"
            )


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (all static — they define the compiled-program grid).

    ``prompt_buckets`` are the padded prompt lengths the engine compiles
    for (a request lands in the smallest bucket that fits it).  The
    compiled grid is one insert program per prompt bucket plus ONE
    chunk program over the
    ``(num_slots, prompt_buckets[-1] + max_new_tokens)`` slot cache;
    ``chunk_tokens`` is the scheduling quantum (admission/retirement
    granularity vs dispatch overhead — docs/serving.md).
    ``max_queue``/``admission`` are the backpressure contract (module
    docstring).
    """

    max_new_tokens: int = 32
    prompt_buckets: Tuple[int, ...] = (32, 128, 512)
    max_queue: int = 256
    admission: str = "block"
    #: Decode-slot count of the grid.
    num_slots: int = 8
    #: Tokens decoded per chunk dispatch.  Small
    #: chunks admit/retire at finer granularity (lower latency under
    #: churn); large chunks amortize host dispatch overhead.
    chunk_tokens: int = 8
    #: Shared-prefix KV cache: pool size in blocks.
    #: 0 (default) disables — the compatibility default.  When set, the
    #: scheduler looks up each arriving prompt's longest cached prefix,
    #: copies its KV into the slot row (``generation.
    #: copy_prefix_program``), and prefills only the uncached suffix;
    #: completed prefills donate their new full blocks back to the
    #: pool.  Greedy outputs stay token-identical either way.
    prefix_cache_blocks: int = 0
    #: Tokens per prefix block — the hit granularity (hits are whole
    #: blocks; a prompt's trailing partial block never caches).
    prefix_block_tokens: int = 16
    #: Host-DRAM second tier for the prefix cache: blocks evicted from
    #: the HBM pool demote to a bounded host-side pool of this many
    #: blocks instead of vanishing, and a hit on a demoted prefix swaps
    #: its blocks back in asynchronously (``serve/prefix_swapin``) —
    #: hot system prompts survive HBM pressure.  0 (default) disables
    #: the tier entirely (byte-identical to the single-tier cache;
    #: the ``prefix_dram_*`` health/stats keys read zero).  Requires
    #: ``prefix_cache_blocks > 0``.
    prefix_dram_blocks: int = 0
    #: Chunked prefill: split prompt prefill into
    #: dispatches of this many tokens, interleaved with decode chunks,
    #: so a long arrival stalls in-flight decode by at most ONE chunk
    #: instead of one full prefill.  None (default) keeps the one-shot
    #: insert prefill — the compatibility default.
    prefill_chunk_tokens: Optional[int] = None
    #: Draft-and-verify speculative decoding: arm with
    #: ``DraftConfig(config=..., params=..., spec_k=...)``.  ``None``
    #: (default) keeps the one-dispatch-per-token decode path
    #: byte-identical.  Greedy-only (module docstring).
    draft: Optional[DraftConfig] = None
    #: Sampling config shared by every request (static: it specializes
    #: the compiled decode program).  Default greedy.
    sample: "SampleConfig" = None  # type: ignore[assignment]
    kv_quant: bool = False
    #: Pre-compile the slot grid's programs at start on a background
    #: worker (``training.compile_cache``).
    warmup: bool = False
    #: Seed for the engine-owned sampling rng chain (non-greedy configs).
    seed: int = 0
    #: Watchdog bound on any single device dispatch (prefill, chunk).
    #: ``None`` (default) trusts the device; when set, a
    #: dispatch exceeding it fails its requests with
    #: :class:`DispatchTimeoutError` and marks the engine unhealthy
    #: (``health()``) instead of wedging the scheduler forever.  Costs
    #: one short-lived supervision thread per dispatch — serving rigs
    #: that want an SLO on "the device answered at all" opt in.
    dispatch_timeout_s: Optional[float] = None
    #: Tensor-parallel serving slice: the ``(tp, sp)`` chip grid ONE
    #: replica spans.  ``tp`` shards params (heads/mlp/vocab) and the
    #: slot KV cache + prefix block pool by attention head — it must
    #: divide the model's ``num_heads`` (typed error otherwise); ``sp``
    #: is sequence parallelism over activations.  ``None`` or ``(1, 1)``
    #: (the default) keeps the existing single-chip path byte-identical.
    #: Greedy outputs on any slice are token-identical to single-chip
    #: ``generate()`` — sharding moves bytes, never tokens.
    mesh_shape: Optional[Tuple[int, int]] = None
    #: ``"explicit"`` (default) uses ``mesh_shape`` verbatim;
    #: ``"auto"`` asks ``parallel.planner.plan_serve_layout`` to pick
    #: the slice partition from the model's head count, the visible
    #: devices (bounded by ``mesh_shape`` when set), and
    #: ``hbm_bytes_per_chip``.
    layout: str = "explicit"
    #: Per-chip HBM budget for ``layout="auto"`` (bytes).  ``None``
    #: uses the whole slice (widest head-dividing tp) for per-request
    #: speed; a budget picks the NARROWEST tp that fits, leaving chips
    #: for more replicas.
    hbm_bytes_per_chip: Optional[int] = None
    #: Multi-tenant QoS: ``serving.qos.QosConfig``
    #: arms priority classes (slot admission by SLO slack + weighted
    #: fairness debt instead of arrival order) and class-aware brownout
    #: shedding.  ``None`` (default) keeps the FIFO path byte-identical
    #: — priority tags are accepted but never reorder anything, and the
    #: per-class health/stats keys read zero.  Host-side policy only:
    #: the compiled programs are untouched either way.
    qos: Optional[QosConfig] = None
    #: How a prefix hit reaches the slot grid's attention.
    #: ``"xla"`` (default): hits are COPIED into the slot's row before
    #: decode (``copy_prefix_program``) and every program reads slot rows
    #: only.  ``"pallas"`` routes the chunk/prefill-chunk/verify
    #: programs through ``ops.paged_attention`` with a per-slot block
    #: table: prefix hits ATTACH pool blocks to the table instead of
    #: dispatching the copy, with the Pallas kernel forced on;
    #: ``"auto"`` takes the same route but lets the op's dispatch pick
    #: kernel vs its jnp reference per shape (docs/KERNELS.md).  The
    #: decode step's skip of rows that hold nothing does NOT depend on
    #: this: on a TPU the decode read goes through the paged kernel
    #: under every setting (``generation._scan_layers``).  Greedy
    #: outputs are token-identical on every setting.
    decode_kernel: str = "xla"
    #: Disaggregated-serving role this engine plays in a fleet:
    #: ``"prefill"`` (serves the prefill leg of split requests),
    #: ``"decode"`` (serves handoff-carrying decode legs), or
    #: ``"both"`` (default — the colocated engine, byte-identical to
    #: today; the ``role``/handoff health keys read ``"both"``/zero).
    #: Routing policy lives in the fleet; the engine only reports the
    #: role and accepts the handoff submit kwargs, which themselves
    #: need a prefix pool (the handoff IS cross-replica prefix-cache
    #: seeding — docs/fleet.md).  A fleet
    #: replica may override per-replica via :meth:`ServingEngine.
    #: set_role`, so one factory serves mixed-role fleets.
    role: str = "both"
    #: TTL (seconds) on the router-facing ``hot_prefixes()`` summary:
    #: entries for prefixes not HIT within it age out of ``health()``'s
    #: ``cached_prefixes``, so a replica that lost its hot tenant stops
    #: advertising stale cached-prefix credit to the cost-model router.
    #: ``None`` (default) never expires — byte-identical to today.
    prefix_summary_ttl_s: Optional[float] = None
    #: Scheduler pipelining depth.  ``1`` (default) is the strictly
    #: synchronous loop — dispatch a chunk, block on its emissions,
    #: mutate slots, dispatch the next — byte-identical to today.  ``2``
    #: keeps a second chunk in flight: chunk N+1 is dispatched against
    #: the device-resident slot state *before* chunk N's emissions are
    #: synchronized, and N drains (non-blocking device→host copy) while
    #: the device runs N+1, hiding the host scheduling bubble.  Slot
    #: mutations from a drain apply to the *next* dispatch (one pass
    #: stale); the chunk program's active mask keeps a speculatively
    #: dispatched chunk for a just-finished slot emitting only masked
    #: tokens, so greedy outputs are token-identical to depth 1
    #: (docs/serving.md "Pipelined scheduling").  Kill switch:
    #: ``CLOUD_TPU_PIPELINE=0`` forces depth 1 at engine build.
    pipeline_depth: int = 1

    def __post_init__(self):
        from cloud_tpu.models.generation import SampleConfig

        if self.sample is None:
            object.__setattr__(self, "sample",
                               SampleConfig(temperature=0.0))
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}"
            )
        buckets = tuple(self.prompt_buckets)
        object.__setattr__(self, "prompt_buckets", buckets)
        if not buckets or any(b < 1 for b in buckets):
            raise ValueError("prompt_buckets must be non-empty and positive")
        if list(buckets) != sorted(set(buckets)):
            raise ValueError(
                f"prompt_buckets must be strictly increasing, got {buckets}"
            )
        if self.admission not in ("block", "reject"):
            raise ValueError(
                f"admission must be 'block' or 'reject', "
                f"got {self.admission!r}"
            )
        if self.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {self.num_slots}")
        if self.chunk_tokens < 1:
            raise ValueError(
                f"chunk_tokens must be >= 1, got {self.chunk_tokens}"
            )
        if self.prefix_cache_blocks < 0:
            raise ValueError(
                f"prefix_cache_blocks must be >= 0, got "
                f"{self.prefix_cache_blocks}"
            )
        if self.prefix_block_tokens < 1:
            raise ValueError(
                f"prefix_block_tokens must be >= 1, got "
                f"{self.prefix_block_tokens}"
            )
        if self.prefix_dram_blocks < 0:
            raise ValueError(
                f"prefix_dram_blocks must be >= 0, got "
                f"{self.prefix_dram_blocks}"
            )
        if self.prefix_dram_blocks and not self.prefix_cache_blocks:
            raise ValueError(
                "prefix_dram_blocks (the host-DRAM tier) needs "
                "prefix_cache_blocks > 0 — there is no HBM pool to "
                "demote from or swap back into"
            )
        if (self.prefill_chunk_tokens is not None
                and self.prefill_chunk_tokens < 1):
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1 or None, got "
                f"{self.prefill_chunk_tokens}"
            )
        if self.draft is not None:
            if self.sample.temperature != 0.0:
                raise ValueError(
                    "draft= (speculative decoding) requires greedy "
                    f"sampling; got temperature={self.sample.temperature}"
                    " (token-identical non-greedy speculation needs "
                    "rejection resampling)"
                )
            if self.sample.repetition_penalty != 1.0:
                raise ValueError(
                    "draft= (speculative decoding) does not compose with "
                    "repetition_penalty: the verify window's emissions "
                    "would each need the penalty state of the emissions "
                    "before them"
                )
        if self.max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {self.max_queue}")
        if self.dispatch_timeout_s is not None and self.dispatch_timeout_s <= 0:
            raise ValueError(
                f"dispatch_timeout_s must be > 0 or None, "
                f"got {self.dispatch_timeout_s}"
            )
        if self.qos is not None and not isinstance(self.qos, QosConfig):
            raise ValueError(
                f"qos must be a serving.qos.QosConfig, got "
                f"{type(self.qos).__name__}"
            )
        if self.decode_kernel not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"decode_kernel must be 'auto', 'pallas', or 'xla', "
                f"got {self.decode_kernel!r}"
            )
        if self.role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode', or 'both', "
                f"got {self.role!r}"
            )
        if self.role != "both" and not self.prefix_cache_blocks:
            raise ValueError(
                "role= (disaggregated serving) needs "
                "prefix_cache_blocks > 0 — the KV handoff "
                "exports/imports prefix-pool blocks"
            )
        if (self.prefix_summary_ttl_s is not None
                and self.prefix_summary_ttl_s <= 0):
            raise ValueError(
                f"prefix_summary_ttl_s must be > 0 or None, got "
                f"{self.prefix_summary_ttl_s}"
            )
        if self.pipeline_depth not in (1, 2):
            raise ValueError(
                f"pipeline_depth must be 1 or 2, got "
                f"{self.pipeline_depth!r}"
            )
        if self.layout not in ("explicit", "auto"):
            raise ValueError(
                f"layout must be 'explicit' or 'auto', got {self.layout!r}"
            )
        if self.mesh_shape is not None:
            shape = tuple(int(v) for v in self.mesh_shape)
            if len(shape) != 2 or any(v < 1 for v in shape):
                raise ValueError(
                    f"mesh_shape must be a (tp, sp) pair of positive "
                    f"ints, got {self.mesh_shape!r}"
                )
            object.__setattr__(self, "mesh_shape", shape)
        if (self.hbm_bytes_per_chip is not None
                and self.hbm_bytes_per_chip < 1):
            raise ValueError(
                f"hbm_bytes_per_chip must be >= 1 or None, got "
                f"{self.hbm_bytes_per_chip}"
            )


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One resolved request.

    ``tokens`` is the request's generated row, length =
    its ``max_new_tokens`` (eos included where sampled, pad after it) —
    byte-identical to ``generation.generate``'s row for the same prompt.
    ``num_generated`` counts real tokens (eos included).
    ``bucket_len`` is the prompt bucket it was inserted at and
    ``batch_size`` the grid's ``num_slots``.
    """

    tokens: np.ndarray
    num_generated: int
    bucket_len: int
    batch_size: int
    latency_seconds: float
    #: Submit -> first token known.  The first token is sampled when
    #: the prefill lands, so this isolates queueing + prefill (what
    #: prefix caching and chunked prefill move) from decode.
    ttft_seconds: float = 0.0
    #: Fleet-wide trace id when the request carried a ``TraceContext``
    #: (``tracing.new_trace_context``); None otherwise — the key that
    #: joins this result to its spans in a merged timeline.  Rides
    #: ``dataclasses.replace`` untouched, so the fleet's latency rebase
    #: on failover keeps the identity.
    trace_id: Optional[str] = None
    #: KV handoff payload exported for this request (disaggregated
    #: serving: ``submit(handoff_export=True)`` on a prefill replica) —
    #: the prompt's cached prefix blocks serialized host-side, dict
    #: shape per ``fleet.disagg``.  None everywhere else (the default
    #: fleet never builds one — pinned byte-identical).
    handoff: Optional[dict] = None


#: eq=False: requests are removed from mid-queue by IDENTITY (QoS
#: admission, brownout shed) — a generated __eq__ would compare numpy
#: prompt arrays element-wise and raise on the first non-match.
@dataclasses.dataclass(eq=False)
class _Request:
    prompt: np.ndarray
    prompt_len: int
    max_new_tokens: int
    bucket_len: int
    future: Future
    submitted: float  # perf_counter
    #: Absolute perf_counter time after which the request is shed from
    #: the queue instead of served (None: wait forever).
    deadline: Optional[float] = None
    #: QoS class name (resolved at submit when a QosConfig is armed;
    #: carried-but-inert on the FIFO path).
    priority: Optional[str] = None
    #: Per-token delivery (``submit(stream=True)``): fed from the
    #: emission path as chunks commit, closed by the future's
    #: done-callback.  None for plain futures.
    stream: Optional[TokenStream] = None
    #: Cross-layer per-token hook (the fleet's stream forwarding):
    #: called as ``on_token(index, token)`` from the scheduler thread.
    on_token: Optional[object] = None
    #: The request's ``tracing.TraceContext``: the caller's (the fleet
    #: mints one per request) or, while a collector is active, one
    #: ``submit`` minted.  None only while tracing is off.
    trace: Optional[tracing.TraceContext] = None
    #: perf_counter when the scheduler took the request off the queue
    #: (the end of its ``serve/queue_wait``).
    admitted: Optional[float] = None
    #: Disaggregated prefill leg: export the prompt's cached prefix
    #: blocks host-side after prefill (``ServeResult.handoff``).
    handoff_export: bool = False
    #: Disaggregated decode leg: a handoff payload to seed the prefix
    #: cache with BEFORE this request's own prefix lookup, so admission
    #: sees an ordinary hit.  None on every non-handoff request.
    handoff: Optional[dict] = None

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now >= self.deadline

    @property
    def trace_id(self) -> Optional[str]:
        return self.trace.trace_id if self.trace is not None else None


def _trace_attrs(request: _Request, **attrs) -> dict:
    """Span attributes + the request's ``trace_id`` (every request has
    one while a collector is active)."""
    if request.trace is not None:
        attrs["trace_id"] = request.trace.trace_id
    return attrs


@dataclasses.dataclass
class _Slot:
    """Host mirror of one live decode slot (scheduler-thread only):
    which request occupies it and the tokens emitted for it so far.
    The device-side twin is the slot's row of the grid state
    (``generation.init_slot_state``); host and device transition in
    lockstep — both retire a slot exactly when its emission count hits
    the request's ``max_new_tokens`` or the last emission was eos.
    ``prefix_nodes`` are the prefix-cache blocks this slot holds
    references on (copied-in hit + saved-out new blocks), released when
    the slot retires."""

    request: _Request
    tokens: List[int]
    prefix_nodes: List[object] = dataclasses.field(default_factory=list)
    first_token_ts: Optional[float] = None
    #: Tokens already delivered to the request's stream/on_token hook
    #: (prefix of ``tokens``, capped at the request's budget).
    streamed: int = 0
    #: Scheduler passes whose chunk decoded for this slot.
    passes: int = 0
    #: Exported KV handoff payload (``handoff_export`` requests only):
    #: built right after the prefix save, carried to ``_retire_slot``
    #: which rides it out on the result.
    handoff: Optional[dict] = None


@dataclasses.dataclass
class _PrefillTask:
    """A request mid-prefill (chunked prefill and/or a prefix hit): the
    slot is claimed — ``_slot_table`` already holds its host mirror, so
    a crash fails it — but decode has not started.  ``next_pos`` is the
    first prompt position not yet prefilled; the scheduler advances the
    OLDEST task by one ``chunk_width`` dispatch per pass, so in-flight
    decode never waits more than one chunk on a long arrival."""

    request: _Request
    slot: int
    chunk_width: int
    next_pos: int
    #: The acquired prefix hit (its KV was copied in before the first
    #: chunk), or None on a cold prefill.
    hit: Optional[object] = None


@dataclasses.dataclass
class _InflightChunk:
    """One dispatched-but-undrained chunk in the pipelined scheduler's
    in-flight ring (``pipeline_depth=2``; scheduler-thread only).

    Holds the *device-side* emission arrays exactly as the chunk
    program returned them — the drain half materializes them with a
    blocking host copy (``engine._to_host``) one pass later, after the
    NEXT chunk has already been dispatched, so the host-side copy wait
    overlaps device compute.  A slot occupying a row here is never in
    ``_free_slots`` (retirement happens at drain), so an in-flight
    chunk can never describe a slot that was re-assigned under it.
    """

    #: Device array of emitted token ids, ``[num_slots, width]``.
    toks: object
    #: Device bool array — which emissions are live, same shape.
    valid: object
    #: Device int32 ``[emitted_count, active_count]`` summary from the
    #: chunk program (``with_summary=True``) — rides along so callers
    #: that only need occupancy never block on the full emission grid.
    summary: object
    #: Emission width: ``chunk_tokens`` (decode) or ``spec_k`` (verify).
    width: int
    #: ``"chunk"`` or ``"verify"`` — picks the terminal span name and
    #: the stats the drain updates.
    kind: str
    #: ``len(_active_slots)`` at dispatch (the verify drain's
    #: accept-rate denominator).
    active: int
    #: Span attributes captured at dispatch (slots/chunk/active/slice/
    #: traces) — the drain adds tokens/occupancy and records the span
    #: over the full dispatch→drain interval.
    span_attrs: dict
    #: ``time.perf_counter()`` bracketing the dispatch call itself.
    dispatch_start: float
    dispatch_end: float
    #: Device routing counts of a model with dropless experts
    #: (``moe.ROUTING_HEAD``), read back with the tokens; else empty.
    routing: tuple = ()


class _DeferredPayload:
    """A demoted block's host bytes, not yet downloaded.

    Inside a demotion burst (``_demote_burst``), ``_demote_block``
    returns one of these instead of paying a supervised download per
    evicted block; the burst's exit flushes ALL pending downloads as
    one batched dispatch under ONE watchdog window
    (``_flush_demotes``), mirroring how the swap-in side budgets a
    whole plan.  Safe because nothing materializes a demoted payload
    until after the burst scope closes: the save/swap-in programs that
    reuse the evicted rows dispatch strictly AFTER the manager call the
    burst wraps, and ``_dispatch_swapin`` resolves placeholders via
    ``_resolve_payload`` at upload time.  Scheduler-thread only.
    """

    __slots__ = ("value", "filled")

    def __init__(self):
        self.value = None
        self.filled = False


def _resolve_payload(payload):
    """A demoted block's actual host bytes (unwraps a burst-deferred
    placeholder; anything else passes through)."""
    if isinstance(payload, _DeferredPayload):
        if not payload.filled:
            raise RuntimeError(
                "deferred demote payload read before its burst flushed "
                "— demote downloads must complete before row reuse"
            )
        return payload.value
    return payload


def _refuse_unless_kv_rows(config, cfg: ServeConfig) -> None:
    """Every engine feature that takes "a cache row is K and V per head,
    and a prefix's cache is its rows" for granted refuses, at
    construction and in words, a model whose slot cache is of another
    kind (``generation.CACHE_KINDS``: a recurrent state beside the rows,
    ``TransformerConfig.ssm``; latent rows, ``TransformerConfig.latent``);
    the plain slot path — insert at a bucket, decode chunks — serves
    it."""
    from cloud_tpu.models import generation

    kind = generation.cache_kind(config)
    if kind is None:
        return
    field, name, holds = kind
    asked = [what for what, on in (
        ("kv_quant (an int8 cache)", cfg.kv_quant and field == "latent"),
        ("prefix_cache_blocks (the prefix pool)", cfg.prefix_cache_blocks),
        ("prefill_chunk_tokens (chunked prefill)",
         cfg.prefill_chunk_tokens is not None),
        ("decode_kernel != 'xla' (the paged read)",
         cfg.decode_kernel != "xla"),
        ("draft (draft and verify)", cfg.draft is not None),
        ("role != 'both' (KV hand-off)", cfg.role != "both"),
        ("mesh_shape / layout='auto' (a tp/sp serving mesh)",
         cfg.layout == "auto" or cfg.mesh_shape not in (None, (1, 1))),
    ) if on]
    if asked:
        raise NotImplementedError(
            "ServeConfig asks for " + "; ".join(asked) + ", which a model "
            f"with {name} (TransformerConfig.{field}) does not support "
            "yet: a copied, chunked, paged, rewound, exported, quantized "
            f"or head-sharded cache of K/V rows is not what its slot "
            f"cache holds ({holds}) (ROADMAP R4)"
        )


class ServingEngine:
    """In-process continuous-batching server over ``generation`` (module
    docstring).  Construct, ``submit()`` concurrently from any thread,
    ``close()`` when done (or use as a context manager)."""

    def __init__(
        self,
        params,
        config,
        serve_config: Optional[ServeConfig] = None,
        *,
        rules=None,
        mesh=None,
        start: bool = True,
    ):
        import jax

        from cloud_tpu.models import generation
        from cloud_tpu.ops import ssm_state
        from cloud_tpu.parallel import mesh as mesh_lib
        from cloud_tpu.parallel.sharding import DEFAULT_RULES
        from cloud_tpu.training import compile_cache

        # Persistent executable cache, as Trainer.fit enables it: engine
        # warm-up is most of a cold serve.  A cheap no-op when no cache
        # directory is configured.
        compile_cache.maybe_enable_persistent_cache()
        self.params = params
        self.config = config
        self.serve_config = serve_config or ServeConfig()
        self.rules = rules if rules is not None else DEFAULT_RULES
        self.mesh = mesh if mesh is not None else mesh_lib.get_global_mesh()
        #: The replica's slice: (tp, sp) and total chips (= tp * sp).
        #: (1, 1)/1 on the single-chip path; a ServeConfig.mesh_shape /
        #: layout="auto" slice builds its own TP(xSP) mesh (flagged so
        #: param placement only happens for engine-owned meshes — a
        #: caller-provided mesh keeps the caller's placement).
        self._built_serving_mesh = False
        _refuse_unless_kv_rows(config, self.serve_config)
        self._slice_shape, self._slice_chips = self._resolve_serving_mesh()
        generation.check_inference_supported(
            config, self.rules, self.mesh, "serving"
        )
        if self._built_serving_mesh:
            self._shard_params()
        metrics.gauge_set("serve/slice_chips", self._slice_chips)
        # Engine-owned rng chain: split per dispatch (carried but
        # unobservable under greedy — one decode signature either way).
        self._rng = jax.random.PRNGKey(self.serve_config.seed)

        def split_key(key):
            new, sub = jax.random.split(key)
            return new, sub

        #: One program per key split (``_split_rng``): the eager split
        #: is two dispatches, a millisecond of host time before every
        #: insert and chunk with the device idle.
        self._split_key = jax.jit(split_key)

        self._cond = threading.Condition()
        #: bucket_len -> FIFO of waiting _Requests (guarded by _cond).
        self._pending: Dict[int, collections.deque] = {}
        self._waiting = 0
        self._closed = False
        self._draining = True
        self._thread: Optional[threading.Thread] = None
        self._warmup_plan = None
        #: Why the engine is unhealthy (watchdog fire, scheduler crash);
        #: None while healthy.  Written by the scheduler, read by
        #: ``health()`` from any thread (str swap — atomic enough).
        self._unhealthy_reason: Optional[str] = None
        #: Watchdog-abandoned dispatch threads, joined (bounded) by
        #: close() so a finite hang never leaks past the engine's life.
        self._orphan_dispatches: List[threading.Thread] = []
        self._last_dispatch_ts: Optional[float] = None
        #: Sequence number of the scheduler's open pass (from 1):
        #: ``serve/pass`` and every span recorded inside the pass carry
        #: it.
        self._pass_seq = 0
        #: Timeline lane (synthetic Chrome-trace pid) this engine's
        #: scheduler stamps its spans with; None = the real process pid.
        #: Set by the owning fleet replica via :meth:`set_trace_lane`.
        self._trace_lane: Optional[int] = None
        #: Live demotion burst: while a prefix-cache insert/swap-in
        #: reservation runs, demote downloads are DEFERRED into this
        #: list and flushed as one batched dispatch under ONE watchdog
        #: window at burst exit (``_flush_demotes``) — mirroring how
        #: the swap-in side budgets a whole plan, instead of paying a
        #: supervised thread per evicted block.  Scheduler-thread only.
        self._demote_batch: Optional[List[tuple]] = None
        #: This engine's disaggregated-serving role (``"both"`` keeps
        #: the colocated default).  Plain str swap — the owning fleet
        #: replica may restamp it via :meth:`set_role`.
        self._role = self.serve_config.role

        self._stats_lock = threading.Lock()
        self._stats = {
            "requests": 0, "completed": 0, "failed": 0, "rejected": 0,
            "batches": 0, "slots": 0, "real_rows": 0,
            "generated_tokens": 0,
            # Token-level decode accounting: useful emissions vs
            # dispatched emission slots.
            "decode_slot_steps": 0, "useful_decode_tokens": 0,
            # Slot churn counters.
            "inserts": 0, "retires": 0, "expired": 0, "chunks": 0,
            # Prefix-cache / chunked-prefill counters (0 when disabled).
            "prefill_chunks": 0, "prefix_hits": 0, "prefix_misses": 0,
            # Paged-attention block-table attaches (0 with
            # decode_kernel="xla" — every hit then goes through the
            # copy program instead).
            "prefix_attaches": 0,
            # Speculative-decoding counters (0 when draft=None):
            # spec_chunks = verify (target) dispatches, spec_emitted =
            # tokens they committed, spec_proposed/accepted = draft
            # tokens offered/committed — acceptance is their quotient.
            "spec_chunks": 0, "spec_emitted": 0,
            "spec_proposed": 0, "spec_accepted": 0, "draft_prefills": 0,
            # Robustness counters: queue-shed deadlines, watchdog fires.
            "shed": 0, "watchdog_timeouts": 0,
            # KV rows reserved against in use, summed over every chunk
            # dispatch (their quotient is the share of the rows a
            # decode step reads that hold a live token), and the rows
            # a decode step fetches: the grid's,
            # or the live slots' pages where the paged kernel reads.
            "kv_row_steps_reserved": 0, "kv_row_steps_in_use": 0,
            "kv_row_steps_read": 0,
            # Rows of the prompt buffers dispatched to the insert
            # program against the rows it computed for them (the
            # smallest of the buffer's widths that holds the prompt).
            "insert_rows_bucket": 0, "insert_rows_computed": 0,
            # The flash forward kernel's compute tiles (a head a layer)
            # for those prompts against the tiles of their widths'
            # whole causal triangles; zeros where an insert's attention
            # is not the kernel's.
            "flash_pairs_run": 0, "flash_pairs_width": 0,
            # The same for a recurrent state's rows, one a slot a layer
            # (0 for a model without one); read: every reserved row, or
            # the decoding slots' where the state kernel advances them.
            "state_row_steps_reserved": 0, "state_row_steps_in_use": 0,
            "state_row_steps_read": 0,
            # Dropless experts (``MoeConfig.dropless``; zeros for a
            # model without them), as the insert and chunk programs
            # count them on the device: (token, choice) assignments
            # made, those that landed on an expert held here; per
            # decode step and expert layer the held experts, and those
            # of them that got a token (whose weights the step read).
            "expert_assignments": 0, "expert_assignments_here": 0,
            "expert_steps": 0, "expert_steps_touched": 0,
            # QoS brownout sheds (0 unless qos arms a brownout depth).
            "brownout_shed": 0,
            # Disaggregated-serving KV handoff counters (all 0 with
            # role="both" and no handoff submits — stable schema).
            "handoff_exports": 0, "handoff_export_blocks": 0,
            "handoff_imports": 0, "handoff_import_blocks": 0,
        }
        #: QoS state: None keeps the FIFO path byte-identical (every
        #: policy branch below checks this).  The scheduler object owns
        #: the fairness-debt state; per-class counters feed health()/
        #: stats() (zeros when off — stable schema).
        self._qos = self.serve_config.qos
        self._qos_sched = (
            qos_lib.QosScheduler(self._qos) if self._qos else None
        )
        classes = (
            tuple(self._qos.classes) if self._qos
            else qos_lib.DEFAULT_PRIORITIES
        )
        self._class_names = classes
        self._class_completed = {c: 0 for c in classes}
        self._class_shed = {c: 0 for c in classes}
        self._qps = metrics.WindowedRate("serve/qps", window=16)
        self._tokens_rate = metrics.WindowedRate(
            "serve/tokens_per_sec", window=256
        )

        cfg = self.serve_config
        #: Slot cache rows must fit the largest bucket's prompt plus
        #: the engine-wide decode budget.
        self._max_len = cfg.prompt_buckets[-1] + cfg.max_new_tokens

        def make_grid():
            return generation.init_slot_cache(
                config, cfg.num_slots, self._max_len, rules=self.rules,
                mesh=self.mesh, kv_quant=cfg.kv_quant,
            )

        # Under a serving slice the grid is born head-sharded:
        # building it INSIDE jit binds init_slot_cache's logical-
        # axis constraints to the mesh, so every leaf lands
        # [L, slots, S, H/tp, hd] per chip.  Single-chip keeps the
        # eager allocation — byte-identical to the pre-slice path.
        self._grid_cache = (
            jax.jit(make_grid)() if self._slice_chips > 1
            else make_grid()
        )
        self._slot_state = generation.init_slot_state(
            config, cfg.num_slots, sample=cfg.sample
        )
        if self._slice_chips > 1:
            # Per-slot scalars are tiny: replicate them across the
            # slice so every chip samples from the same state.
            from jax.sharding import NamedSharding, PartitionSpec

            self._slot_state = jax.device_put(
                self._slot_state,
                NamedSharding(self.mesh, PartitionSpec()),
            )
        #: Scheduler-thread-only slot bookkeeping (the host mirror).
        self._slot_table: List[Optional[_Slot]] = [None] * cfg.num_slots
        #: Slots in the open pass's chunk (``serve/pass``'s
        #: ``active``; scheduler-thread only, reset at each pass).
        self._pass_active = 0
        self._free_slots = list(range(cfg.num_slots))[::-1]
        self._active_slots: set = set()
        self._insert_cells: Dict[int, "compile_cache.AotStep"] = {}
        #: Requests mid-prefill (chunked prefill / prefix hits):
        #: FIFO, advanced one chunk dispatch per scheduler pass.
        self._prefill_tasks: collections.deque = collections.deque()
        self._chunk_prefill_cells: Dict[int, "compile_cache.AotStep"] = {}
        self._finalize_step = None
        self._copy_cells: Dict[int, "compile_cache.AotStep"] = {}
        self._save_cells: Dict[int, "compile_cache.AotStep"] = {}
        #: The shared-prefix block pool + its host-side radix
        #: bookkeeping (None unless prefix_cache_blocks > 0).
        self._prefix = None
        self._prefix_pool = None
        if cfg.prefix_cache_blocks:
            from cloud_tpu.serving.prefix_cache import PrefixCacheManager

            self._prefix = PrefixCacheManager(
                cfg.prefix_cache_blocks, cfg.prefix_block_tokens,
                dram_blocks=cfg.prefix_dram_blocks,
                demote_fn=(
                    self._demote_block if cfg.prefix_dram_blocks
                    else None
                ),
                summary_ttl_s=cfg.prefix_summary_ttl_s,
            )

            def make_pool():
                return generation.init_prefix_pool(
                    config, cfg.prefix_cache_blocks,
                    cfg.prefix_block_tokens, rules=self.rules,
                    mesh=self.mesh, kv_quant=cfg.kv_quant,
                )

            # The block pool shards by head exactly like the slot
            # grid (same pytree structure), so pool<->slot copies
            # stay chip-local — no resharding on the hit path.
            self._prefix_pool = (
                jax.jit(make_pool)() if self._slice_chips > 1
                else make_pool()
            )
        # Engine device-state lives WITH the params: the init
        # programs above land on the process default device, so on
        # multi-device hosts (a fleet pinning one replica's params
        # per device) the grid, slot state, and pool must be
        # re-committed to the params' device or the first dispatch
        # raises on mixed committed placements.
        if self.mesh is None:
            device = self._params_device()
            if device is not None:
                self._grid_cache = jax.device_put(
                    self._grid_cache, device
                )
                self._slot_state = jax.device_put(
                    self._slot_state, device
                )
                if self._prefix_pool is not None:
                    self._prefix_pool = jax.device_put(
                        self._prefix_pool, device
                    )
        #: KV accounting: the rows and bytes the slot grid and the
        #: prefix pool reserve (constant), and the rows that held a
        #: live token at the last chunk dispatch (plain int swap:
        #: the scheduler writes, ``stats()``/``health()`` read).
        leaves = jax.tree_util.tree_leaves
        self._kv_rows_reserved = cfg.num_slots * self._max_len + (
            cfg.prefix_cache_blocks * cfg.prefix_block_tokens
            if self._prefix is not None else 0
        )
        state_leaves = [
            self._grid_cache[name] for name in generation.STATE_LEAVES
            if name in self._grid_cache
        ]
        self._state_bytes_reserved = sum(x.nbytes for x in state_leaves)
        self._kv_bytes_reserved = sum(
            x.nbytes for x in
            leaves(self._grid_cache) + leaves(self._prefix_pool)
        ) - self._state_bytes_reserved
        self._kv_rows_in_use = 0
        #: A recurrent state's rows: one a slot a layer, in use while
        #: the slot decodes (0 for a model without a state).
        self._state_rows_reserved = (
            cfg.num_slots * config.num_layers if state_leaves else 0
        )
        self._state_rows_in_use = 0
        #: Dropless experts: the tokens each held expert got so far
        #: (None for a model without them), and the assignments that
        #: landed here in the open pass (``serve/pass``).
        moe = config.moe if config.moe is not None and config.moe.dropless \
            else None
        self._expert_loads = (
            np.zeros((moe.held,), np.int64) if moe is not None else None)
        self._pass_assignments_here = 0
        #: Whether a decode step advances the state through
        #: ``ops.ssm_state``'s kernel, which fetches the decoding
        #: slots' rows alone (``generation._scan_layers``' own rule),
        #: or reads and rewrites every reserved row.
        self._state_read_in_place = bool(state_leaves) and (
            ssm_state.takes_kernel(self._grid_cache["ssm"],
                                   config.ssm.num_groups))
        #: Block-table attention (``decode_kernel != "xla"``): the
        #: slot grid's attention reads KV through a per-slot block
        #: table — page p of a row resolves to a prefix-pool block
        #: (entry >= 0) or the slot row itself (-1) — so a prefix
        #: hit ATTACHES pool blocks instead of dispatching the copy
        #: program.
        #: Page size is ``prefix_block_tokens`` (hits are whole
        #: blocks, so attached pages align by construction).
        #: ``_block_table`` is the table's host-side
        #: [num_slots, n_pages] mirror (None on the XLA path).
        self._paged = cfg.decode_kernel != "xla"
        self._block_table = None
        #: "pallas" forces the kernel; "auto" defers to the op's
        #: measured-crossover dispatch (kernel on eligible TPU
        #: shapes, jnp paged reference elsewhere).
        self._paged_use_pallas = (
            True if cfg.decode_kernel == "pallas" else None
        )
        if self._paged:
            n_pages = -(-self._max_len // cfg.prefix_block_tokens)
            self._block_table = np.full(
                (cfg.num_slots, n_pages), -1, np.int32
            )
        self._decode_read_page = self._decode_page()
        #: Python-trace counters: the retrace guard for "one chunk
        #: compile serves the whole run" (tests/helpers/retrace_guard
        #: idiom — the wrapped body executes only while tracing).
        self._chunk_traces = 0
        self._insert_traces = 0
        self._prefill_chunk_traces = 0
        self._finalize_traces = 0
        self._copy_traces = 0
        self._save_traces = 0
        self._download_traces = 0
        self._swapin_traces = 0
        #: The DRAM-tier block movers (built on demand; one compile
        #: each — block index and payload shapes are static).
        self._download_step = None
        self._swapin_step = None
        self._upload_traces = 0
        self._upload_step = None
        self._export_traces = 0
        self._export_step = None
        self._draft_traces = 0
        self._verify_traces = 0
        self._draft_prefill_traces = 0
        # Donating the grid through each dispatch keeps the cache
        # update in place; CPU ignores donation with a warning, so
        # only ask for it where the backend honors it.
        self._donate = jax.default_backend() != "cpu"
        #: Effective pipelining depth: the config's, unless the
        #: CLOUD_TPU_PIPELINE=0 kill switch forces the synchronous
        #: loop (same env idiom as CLOUD_TPU_TRACE).  Resolved once
        #: at build — flipping the env mid-run does nothing.
        self._pipe_depth = cfg.pipeline_depth
        if os.environ.get("CLOUD_TPU_PIPELINE", "1") == "0":
            self._pipe_depth = 1
        #: Dispatched-but-undrained chunks, oldest first
        #: (scheduler-thread only).  Empty at every pass boundary
        #: at depth 1 — the synchronous loop never grows it, so
        #: the default path stays byte-identical.
        self._inflight: collections.deque = collections.deque()
        #: Rolling dispatch→dispatch host gaps (ms) — the bubble
        #: the pipeline exists to hide.  Tracked at every depth
        #: (host-side bookkeeping only; no spans at depth 1) so
        #: bench probes can compare p50/p99 across arms.
        self._dispatch_gaps: collections.deque = collections.deque(
            maxlen=512
        )
        self._last_chunk_dispatch_end: Optional[float] = None
        self._chunk_step = self._make_chunk_step()
        #: Speculative decoding (None unless ServeConfig.draft):
        #: the draft model's own slot cache + its program cells and
        #: a rolling per-dispatch (accepted, proposed) window for
        #: health()'s acceptance rate.
        self._spec = cfg.draft is not None
        self._draft_cache = None
        self._draft_step = None
        self._verify_step = None
        self._draft_prefill_cells: Dict[int, "compile_cache.AotStep"] = {}
        self._accept_window: collections.deque = collections.deque(
            maxlen=64
        )
        if self._spec:
            self._init_draft()

        if self.serve_config.warmup:
            self._start_warmup()
        if start:
            self.start()

    # -- sharded serving ---------------------------------------------------

    def _resolve_serving_mesh(self) -> Tuple[Tuple[int, int], int]:
        """Build the replica's TP(xSP) serving mesh from ``ServeConfig``.

        Returns ``((tp, sp), chips)``.  With ``mesh_shape`` unset (or
        1x1) and ``layout="explicit"`` this does NOTHING — ``self.mesh``
        stays exactly what the caller passed (usually None), which is
        the byte-identical single-chip default; a caller-provided mesh
        is honored as-is and only described here.  A nontrivial
        ``mesh_shape``/``layout="auto"`` builds a fresh mesh over the
        first ``tp * sp`` visible devices, with the head-divisibility
        contract enforced as a typed error.
        """
        cfg = self.serve_config
        wants = cfg.layout == "auto" or (
            cfg.mesh_shape is not None and cfg.mesh_shape != (1, 1)
        )
        have_mesh = self.mesh is not None and not getattr(
            self.mesh, "empty", False
        )
        if not wants:
            if have_mesh:
                # Caller-provided (or global) mesh: honored as-is — the
                # caller owns param placement, the engine never touches
                # it.  The slice is the mesh's SERVING-parallel extent,
                # tp x sp: a pure dp/fsdp training mesh reads (1, 1)/1
                # and keeps the exact pre-slice engine behavior (no
                # reshard spans, eager grid init).
                shape = dict(self.mesh.shape)
                from cloud_tpu.parallel import mesh as mesh_lib

                tp = int(shape.get(mesh_lib.AXIS_TP, 1))
                sp = int(shape.get(mesh_lib.AXIS_SP, 1))
                return (tp, sp), tp * sp
            return (1, 1), 1
        if have_mesh:
            raise ValueError(
                "pass either an explicit mesh= or "
                "ServeConfig.mesh_shape/layout='auto', not both — the "
                "engine builds its own serving mesh from the config"
            )
        import jax

        from cloud_tpu.parallel import mesh as mesh_lib

        devices = jax.devices()
        bound = len(devices)
        if cfg.mesh_shape is not None:
            want = cfg.mesh_shape[0] * cfg.mesh_shape[1]
            if want > bound:
                raise ValueError(
                    f"mesh_shape={cfg.mesh_shape} needs {want} "
                    f"device(s); only {bound} visible"
                )
        num_heads = int(self.config.num_heads)
        if cfg.layout == "auto":
            from cloud_tpu.parallel import planner
            # Generic array-pytree byte sum (despite the name — it is
            # the repo's one accounting helper for this).
            from cloud_tpu.training.optimizers import optimizer_state_bytes

            draft_bytes = 0
            if cfg.draft is not None:
                # The draft rides every chip (replicated unless its head
                # count happens to divide tp — budget the worst case):
                # params plus its own slot KV grid, no prefix pool.
                draft_bytes = optimizer_state_bytes(cfg.draft.params) + (
                    self._kv_bytes_estimate(
                        cfg.draft.config, include_prefix=False
                    )
                )
            plan = planner.plan_serve_layout(
                num_heads=num_heads,
                num_devices=(
                    cfg.mesh_shape[0] * cfg.mesh_shape[1]
                    if cfg.mesh_shape is not None else bound
                ),
                param_bytes=optimizer_state_bytes(self.params),
                kv_bytes=self._kv_bytes_estimate(),
                draft_bytes=draft_bytes,
                hbm_bytes_per_chip=cfg.hbm_bytes_per_chip,
            )
            tp, sp = plan.tp, plan.sp
            logger.info("serving layout auto-picked: %s", plan.description)
        else:
            tp, sp = cfg.mesh_shape
            if num_heads % tp:
                raise ValueError(
                    f"mesh_shape tp={tp} does not divide "
                    f"num_heads={num_heads}: the slot KV cache shards "
                    "by attention head, so the tensor-parallel degree "
                    "must divide the model's head count"
                )
        chips = tp * sp
        if chips <= 1:
            return (1, 1), 1
        self.mesh = mesh_lib.MeshSpec(
            sizes={mesh_lib.AXIS_SP: sp, mesh_lib.AXIS_TP: tp}
        ).build(devices[:chips])
        self._built_serving_mesh = True
        return (tp, sp), chips

    def _kv_bytes_estimate(self, model_config=None,
                           include_prefix: bool = True) -> int:
        """Total KV bytes the engine will allocate (slot grid + prefix
        pool) — the planner's auto-layout input, an estimate, not an
        allocator.  ``model_config`` sizes a different model's cache
        over the same grid (the speculative draft, which gets no
        prefix pool — ``include_prefix=False``)."""
        cfg = self.serve_config
        c = model_config if model_config is not None else self.config
        itemsize = 1 if cfg.kv_quant else np.dtype(c.dtype).itemsize
        # Per cached position: k + v across every layer and head (+ the
        # two f32 scale columns when quantized), or one latent row a
        # layer.
        if c.latent is not None:
            per_pos = c.num_layers * c.latent.row_width * itemsize
        else:
            per_pos = 2 * c.num_layers * c.kv_heads * (
                c.head_dim * itemsize + (4 if cfg.kv_quant else 0)
            )
        max_len = cfg.prompt_buckets[-1] + cfg.max_new_tokens
        positions = cfg.num_slots * max_len
        if include_prefix:
            positions += cfg.prefix_cache_blocks * cfg.prefix_block_tokens
        return per_pos * positions

    def _shard_params(self) -> None:
        """Place params per the rules table — heads/mlp/vocab dims over
        ``tp`` (the plan :func:`parallel.planner.plan_serve_layout`
        picked or ``mesh_shape`` pinned), everything else replicated —
        so every generation program lowers against sharded weights."""
        import jax

        from cloud_tpu.models import transformer
        from cloud_tpu.training.train import param_shardings

        axes = transformer.param_logical_axes(self.config)
        self.params = jax.device_put(
            self.params, param_shardings(self.mesh, axes, self.rules)
        )

    # -- speculative decoding ----------------------------------------------

    def _init_draft(self) -> None:
        """Arm draft-and-verify: validate the draft against the target,
        place its params/cache on the slice, and build the program
        cells.  The draft head-shards like the target when ``tp``
        divides its head count; otherwise params and its slot cache
        replicate across the slice (a draft is small — replication
        costs HBM the planner's draft term budgets for, and buys the
        verify path an undisturbed layout)."""
        import jax

        from cloud_tpu.models import generation

        cfg = self.serve_config
        dcfg = cfg.draft.config
        if int(dcfg.vocab_size) != int(self.config.vocab_size):
            raise ValueError(
                f"draft vocab_size={dcfg.vocab_size} != target "
                f"vocab_size={self.config.vocab_size}: acceptance "
                "compares token ids, so the two models must share a "
                "vocabulary"
            )
        generation.check_inference_supported(
            dcfg, self.rules, None, "speculative draft"
        )
        tp = self._slice_shape[0]
        self._draft_sharded = (
            self._slice_chips > 1 and int(dcfg.num_heads) % tp == 0
        )
        #: Mesh the draft programs constrain against: the slice when
        #: head-sharded, None (replicated compute) otherwise.
        self._draft_mesh = self.mesh if self._draft_sharded else None
        self._draft_params = cfg.draft.params

        def make_draft_grid():
            return generation.init_slot_cache(
                dcfg, cfg.num_slots, self._max_len, rules=self.rules,
                mesh=self._draft_mesh, kv_quant=cfg.kv_quant,
            )

        if self._draft_sharded:
            if self._built_serving_mesh:
                from cloud_tpu.models import transformer
                from cloud_tpu.training.train import param_shardings

                axes = transformer.param_logical_axes(dcfg)
                self._draft_params = jax.device_put(
                    cfg.draft.params,
                    param_shardings(self.mesh, axes, self.rules),
                )
            self._draft_cache = jax.jit(make_draft_grid)()
        elif self._slice_chips > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            replicated = NamedSharding(self.mesh, PartitionSpec())
            self._draft_params = jax.device_put(
                cfg.draft.params, replicated
            )
            self._draft_cache = jax.device_put(make_draft_grid(),
                                               replicated)
        else:
            self._draft_cache = make_draft_grid()
        self._draft_step = self._make_draft_step()
        self._verify_step = self._make_verify_step()

    def _make_draft_step(self):
        """The draft-proposal program: ONE compile serves the engine's
        life (static spec_k window over the whole grid)."""
        import jax

        from cloud_tpu.models import generation
        from cloud_tpu.training import compile_cache

        cfg = self.serve_config
        dcfg = cfg.draft.config

        def draft_fn(params, cache, state):
            self._draft_traces += 1
            return generation.draft_chunk_program(
                params, cache, state, dcfg, spec_k=cfg.draft.spec_k,
                rules=self.rules, mesh=self._draft_mesh,
            )

        donate = (1,) if self._donate else ()
        return compile_cache.AotStep(
            jax.jit(draft_fn, donate_argnums=donate),
            label="serve/draft_chunk",
        )

    def _make_verify_step(self):
        """The target's verify program: scores a whole spec_k window per
        slot in one dispatch and commits the accepted prefix.  ONE
        compile serves the engine's life."""
        import jax

        from cloud_tpu.models import generation
        from cloud_tpu.training import compile_cache

        cfg = self.serve_config

        def verify_fn(params, cache, state, window, *extra):
            self._verify_traces += 1
            return generation.verify_chunk_program(
                params, cache, state, window, self.config,
                sample=cfg.sample, rules=self.rules, mesh=self.mesh,
                with_summary=self._pipe_depth > 1,
                **self._paged_kwargs(extra),
            )

        donate = (1, 2) if self._donate else ()
        return compile_cache.AotStep(
            jax.jit(verify_fn, donate_argnums=donate),
            label="serve/verify_chunk",
        )

    def _draft_prefill_cell(self, bucket_len: int):
        """The draft-side prompt prefill for one bucket (one executable
        per bucket, like the insert programs)."""
        cell = self._draft_prefill_cells.get(bucket_len)
        if cell is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            dcfg = self.serve_config.draft.config

            def draft_prefill_fn(params, cache, tokens, prompt_len, slot):
                self._draft_prefill_traces += 1
                return generation.draft_prefill_slot_program(
                    params, cache, tokens, prompt_len, slot, dcfg,
                    rules=self.rules, mesh=self._draft_mesh,
                )

            donate = (1,) if self._donate else ()
            cell = compile_cache.AotStep(
                jax.jit(draft_prefill_fn, donate_argnums=donate),
                label=f"serve/draft_prefill_L{bucket_len}",
            )
            self._draft_prefill_cells[bucket_len] = cell
        return cell

    def _to_host(self, what: str, *arrays):
        """Materialize device results host-side: the wait for a
        program's result and its copy, spanned as ``serve/readback``.
        On a sharded slice this pull is also the sampling boundary's
        logits/token gather — the slice's only cross-chip reshard —
        spanned as ``serve/reshard`` inside it."""
        with tracing.span("serve/readback", what=what,
                          **{"pass": self._pass_seq}):
            if self._slice_chips > 1:
                with tracing.span("serve/reshard", what=what,
                                  chips=self._slice_chips):
                    return tuple(np.asarray(a) for a in arrays)
            return tuple(np.asarray(a) for a in arrays)

    # -- lifecycle ---------------------------------------------------------

    def set_trace_lane(self, lane: Optional[int]) -> None:
        """Adopt a timeline lane (``tracing.register_lane``): the
        scheduler thread stamps its spans with ``pid=lane`` so a merged
        fleet timeline renders this engine as its own labelled process
        row.  Duck-typed — the fleet replica calls it via ``hasattr``
        after building the engine, so non-engine fakes stay valid.
        Thread-safe (int swap); the scheduler re-reads it every pass."""
        self._trace_lane = lane

    def set_role(self, role: str) -> None:
        """Adopt a disaggregated-serving role (``"prefill"``,
        ``"decode"``, or ``"both"``): advertised through ``health()``/
        ``stats()`` so the fleet router can steer legs, and validated
        against the same requirement as the ctor knob.
        Duck-typed like :meth:`set_trace_lane` — the fleet replica
        calls it via ``hasattr``.  Thread-safe (str swap)."""
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got {role!r}"
            )
        if role != "both" and not self.serve_config.prefix_cache_blocks:
            raise ValueError(
                "role= (disaggregated serving) needs "
                "prefix_cache_blocks > 0 — the KV handoff "
                "exports/imports prefix-pool blocks"
            )
        self._role = role

    def start(self) -> "ServingEngine":
        """Launch the scheduler thread (idempotent)."""
        with self._cond:
            if self._closed:
                raise EngineClosedError("engine already closed")
            if self._thread is not None:
                return self
            self._thread = threading.Thread(
                target=self._scheduler_loop, daemon=True,
                name=SERVE_SCHEDULER_THREAD_NAME,
            )
            self._thread.start()
        return self

    def close(self, drain: bool = True, timeout: Optional[float] = None
              ) -> None:
        """Stop the engine: no more admissions, resolve what is owed.

        ``drain=True`` (default) serves every already-admitted request
        before the scheduler exits; ``drain=False`` fails waiting
        requests with :class:`EngineClosedError` immediately.  Joins the
        scheduler and any warmup worker — after ``close()`` returns, the
        engine owns zero live threads.
        """
        with self._cond:
            self._closed = True
            self._draining = drain
            # A never-started engine has no scheduler to drain through:
            # fail what waits rather than strand the futures forever.
            if not drain or self._thread is None:
                self._fail_pending_locked(
                    EngineClosedError("engine closed before dispatch")
                )
            self._cond.notify_all()
            thread = self._thread
        if thread is not None:
            thread.join(timeout)
        if self._warmup_plan is not None:
            self._warmup_plan.wait(timeout=timeout)
        # Watchdog-abandoned dispatches: a finite hang (chaos harness,
        # recovered device) unwinds here so the closed engine owns zero
        # live threads; a truly wedged one is left daemonized after the
        # bounded join (nothing in-process can reclaim it).
        for orphan in self._orphan_dispatches:
            orphan.join(timeout if timeout is not None else 60.0)
        self._orphan_dispatches = [
            t for t in self._orphan_dispatches if t.is_alive()
        ]
        now = time.perf_counter()
        self._qps.flush(now)
        self._tokens_rate.flush(now)

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    # -- submission --------------------------------------------------------

    @property
    def max_prompt_len(self) -> int:
        return self.serve_config.prompt_buckets[-1]

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               deadline_s: Optional[float] = None,
               priority: Optional[str] = None,
               stream: bool = False,
               on_token=None,
               trace: Optional[tracing.TraceContext] = None,
               handoff_export: bool = False,
               handoff: Optional[dict] = None) -> Future:
        """Enqueue one prompt; returns a Future of :class:`ServeResult`
        (or a :class:`~cloud_tpu.serving.qos.TokenStream` with
        ``stream=True``).

        ``prompt`` is a 1-D int sequence (length 1 ..
        ``prompt_buckets[-1]``).  ``max_new_tokens`` may be below the
        engine-wide ``serve_config.max_new_tokens`` (the row is trimmed —
        greedy decode is prefix-consistent, so this equals a shorter
        direct run); above it is an error.  Thread-safe; blocks or
        raises :class:`QueueFullError` at ``max_queue`` per the
        admission policy.

        ``deadline_s`` bounds the QUEUE WAIT: a request still waiting
        when its deadline passes is shed — its future fails with
        :class:`DeadlineExceededError` — without ever occupying a decode
        slot, so under overload capacity goes to requests whose caller
        is still listening (the load-shedding half of an SLO).  A
        request that reached the device before the deadline runs to
        completion; dispatch is never aborted mid-flight for deadlines
        (that is the watchdog's job, and only for hangs).

        ``priority`` names the request's QoS class: with
        ``ServeConfig.qos`` armed, slot admission orders by (SLO slack,
        weighted fairness debt) over these classes and brownout sheds
        the lowest class first; without it the tag is validated and
        recorded but never reorders anything (FIFO — byte-identical).
        ``stream=True`` returns a :class:`~cloud_tpu.serving.qos.
        TokenStream` fed per emitted token from the chunk-commit path;
        iterating yields the exact tokens the final result row carries.
        ``on_token`` is the cross-layer per-token hook the fleet uses to forward a
        stream — called as ``(index, token)`` on the scheduler thread.

        ``trace`` carries the caller's
        :class:`~cloud_tpu.monitoring.tracing.TraceContext` (the fleet
        mints one per request) so every span this request touches
        stamps its ``trace_id`` (and the result reports it).  Without
        one, ``submit`` mints the request's own while a collector is
        active; with tracing off nothing is minted and nothing
        recorded.

        ``handoff_export=True`` marks the request as a disaggregated
        PREFILL leg: right after its prompt blocks land in the prefix
        pool the engine downloads them host-side and rides the payload
        out on ``ServeResult.handoff`` for a decode replica to import.
        ``handoff=<payload>`` marks the DECODE leg: the payload's
        blocks are seeded into this engine's prefix trie before
        admission, so the request's normal prefix lookup hits them
        (ATTACH when paged, copy program otherwise) and decode runs
        token-identical to a colocated ``generate()``.  Both require
        a prefix cache; both default off — the engine stays
        byte-identical without them.
        """
        cfg = self.serve_config
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        if (handoff_export or handoff is not None) and self._prefix is None:
            raise ValueError(
                "handoff_export/handoff need prefix_cache_blocks > 0 — "
                "the KV handoff moves prefix-pool blocks"
            )
        if self._qos is not None:
            priority = self._qos.resolve_priority(priority)
        else:
            priority = qos_lib.validate_priority(priority)
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(
                f"prompt must be 1-D token ids, got shape {prompt.shape}"
            )
        n = int(prompt.shape[0])
        if not 1 <= n <= self.max_prompt_len:
            raise ValueError(
                f"prompt length {n} outside [1, {self.max_prompt_len}] "
                f"(prompt_buckets={cfg.prompt_buckets})"
            )
        m = cfg.max_new_tokens if max_new_tokens is None else int(
            max_new_tokens)
        if not 1 <= m <= cfg.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {m} outside [1, {cfg.max_new_tokens}]"
            )
        bucket_len = next(b for b in cfg.prompt_buckets if b >= n)
        submitted = time.perf_counter()
        if trace is None:
            trace = tracing.new_trace_context()  # None while tracing is off
        token_stream = TokenStream() if stream else None
        request = _Request(
            prompt=prompt, prompt_len=n, max_new_tokens=m,
            bucket_len=bucket_len, future=Future(),
            submitted=submitted,
            deadline=(
                None if deadline_s is None else submitted + deadline_s
            ),
            priority=priority, stream=token_stream, on_token=on_token,
            trace=trace,
            handoff_export=handoff_export, handoff=handoff,
        )
        if token_stream is not None:
            token_stream.trace_id = request.trace_id
            # EVERY resolution path (retire, shed, crash, close) goes
            # through the future; the callback closes the stream with
            # the same result/exception and back-fills any tokens the
            # incremental path did not deliver.
            request.future.add_done_callback(
                token_stream._complete_from_future
            )
        with self._cond:
            if self._closed:
                raise EngineClosedError("engine is closed")
            if self._waiting >= cfg.max_queue:
                if cfg.admission == "reject":
                    with self._stats_lock:
                        self._stats["rejected"] += 1
                    metrics.counter_inc("serve/rejected")
                    raise QueueFullError(
                        f"serving queue full ({cfg.max_queue} waiting); "
                        "retry with backoff or raise max_queue"
                    )
                while self._waiting >= cfg.max_queue and not self._closed:
                    self._cond.wait()
                if self._closed:
                    raise EngineClosedError("engine closed while blocked "
                                            "on admission")
            self._pending.setdefault(
                bucket_len, collections.deque()
            ).append(request)
            self._waiting += 1
            self._cond.notify_all()
        with self._stats_lock:
            self._stats["requests"] += 1
        metrics.counter_inc("serve/requests")
        return token_stream if token_stream is not None else request.future

    # -- warmup ------------------------------------------------------------

    def _make_chunk_step(self):
        """The single chunk-decode program: jitted once, optionally
        AOT-warmed; every dispatch carries the same static shapes, so
        one compile serves the engine's whole life (asserted via
        ``_chunk_traces`` in the retrace-guard tests)."""
        import jax

        from cloud_tpu.models import generation
        from cloud_tpu.training import compile_cache

        cfg = self.serve_config

        def chunk_fn(params, cache, state, rng, *extra):
            self._chunk_traces += 1
            return generation.decode_chunk_program(
                params, cache, state, self.config,
                chunk_size=cfg.chunk_tokens, sample=cfg.sample, rng=rng,
                rules=self.rules, mesh=self.mesh,
                with_summary=self._pipe_depth > 1,
                **self._paged_kwargs(extra),
            )

        donate = (1, 2) if self._donate else ()
        return compile_cache.AotStep(
            jax.jit(chunk_fn, donate_argnums=donate),
            label="serve/decode_chunk",
        )

    def _paged_extra(self) -> tuple:
        """The extra traced operands every paged dispatch appends: the
        prefix pool (when one exists — read-only, NEVER donated: the
        attention reads its blocks in place) and the host block table.
        Empty on the XLA path, so those cells' signatures — and their
        compiled programs — stay byte-identical to pre-paged."""
        if not self._paged:
            return ()
        if self._prefix_pool is not None:
            return (self._prefix_pool, self._block_table)
        return (self._block_table,)

    def _paged_kwargs(self, extra: tuple) -> dict:
        """Unpack ``_paged_extra``'s operands into the generation
        programs' paged kwargs (inside a cell trace)."""
        if not self._paged:
            return {}
        if len(extra) == 2:
            return {"pool": extra[0], "block_table": extra[1],
                    "use_pallas": self._paged_use_pallas}
        return {"block_table": extra[0],
                "use_pallas": self._paged_use_pallas}

    def _insert_cell(self, bucket_len: int):
        """The slot-insert program for one prompt bucket (compiled per
        bucket length; ``prompt_len``/``slot``/``max_new_tokens`` are
        traced scalars, so one executable serves every slot)."""
        cell = self._insert_cells.get(bucket_len)
        if cell is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            cfg = self.serve_config

            def insert_fn(params, cache, state, tokens, prompt_len, slot,
                          max_new, rng):
                self._insert_traces += 1
                return generation.insert_slot_program(
                    params, cache, state, tokens, prompt_len, slot,
                    max_new, self.config, sample=cfg.sample, rng=rng,
                    rules=self.rules, mesh=self.mesh,
                )

            donate = (1, 2) if self._donate else ()
            cell = compile_cache.AotStep(
                jax.jit(insert_fn, donate_argnums=donate),
                label=f"serve/insert_L{bucket_len}",
            )
            self._insert_cells[bucket_len] = cell
        return cell

    def _chunk_prefill_cell(self, width: int):
        """The bounded-prefill program for one chunk width.  With
        ``prefill_chunk_tokens`` set there is exactly one width (ONE
        compile serves every prompt, offset, and slot); with only the
        prefix cache on, suffix-after-hit prefills use the request's
        bucket length as the width — one compile per bucket, like the
        insert programs."""
        cell = self._chunk_prefill_cells.get(width)
        if cell is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def chunk_prefill_fn(params, cache, tokens, start, chunk_len,
                                 slot, *extra):
                self._prefill_chunk_traces += 1
                return generation.prefill_chunk_program(
                    params, cache, tokens, start, chunk_len, slot,
                    self.config, rules=self.rules, mesh=self.mesh,
                    **self._paged_kwargs(extra),
                )

            donate = (1,) if self._donate else ()
            cell = compile_cache.AotStep(
                jax.jit(chunk_prefill_fn, donate_argnums=donate),
                label=f"serve/prefill_chunk_W{width}",
            )
            self._chunk_prefill_cells[width] = cell
        return cell

    def _finalize_cell(self):
        """Arm-the-slot program for the final prefill chunk: logits are
        [1, vocab] whatever the bucket, so one compile serves the whole
        engine."""
        if self._finalize_step is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            cfg = self.serve_config

            def finalize_fn(state, logits, prompt_len, slot, max_new, rng):
                self._finalize_traces += 1
                return generation.finalize_slot_program(
                    state, logits, prompt_len, slot, max_new, self.config,
                    sample=cfg.sample, rng=rng,
                )

            donate = (0,) if self._donate else ()
            self._finalize_step = compile_cache.AotStep(
                jax.jit(finalize_fn, donate_argnums=donate),
                label="serve/finalize_slot",
            )
        return self._finalize_step

    def _copy_cell(self, bucket_len: int):
        """Pool-to-slot prefix copy for one prompt bucket (``n_blocks =
        bucket_len // prefix_block_tokens`` is static per bucket; the
        block-id vector is traced, so one executable serves every hit)."""
        cell = self._copy_cells.get(bucket_len)
        if cell is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def copy_fn(cache, pool, block_ids, slot):
                self._copy_traces += 1
                return generation.copy_prefix_program(
                    cache, pool, block_ids, slot
                )

            donate = (0,) if self._donate else ()
            cell = compile_cache.AotStep(
                jax.jit(copy_fn, donate_argnums=donate),
                label=f"serve/prefix_copy_L{bucket_len}",
            )
            self._copy_cells[bucket_len] = cell
        return cell

    def _save_cell(self, bucket_len: int):
        """Slot-to-pool block save for one prompt bucket (SKIP-sentinel
        ids are dropped by the scatter, so already-cached blocks are
        never rewritten)."""
        cell = self._save_cells.get(bucket_len)
        if cell is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def save_fn(pool, cache, slot, block_ids):
                self._save_traces += 1
                return generation.save_prefix_program(
                    pool, cache, slot, block_ids
                )

            donate = (0,) if self._donate else ()
            cell = compile_cache.AotStep(
                jax.jit(save_fn, donate_argnums=donate),
                label=f"serve/prefix_save_L{bucket_len}",
            )
            self._save_cells[bucket_len] = cell
        return cell

    def _download_cell(self):
        """Pool-row download for the DRAM tier's demote path (ONE
        compile — the block index is traced).  Reads only: the pool is
        never donated through it."""
        if self._download_step is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def download_fn(pool, block):
                self._download_traces += 1
                return generation.download_prefix_block(pool, block)

            self._download_step = compile_cache.AotStep(
                jax.jit(download_fn), label="serve/prefix_download"
            )
        return self._download_step

    def _swapin_cell(self):
        """Pool-row upload for the DRAM tier's promote path (ONE
        compile — block index traced, payload shapes static)."""
        if self._swapin_step is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def swapin_fn(pool, payload, block):
                self._swapin_traces += 1
                return generation.upload_prefix_block(pool, payload, block)

            donate = (0,) if self._donate else ()
            self._swapin_step = compile_cache.AotStep(
                jax.jit(swapin_fn, donate_argnums=donate),
                label="serve/prefix_swapin",
            )
        return self._swapin_step

    def _upload_cell(self):
        """Batched pool-row upload for the KV-handoff import seam (jit
        recompiles per padded batch-size bucket; AotStep's fallback
        handles the shape churn)."""
        if self._upload_step is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def upload_fn(pool, payloads, blocks):
                self._upload_traces += 1
                return generation.upload_prefix_blocks(
                    pool, payloads, blocks
                )

            donate = (0,) if self._donate else ()
            self._upload_step = compile_cache.AotStep(
                jax.jit(upload_fn, donate_argnums=donate),
                label="serve/kv_handoff",
            )
        return self._upload_step

    def _handoff_batch_blocks(self) -> int:
        """The FIXED batch size every handoff gather/scatter pads to —
        the longest exportable chain the config admits (capped by pool
        capacity).  One shape means ONE executable for every import
        and export, compiled by the first handoff (e.g. a warm-up
        request) instead of a fresh multi-second compile stalling the
        scheduler thread — and every active decode slot with it — the
        first time a dedup'd or truncated payload shows up with a new
        block count."""
        cfg = self.serve_config
        bound = cfg.prefix_cache_blocks
        if cfg.prompt_buckets:
            bound = min(
                bound,
                max(cfg.prompt_buckets) // cfg.prefix_block_tokens,
            )
        return max(1, bound)

    def _params_device(self):
        """The single device the params are committed to (None when
        sharded across several, or on exotic leaves) — the placement
        every piece of engine device-state follows."""
        import jax

        try:
            leaf = jax.tree_util.tree_leaves(self.params)[0]
            devices = leaf.devices()
            if len(devices) == 1:
                return next(iter(devices))
        except Exception:  # pragma: no cover - exotic param leaves
            pass
        return None

    def _pool_device(self):
        """The device the prefix pool is committed to (None when the
        pool is sharded or unallocated — device_put then falls back to
        its default placement).  Host-side payload uploads target this
        so a fleet of replicas pinned to distinct host devices never
        mixes a default-device payload into another device's pool."""
        try:
            leaf = next(iter(self._prefix_pool.values()))
            devices = leaf.devices()
            if len(devices) == 1:
                return next(iter(devices))
        except Exception:  # pragma: no cover - sharded/exotic pools
            pass
        return None

    def _export_cell(self):
        """Batched pool-row download for the KV-handoff export seam
        (pool is read, not donated — the rows stay live for serving)."""
        if self._export_step is None:
            import jax

            from cloud_tpu.models import generation
            from cloud_tpu.training import compile_cache

            def export_fn(pool, blocks):
                self._export_traces += 1
                return generation.download_prefix_blocks(pool, blocks)

            self._export_step = compile_cache.AotStep(
                jax.jit(export_fn), label="serve/kv_handoff",
            )
        return self._export_step

    def _demote_block(self, block: int):
        """The manager's ``demote_fn``: capture one HBM pool row's bytes
        host-side (numpy, outside jit) before the row is reused.  Runs
        on the scheduler thread during allocation, strictly BEFORE the
        save/swap-in dispatch that overwrites the row, so the bytes are
        exactly what the trie says they are.  Inside a burst
        (``_demote_burst``) the download is DEFERRED: the trie keeps a
        :class:`_DeferredPayload` placeholder and the burst's exit
        flushes every pending download as ONE supervised dispatch —
        one watchdog thread per burst, mirroring how the swap-in side
        budgets a whole plan.  Outside a burst the download (and its
        blocking device->host sync) runs under the watchdog like every
        other dispatch: a wedged device fails typed instead of hanging
        the scheduler on ``np.asarray`` forever."""
        import jax

        if self._demote_batch is not None:
            deferred = _DeferredPayload()
            self._demote_batch.append((int(block), deferred))
            metrics.counter_inc("serve/prefix_demotions")
            return deferred

        cell = self._download_cell()

        def dispatch():
            payload = cell(self._prefix_pool, np.int32(block))
            return jax.tree_util.tree_map(np.asarray, payload)

        with tracing.span("serve/prefix_demote", block=int(block)):
            payload = self._supervised("serve/prefix_demote", dispatch)
        metrics.counter_inc("serve/prefix_demotions")
        return payload

    @contextlib.contextmanager
    def _demote_burst(self):
        """Scope one prefix-cache allocation burst: every
        ``_demote_block`` inside defers its download into one batch,
        flushed at scope exit as ONE supervised dispatch (one watchdog
        thread per burst, mirroring how ``_dispatch_swapin`` budgets a
        whole plan) instead of paying a fresh thread per evicted block.
        Safe because the save/swap-in programs that reuse the evicted
        rows dispatch strictly AFTER this scope closes.  No-op when
        already inside a burst."""
        if self._demote_batch is not None:
            yield
            return
        batch: List[tuple] = []
        self._demote_batch = batch
        try:
            yield
        finally:
            self._demote_batch = None
            if batch:
                self._flush_demotes(batch)

    def _flush_demotes(self, batch: List[tuple]) -> None:
        """Download a burst's deferred demotions under ONE supervised
        dispatch, filling their placeholders — strictly before any row
        reuse (the caller's scope exits before the save/swap-in that
        overwrites the rows is dispatched)."""
        import jax

        cell = self._download_cell()

        def dispatch():
            for block, deferred in batch:
                payload = cell(self._prefix_pool, np.int32(block))
                deferred.value = jax.tree_util.tree_map(np.asarray, payload)
                deferred.filled = True

        with tracing.span("serve/prefix_demote", blocks=len(batch)):
            self._supervised("serve/prefix_demote", dispatch)

    def _dispatch_swapin(self, slot: int, plan,
                         trace_id: Optional[str] = None) -> None:
        """Upload a promotion plan's payloads into their fresh pool rows
        (``serve/prefix_swapin`` span — the swap-in stall the report
        attributes).  ``device_put`` is asynchronous: the host enqueues
        the transfers and the subsequent copy dispatch waits on them in
        dataflow order, off the scheduler's critical path."""
        import jax

        cell = self._swapin_cell()
        tokens = len(plan) * self.serve_config.prefix_block_tokens

        def dispatch():
            # One watchdog budget for the WHOLE plan (a fully demoted
            # long prefix can be dozens of blocks — one supervised
            # thread, not one per block); still one executable, one
            # upload dispatch per block.
            pool = self._prefix_pool
            device = self._pool_device()
            for _node, block, payload in plan:
                pool = cell(pool,
                            jax.device_put(_resolve_payload(payload),
                                           device),
                            np.int32(block))
            return pool

        span_attrs = dict(slot=slot, blocks=len(plan), tokens=tokens)
        if trace_id is not None:
            span_attrs["trace_id"] = trace_id
        with tracing.span("serve/prefix_swapin", **span_attrs):
            self._prefix_pool = self._supervised(
                "serve/prefix_swapin", dispatch
            )
        metrics.counter_inc("serve/prefix_swapins")
        metrics.counter_inc("serve/prefix_swapin_blocks", len(plan))

    def _start_warmup(self) -> None:
        """Queue AOT compiles for every program the slot grid will
        dispatch on the compile-ahead worker (one background thread,
        smallest programs first so early traffic warms soonest)."""
        import jax

        from cloud_tpu.training import compile_cache

        cfg = self.serve_config
        params_avals = compile_cache.abstract_state(self.params)
        context = compile_cache.context_key(mesh=self.mesh, rules=self.rules)
        rng_aval = jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype)
        cache_avals = compile_cache.abstract_state(self._grid_cache)
        state_avals = compile_cache.abstract_state(self._slot_state)
        scalar = jax.ShapeDtypeStruct((), np.int32)
        use_chunks = cfg.prefill_chunk_tokens is not None
        # Paged cells take the (pool,) table as extra operands —
        # warm with matching avals so the AOT executable is the one
        # traffic dispatches.
        paged_avals: tuple = ()
        if self._paged:
            table_aval = jax.ShapeDtypeStruct(
                self._block_table.shape, np.int32
            )
            if self._prefix_pool is not None:
                paged_avals = (
                    compile_cache.abstract_state(self._prefix_pool),
                    table_aval,
                )
            else:
                paged_avals = (table_aval,)
        jobs = []
        if not use_chunks:
            # One-shot inserts serve cold prefills (and with
            # chunking on they are never dispatched — skip them).
            for bucket_len in cfg.prompt_buckets:
                cell = self._insert_cell(bucket_len)
                tok_aval = jax.ShapeDtypeStruct(
                    (1, bucket_len), np.int32
                )
                jobs.append((cell, (
                    params_avals, cache_avals, state_avals, tok_aval,
                    scalar, scalar, scalar, rng_aval,
                ), context))
        # Chunked-prefill widths: THE chunk width when chunking is
        # on; the per-bucket suffix widths when only the prefix
        # cache drives partial prefills.
        if use_chunks:
            widths = (cfg.prefill_chunk_tokens,)
        elif self._prefix is not None:
            widths = cfg.prompt_buckets
        else:
            widths = ()
        for width in widths:
            cell = self._chunk_prefill_cell(width)
            tok_aval = jax.ShapeDtypeStruct((1, width), np.int32)
            jobs.append((cell, (
                params_avals, cache_avals, tok_aval, scalar, scalar,
                scalar, *paged_avals,
            ), context))
        if widths:
            logits_aval = jax.ShapeDtypeStruct(
                (1, self.config.vocab_size), np.float32
            )
            jobs.append((self._finalize_cell(), (
                state_avals, logits_aval, scalar, scalar, scalar,
                rng_aval,
            ), context))
        if self._prefix is not None:
            pool_avals = compile_cache.abstract_state(self._prefix_pool)
            for bucket_len in cfg.prompt_buckets:
                n_blocks = bucket_len // cfg.prefix_block_tokens
                if n_blocks < 1:
                    continue
                ids_aval = jax.ShapeDtypeStruct((n_blocks,), np.int32)
                if not self._paged:
                    # The paged path NEVER dispatches the copy
                    # program (hits attach); warming it would both
                    # waste a compile and advance _copy_traces,
                    # breaking the zero-copy assertion.
                    jobs.append((self._copy_cell(bucket_len), (
                        cache_avals, pool_avals, ids_aval, scalar,
                    ), context))
                jobs.append((self._save_cell(bucket_len), (
                    pool_avals, cache_avals, scalar, ids_aval,
                ), context))
            if cfg.prefix_dram_blocks:
                # The tier's block movers: one executable each.
                payload_avals = {
                    name: jax.ShapeDtypeStruct(
                        (leaf.shape[0],) + leaf.shape[2:], leaf.dtype
                    )
                    for name, leaf in self._prefix_pool.items()
                }
                jobs.append((self._download_cell(), (
                    pool_avals, scalar,
                ), context))
                jobs.append((self._swapin_cell(), (
                    pool_avals, payload_avals, scalar,
                ), context))
        if self._spec:
            # Speculation replaces the decode chunk wholesale: warm
            # the draft-prefill/draft/verify trio instead (the
            # never-dispatched chunk program is skipped, like the
            # insert programs under chunked prefill).
            draft_params_avals = compile_cache.abstract_state(
                self._draft_params
            )
            draft_cache_avals = compile_cache.abstract_state(
                self._draft_cache
            )
            for bucket_len in cfg.prompt_buckets:
                tok_aval = jax.ShapeDtypeStruct(
                    (1, bucket_len), np.int32
                )
                jobs.append((self._draft_prefill_cell(bucket_len), (
                    draft_params_avals, draft_cache_avals, tok_aval,
                    scalar, scalar,
                ), context))
            jobs.append((self._draft_step, (
                draft_params_avals, draft_cache_avals, state_avals,
            ), context))
            window_aval = jax.ShapeDtypeStruct(
                (cfg.num_slots, cfg.draft.spec_k), np.int32
            )
            jobs.append((self._verify_step, (
                params_avals, cache_avals, state_avals, window_aval,
                *paged_avals,
            ), context))
        else:
            jobs.append((self._chunk_step, (
                params_avals, cache_avals, state_avals, rng_aval,
                *paged_avals,
            ), context))
        self._warmup_plan = compile_cache.start_compile_ahead(jobs)

    def wait_ready(self, timeout: Optional[float] = None) -> None:
        """Block until the warmup plan has finished compiling (no-op
        without ``warmup=True``; compile failures were logged and those
        cells fall back to jit — see ``compile_cache.CompileAhead``)."""
        if self._warmup_plan is not None:
            self._warmup_plan.wait(timeout=timeout)

    # -- scheduler ---------------------------------------------------------

    def _fail_pending_locked(self, exc: BaseException) -> None:
        failed = 0
        for queue_ in self._pending.values():
            while queue_:
                request = queue_.popleft()
                self._waiting -= 1
                failed += 1
                try:
                    request.future.set_exception(exc)
                except InvalidStateError:  # pragma: no cover - cancelled
                    pass
        if failed:
            with self._stats_lock:
                self._stats["failed"] += failed

    def _shed_expired_locked(self, now: float) -> int:
        """Drop queued requests whose deadline passed (caller holds the
        lock).  Runs at every scheduling decision, so a request is shed
        at the first opportunity AFTER expiry — before it can claim a
        slot — with a typed failure the caller can distinguish from a
        crash.  Returns the shed count."""
        shed = 0
        shed_classes: List[str] = []
        for queue_ in self._pending.values():
            if not queue_ or not any(r.expired(now) for r in queue_):
                continue
            kept = collections.deque()
            while queue_:
                request = queue_.popleft()
                if not request.expired(now):
                    kept.append(request)
                    continue
                self._waiting -= 1
                shed += 1
                if request.priority is not None:
                    shed_classes.append(request.priority)
                waited = now - request.submitted
                tracing.record_span(
                    "serve/shed", request.submitted, now,
                    **_trace_attrs(request, bucket=request.bucket_len,
                                   reason="deadline"),
                )
                try:
                    request.future.set_exception(DeadlineExceededError(
                        f"request shed after waiting {waited:.3f}s; "
                        f"deadline_s="
                        f"{request.deadline - request.submitted:.3f}"
                    ))
                except InvalidStateError:  # pragma: no cover - cancelled
                    pass
            queue_.extend(kept)
        if shed:
            metrics.counter_inc("serve/deadline_exceeded", shed)
            with self._stats_lock:
                self._stats["shed"] += shed
                if self._qos is not None:
                    for name in shed_classes:
                        self._class_shed[name] += 1
            self._cond.notify_all()  # admission space freed
        return shed

    def _shed_brownout_locked(self, now: float) -> int:
        """Class-aware load shedding (caller holds the lock; no-op
        unless ``qos.brownout_queue_depth`` is armed): while the waiting
        set exceeds the brownout depth, shed from the LOWEST-weight
        class first — newest arrival first within a class, so the
        requests that waited longest keep their place — with a typed
        :class:`BrownoutShedError`.  The class-ordered generalization
        of the deadline shed: batch sheds before interactive."""
        if (self._qos is None
                or self._qos.brownout_queue_depth is None
                or self._waiting <= self._qos.brownout_queue_depth):
            return 0
        waiting_at_trigger = self._waiting
        excess = waiting_at_trigger - self._qos.brownout_queue_depth
        # qos_lib owns the shed order; this method owns the engine's
        # queue mechanics.
        victims = qos_lib.brownout_victims(
            (r for queue_ in self._pending.values() for r in queue_),
            excess, self._qos,
        )
        shed = 0
        shed_classes: List[str] = []
        for request in victims:
            self._pending[request.bucket_len].remove(request)
            self._waiting -= 1
            shed += 1
            shed_classes.append(request.priority)
            tracing.record_span(
                "serve/shed", request.submitted, now,
                **_trace_attrs(request, bucket=request.bucket_len,
                               reason="brownout",
                               priority=request.priority),
            )
            try:
                request.future.set_exception(BrownoutShedError(
                    f"request shed under brownout: {waiting_at_trigger}"
                    f" waiting > brownout_queue_depth="
                    f"{self._qos.brownout_queue_depth} and "
                    f"{request.priority!r} is the lowest class still "
                    "queued"
                ))
            except InvalidStateError:  # pragma: no cover - cancelled
                pass
        if shed:
            metrics.counter_inc("serve/brownout_shed", shed)
            with self._stats_lock:
                self._stats["shed"] += shed
                self._stats["brownout_shed"] += shed
                for name in shed_classes:
                    self._class_shed[name] += 1
            self._cond.notify_all()  # admission space freed
        return shed

    # -- watchdog ----------------------------------------------------------

    def _supervised(self, label: str, fn):
        """Run one device dispatch under the watchdog (no-op without
        ``dispatch_timeout_s``).

        The dispatch runs on a short-lived supervised thread; if it
        does not finish inside the budget the scheduler raises
        :class:`DispatchTimeoutError` — failing the dispatch's requests
        and (via the crash path) the engine — rather than blocking
        forever on a wedged device program.  The abandoned thread is
        remembered and joined by ``close()``: a finite hang (the chaos
        harness's ``hang`` mode, a recovered device) unwinds without a
        leak; a truly wedged program leaves one daemon thread, which is
        the best Python can do short of killing the process.

        Spanned as ``serve/launch``: the host's time to enqueue the
        program(s) ``fn`` dispatches (where ``fn`` itself waits for its
        result, the wait is inside).
        """
        timeout = self.serve_config.dispatch_timeout_s
        self._last_dispatch_ts = time.perf_counter()
        with tracing.span("serve/launch", what=label,
                          **{"pass": self._pass_seq}):
            if timeout is None:
                return fn()
            return self._run_under_watchdog(label, fn, timeout)

    def _run_under_watchdog(self, label: str, fn, timeout: float):
        box: dict = {}
        done = threading.Event()

        def runner():
            try:
                box["result"] = fn()
            except BaseException as exc:  # noqa: BLE001 — rethrown below
                box["error"] = exc
            finally:
                done.set()

        thread = threading.Thread(
            target=runner, daemon=True, name=SERVE_DISPATCH_THREAD_NAME
        )
        thread.start()
        if not done.wait(timeout):
            self._orphan_dispatches.append(thread)
            self._unhealthy_reason = (
                f"{label} exceeded dispatch_timeout_s={timeout}"
            )
            metrics.counter_inc("serve/watchdog_timeouts")
            with self._stats_lock:
                self._stats["watchdog_timeouts"] += 1
            raise DispatchTimeoutError(self._unhealthy_reason)
        thread.join()
        if "error" in box:
            raise box["error"]
        return box["result"]

    def _split_rng(self):
        """Advance the engine's key and return a fresh one for the next
        program.  The split is itself a tiny device program, so it is
        spanned as a launch: the device's idle time round it is then
        named in a profile."""
        with tracing.span("serve/launch", what="rng_split",
                          **{"pass": self._pass_seq}):
            self._rng, key = self._split_key(self._rng)
        return key

    def _scheduler_loop(self) -> None:
        try:
            self._continuous_loop()
        except BaseException as exc:  # noqa: BLE001 — scheduler must not
            # die silently: fail everything still queued and in flight,
            # and refuse new work.
            logger.exception("serving scheduler crashed")
            if self._unhealthy_reason is None:
                self._unhealthy_reason = f"scheduler crashed: {exc!r}"
            with self._cond:
                self._closed = True
                self._fail_pending_locked(exc)
                self._cond.notify_all()
            self._dispose_inflight()
            self._fail_live_slots(exc)

    # -- continuous scheduler ----------------------------------------------

    def _continuous_loop(self) -> None:
        """Iteration-level scheduling: fill free slots from the queue,
        advance at most ONE prefill chunk, run one decode chunk, retire
        what finished, repeat.  The one-prefill-chunk bound is the
        chunked-prefill latency contract: however long an arriving
        prompt, in-flight decode waits at most one ``prefill_chunk_
        tokens`` dispatch before its next chunk (without chunking a
        prefill task is a single whole-suffix chunk, so the pass shape
        degenerates to the old insert-then-decode loop).  A dispatch
        failure here is fatal to the grid (the cache/state pytrees may
        be half-donated), so it propagates to the crash handler, which
        fails every queued and in-flight request.  Each iteration that
        did work is one ``serve/pass`` span, numbered, from the pop
        that found its work to the end of its drain."""
        while True:
            # Re-assert the timeline lane each pass: the owning replica
            # tags the engine AFTER this thread is already running (and
            # a restarted engine may inherit the replica's lane late).
            if self._trace_lane is not None:
                tracing.set_thread_lane(self._trace_lane)
            inserts: List[Tuple[_Request, int]] = []
            abort = False
            with self._cond:
                while True:
                    if self._closed and not self._draining:
                        abort = True
                        break
                    # The pass opens at the pop that finds its work:
                    # never over the wait below.
                    pass_start = time.perf_counter()
                    self._pop_inserts_locked(inserts)
                    if (inserts or self._active_slots
                            or self._prefill_tasks or self._inflight):
                        break
                    if self._closed:
                        return  # draining and nothing left to serve
                    self._cond.wait()
            if abort:
                self._prefill_tasks.clear()
                self._dispose_inflight()
                self._fail_live_slots(EngineClosedError(
                    "engine closed without draining in-flight requests"
                ))
                return
            self._pass_seq += 1
            self._pass_active = 0
            self._pass_assignments_here = 0
            try:
                for idx, (request, slot) in enumerate(inserts):
                    self._admit_request(request, slot)
            except BaseException as exc:
                # Requests popped from the queue but not yet in the slot
                # table are invisible to the crash handler: fail them
                # here (the in-flight one may already be tabled — its
                # InvalidStateError is suppressed), then let the crash
                # handler take the grid down.
                failed = 0
                for request, _ in inserts[idx:]:
                    try:
                        request.future.set_exception(exc)
                        failed += 1
                    except InvalidStateError:  # pragma: no cover
                        pass
                if failed:
                    with self._stats_lock:
                        self._stats["failed"] += failed
                raise
            if self._prefill_tasks:
                self._advance_prefill()
            if self._active_slots:
                if self._pipe_depth > 1:
                    # Survivor guard: the host knows every slot's budget,
                    # so it can tell — without syncing — when the work
                    # already in flight will exhaust ALL of them.  A
                    # further dispatch would be pure dead rows (the
                    # device active mask has already killed every slot);
                    # skip it and let the drain below run the pass like
                    # depth 1 instead.  Eos only ends a slot EARLIER
                    # than the budget, so the guard can at worst allow
                    # a partially-dead chunk — never block a live one.
                    if self._predict_survivors():
                        if self._spec:
                            self._dispatch_spec_chunk_async()
                        else:
                            self._dispatch_chunk_async()
                elif self._spec:
                    self._dispatch_spec_chunk()
                else:
                    self._dispatch_chunk()
            # Drain half of the pipelined pass (the ring is ALWAYS empty
            # at depth 1 — the synchronous paths above never grow it, so
            # this loop is a no-op and the default flow is unchanged).
            # While any slot can outlive the work in flight, keep
            # depth-1 chunks in the ring; once nothing can (wave end,
            # idle engine), drain dry so every pass boundary — and a
            # graceful close() — sees an empty ring with all emissions
            # committed and futures settled.  The condition is
            # re-evaluated per drain: a drain that retires the last
            # active slot flips the target to zero and flushes the
            # trailing speculative chunk (whose rows are all masked).
            while len(self._inflight) > (
                    self._pipe_depth - 1
                    if self._active_slots and self._predict_survivors()
                    else 0):
                self._drain_inflight()
            if tracing.enabled():
                # Recorded, not a context manager: a mirrored pass
                # would cover every idle gap of a profile at least as
                # well as its leaves and take them all.
                tracing.record_span(
                    "serve/pass", pass_start, time.perf_counter(),
                    **{"pass": self._pass_seq},
                    inserts=len(inserts), active=self._pass_active,
                    prompt_tokens=sum(r.prompt_len for r, _ in inserts),
                    bucket_tokens=sum(r.bucket_len for r, _ in inserts),
                    computed_tokens=sum(
                        self._insert_rows(r) for r, _ in inserts),
                    kv_rows_in_use=self._kv_rows_in_use,
                    kv_rows_reserved=self._kv_rows_reserved,
                    state_rows_in_use=self._state_rows_in_use,
                    assignments_here=self._pass_assignments_here,
                )

    def _pop_inserts_locked(self, inserts) -> None:
        """Claim one free slot per waiting request — oldest submit first
        across every bucket (FIFO — a minority bucket cannot starve),
        or, with QoS armed, by (SLO slack, weighted fairness debt)
        over the whole waiting set (``qos.QosScheduler``: earliest
        expiring SLO while slack remains, weighted fair shares once
        saturation blows every SLO).  Caller holds the lock; dispatch
        happens outside it."""
        now = time.perf_counter()
        self._shed_expired_locked(now)
        if self._qos_sched is not None:
            self._shed_brownout_locked(now)
            self._pop_inserts_qos_locked(inserts, now)
            return
        popped = False
        while self._free_slots:
            oldest = None
            oldest_queue = None
            for queue_ in self._pending.values():
                if queue_ and (
                    oldest is None or queue_[0].submitted < oldest.submitted
                ):
                    oldest = queue_[0]
                    oldest_queue = queue_
            if oldest is None:
                break
            oldest_queue.popleft()
            self._waiting -= 1
            popped = True
            inserts.append((oldest, self._free_slots.pop()))
        if popped:
            self._cond.notify_all()  # admission space freed

    def _pop_inserts_qos_locked(self, inserts, now: float) -> None:
        """The QoS admission order: consider EVERY waiting request
        (class order is orthogonal to the bucket queues, which exist
        for compiled-program selection), admit
        ``QosScheduler.select``'s pick per free slot, and charge the
        admitted class its fairness debt."""
        popped = False
        while self._free_slots:
            best = self._qos_sched.select(
                (r for queue_ in self._pending.values() for r in queue_),
                now,
            )
            if best is None:
                break
            self._pending[best.bucket_len].remove(best)
            self._waiting -= 1
            popped = True
            self._qos_sched.charge(
                best.priority,
                self._qos.request_cost(best.prompt_len,
                                       best.max_new_tokens),
            )
            inserts.append((best, self._free_slots.pop()))
        if popped:
            self._cond.notify_all()  # admission space freed

    def _admit_request(self, request: _Request, slot: int) -> None:
        """Route one popped request into its claimed slot.

        With neither prefix caching nor chunked prefill configured this
        IS the PR 5 one-shot insert (``_insert_request``).  Otherwise:
        look up the longest cached prefix (``serve/prefix_lookup``),
        pin its blocks — an acquire that fails because the blocks were
        evicted since the match falls back to a cold prefill, never a
        stale copy — copy the hit's KV into the slot row, and queue a
        :class:`_PrefillTask` for the uncached suffix, which the loop
        advances one chunk per pass."""
        cfg = self.serve_config
        use_chunks = cfg.prefill_chunk_tokens is not None
        if self._block_table is not None:
            # Fresh claim: every page reads the slot row until a hit
            # attaches pool blocks below.
            self._block_table[slot, :] = -1
        # Disaggregated decode leg: seed the handoff payload's blocks
        # into the trie FIRST, so the ordinary lookup below hits them.
        # The seed refs are dropped once the acquire has its own pins.
        seed_held: List[object] = []
        if request.handoff is not None and self._prefix is not None:
            seed_held = self._import_handoff(request)
        hit = None
        held: List[object] = []
        swapin_plan = None
        if self._prefix is not None:
            with tracing.span(
                "serve/prefix_lookup",
                **_trace_attrs(request, bucket=request.bucket_len,
                               slot=slot),
            ) as span:
                candidate = self._prefix.match(request.prompt.tolist())
                faults.fault_point("serve.prefix_acquire")
                if candidate:
                    if cfg.prefix_dram_blocks:
                        # Tiered pin: promote any DRAM-demoted blocks
                        # back into fresh HBM rows.  None = the swap-in
                        # lost the race (blocks evicted since the match,
                        # or HBM fully pinned): fall back to a cold
                        # prefill — the PR 9 revalidation, extended.
                        with self._demote_burst():
                            swapin_plan = self._prefix.acquire_swapin(
                                candidate
                            )
                        if swapin_plan is not None:
                            hit = candidate
                            held.extend(candidate.nodes)
                    elif self._prefix.acquire(candidate):
                        hit = candidate
                        held.extend(candidate.nodes)
                span.set_attribute("hit", hit is not None)
                span.set_attribute(
                    "hit_tokens", hit.tokens if hit is not None else 0
                )
                span.set_attribute("dram", bool(swapin_plan))
            if hit is not None:
                metrics.counter_inc("serve/prefix_hits")
                metrics.counter_inc("serve/prefix_hit_tokens", hit.tokens)
                with self._stats_lock:
                    self._stats["prefix_hits"] += 1
            else:
                metrics.counter_inc("serve/prefix_misses")
                with self._stats_lock:
                    self._stats["prefix_misses"] += 1
        if seed_held:
            # The acquire above pinned what it needs; the seed's
            # bridging references have done their job.
            self._prefix.release(seed_held)
        if hit is None and not use_chunks:
            self._insert_request(request, slot)
            return
        now = request.admitted = time.perf_counter()
        tracing.record_span(
            "serve/queue_wait", request.submitted, now,
            **_trace_attrs(request, bucket=request.bucket_len, slot=slot),
        )
        # Tabled BEFORE any dispatch: a grid crash mid-prefill fails
        # this request along with the live slots.
        self._slot_table[slot] = _Slot(
            request=request, tokens=[], prefix_nodes=held
        )
        if swapin_plan:
            # The promoted rows must hold their bytes before the copy
            # below reads them (dataflow-ordered on device).
            self._dispatch_swapin(slot, swapin_plan,
                                  trace_id=request.trace_id)
        if hit is not None and hit.tokens:
            if self._paged:
                self._attach_prefix(request, slot, hit)
            else:
                self._dispatch_copy(request, slot, hit)
        width = (
            cfg.prefill_chunk_tokens if use_chunks else request.bucket_len
        )
        self._prefill_tasks.append(_PrefillTask(
            request=request, slot=slot, chunk_width=width,
            next_pos=hit.tokens if hit is not None else 0, hit=hit,
        ))

    def _attach_prefix(self, request: _Request, slot: int, hit) -> None:
        """The paged path's whole prefix hit: point the slot's leading
        block-table pages at the hit's pool blocks.  Zero device
        dispatch — the chunk/prefill/verify programs read the pool rows
        in place through the table.  Safe against eviction because the
        hit's blocks are ref-pinned from the acquire in
        ``_admit_request`` until ``_retire_slot`` releases them: a
        pinned pool row is never evicted, demoted, or rewritten (the
        save program's SKIP sentinel drops already-cached blocks), so
        the bytes the table points at are immutable for the slot's
        whole life."""
        blocks = hit.blocks
        with tracing.span(
            "serve/prefix_attach",
            **_trace_attrs(request, slot=slot, blocks=len(blocks),
                           tokens=hit.tokens),
        ):
            self._block_table[slot, :len(blocks)] = np.asarray(
                blocks, np.int32
            )
        metrics.counter_inc("serve/prefix_attached_blocks", len(blocks))
        with self._stats_lock:
            self._stats["prefix_attaches"] += 1

    def _dispatch_copy(self, request: _Request, slot: int, hit) -> None:
        """Copy an acquired hit's pool blocks into the slot row.  The
        id vector pads with the hit's own last block (the gather clamps
        out-of-range reads; padding with a REAL id keeps the copied-
        then-overwritten garbage deterministic)."""
        cfg = self.serve_config
        n_blocks = request.bucket_len // cfg.prefix_block_tokens
        blocks = hit.blocks
        ids = np.full((n_blocks,), blocks[-1], np.int32)
        ids[:len(blocks)] = blocks
        cell = self._copy_cell(request.bucket_len)

        def dispatch():
            return cell(self._grid_cache, self._prefix_pool, ids,
                        np.int32(slot))

        with tracing.span(
            "serve/prefix_copy",
            **_trace_attrs(request, slot=slot, blocks=len(blocks),
                           tokens=hit.tokens),
        ):
            self._grid_cache = self._supervised(
                "serve/prefix_copy", dispatch
            )

    def _advance_prefill(self) -> None:
        """One prefill-chunk dispatch for the OLDEST mid-prefill request
        — at most one per scheduler pass, so the next decode chunk is
        never more than one chunk dispatch away.  The final chunk's
        logits arm the slot (``_finalize_insert``)."""
        task = self._prefill_tasks[0]
        request = task.request
        width = task.chunk_width
        start_pos = task.next_pos
        clen = min(request.prompt_len - start_pos, width)
        tokens = np.zeros((1, width), np.int32)
        tokens[0, :clen] = request.prompt[start_pos:start_pos + clen]
        cell = self._chunk_prefill_cell(width)

        def dispatch():
            faults.fault_point("serve.prefill")
            return cell(
                self.params, self._grid_cache, tokens, np.int32(start_pos),
                np.int32(clen), np.int32(task.slot),
                *self._paged_extra(),
            )

        with tracing.span(
            "serve/prefill_chunk",
            **_trace_attrs(request, bucket=request.bucket_len,
                           slot=task.slot, start=start_pos, tokens=clen),
        ):
            self._grid_cache, logits = self._supervised(
                "serve/prefill_chunk", dispatch
            )
        task.next_pos = start_pos + clen
        metrics.counter_inc("serve/prefill_chunks")
        with self._stats_lock:
            self._stats["prefill_chunks"] += 1
        if task.next_pos >= request.prompt_len:
            self._prefill_tasks.popleft()
            self._finalize_insert(task, logits)

    def _finalize_insert(self, task: _PrefillTask, logits) -> None:
        """Arm a fully-prefilled slot from its last chunk's logits (the
        device twin of what ``insert_slot_program`` does inline), save
        the prompt's new prefix blocks, and activate — or retire, when
        the first token already finishes the request."""
        request, slot = task.request, task.slot
        fin_rng = self._split_rng()
        cell = self._finalize_cell()

        def dispatch():
            return cell(
                self._slot_state, logits, np.int32(request.prompt_len),
                np.int32(slot), np.int32(request.max_new_tokens), fin_rng,
            )

        with tracing.span("serve/prefill_finalize",
                          **_trace_attrs(request, slot=slot)):
            self._slot_state, tok0 = self._supervised(
                "serve/prefill_finalize", dispatch
            )
            tok0 = int(self._to_host("finalize_tok0", tok0)[0])
        entry = self._slot_table[slot]
        entry.tokens = [tok0]
        entry.first_token_ts = time.perf_counter()
        self._feed_entry(entry)
        self._save_prefix_blocks(request, slot, already=task.hit)
        self._export_handoff(request, slot)
        self._activate_or_retire(slot, request, tok0)

    def _save_prefix_blocks(self, request: _Request, slot: int,
                            already=None) -> None:
        """Donate a just-prefilled prompt's new full blocks to the pool
        (no-op without the prefix cache).  The slot holds references on
        everything it walked — copied-in hit and saved-out new blocks —
        until it retires."""
        if self._prefix is None:
            return
        from cloud_tpu.serving.prefix_cache import SKIP_BLOCK, PrefixHit

        cfg = self.serve_config
        if self._inflight:
            # Pipelined scheduling: a chunk dispatched last pass is
            # still in flight, so this save-back's pool writes land
            # AFTER it on the device stream (dataflow through the
            # donated grid cache orders them) — the trie entry created
            # below is deferred in exactly that sense.  Counted so the
            # parity tests can assert the ordering path was exercised
            # (prefix_cache.py "Save-back ordering under pipelined
            # scheduling").
            self._prefix.note_deferred_save()
        if already is None:
            already = PrefixHit(nodes=(), tokens=0)
        with self._demote_burst():
            held, created, evicted = self._prefix.insert(
                request.prompt.tolist(), already
            )
        if evicted:
            metrics.counter_inc("serve/prefix_evictions", evicted)
        entry = self._slot_table[slot]
        entry.prefix_nodes.extend(held)
        if not created:
            return
        n_blocks = request.bucket_len // cfg.prefix_block_tokens
        ids = np.full((n_blocks,), SKIP_BLOCK, np.int32)
        created_set = {id(node) for node in created}
        base = already.tokens // cfg.prefix_block_tokens
        for i, node in enumerate(held):
            if id(node) in created_set:
                ids[base + i] = node.block
        cell = self._save_cell(request.bucket_len)

        def dispatch():
            return cell(self._prefix_pool, self._grid_cache,
                        np.int32(slot), ids)

        with tracing.span("serve/prefix_save", slot=slot,
                          blocks=len(created)):
            self._prefix_pool = self._supervised(
                "serve/prefix_save", dispatch
            )
        metrics.counter_inc("serve/prefix_saved_blocks", len(created))

    def _export_handoff(self, request: _Request, slot: int) -> None:
        """Build a disaggregated-serving handoff payload from a
        just-prefilled slot's prefix-pool blocks (no-op unless the
        request asked via ``handoff_export`` and a prefix cache is
        armed).  Runs right after ``_save_prefix_blocks`` — the slot's
        ``prefix_nodes`` is the prompt's full root-down block chain,
        ref-pinned until retire, so the rows are immutable while the
        batched download (ONE supervised dispatch, like the demote
        flush) captures them via ``download_prefix_block`` — per-leaf
        numpy pytrees, the DRAM tier's exact serialization, so kv_quant
        int8 blocks and their scale leaves ride verbatim.  The payload
        parks on the slot and rides out on ``ServeResult.handoff``."""
        if not request.handoff_export or self._prefix is None:
            return
        import jax

        cfg = self.serve_config
        entry = self._slot_table[slot]
        nodes = list(entry.prefix_nodes)
        payload = {
            "version": 1,
            "block_tokens": cfg.prefix_block_tokens,
            "covered_tokens": len(nodes) * cfg.prefix_block_tokens,
            "keys": [tuple(node.key) for node in nodes],
            "payloads": [],
        }
        if nodes:
            cell = self._export_cell()
            blocks = [int(node.block) for node in nodes]
            # One gather for the whole chain, padded to the config's
            # fixed batch size (clipped pad rows are discarded below)
            # so every export reuses one executable.
            n = len(blocks)
            bucket = max(self._handoff_batch_blocks(), n)
            block_ids = np.asarray(
                blocks + [0] * (bucket - n), np.int32
            )

            def dispatch():
                host = jax.tree_util.tree_map(
                    np.asarray, cell(self._prefix_pool, block_ids)
                )
                # Per-block copies: a payload must not pin the whole
                # stacked gather in host memory once the pool/trie
                # dedups it down to a few blocks.
                return [
                    {name: leaf[i].copy() for name, leaf in host.items()}
                    for i in range(n)
                ]

            with tracing.span(
                "serve/kv_handoff",
                **_trace_attrs(request, direction="export", slot=slot,
                               blocks=len(nodes)),
            ):
                payload["payloads"] = self._supervised(
                    "serve/kv_handoff", dispatch
                )
        entry.handoff = payload
        with self._stats_lock:
            self._stats["handoff_exports"] += 1
            self._stats["handoff_export_blocks"] += len(nodes)
        metrics.counter_inc("serve/handoff_exports")
        metrics.counter_inc("serve/handoff_export_blocks", len(nodes))

    def _import_handoff(self, request: _Request) -> List[object]:
        """Seed this engine's prefix trie with a handoff payload's
        blocks, so the request's ordinary admission lookup (just below
        in ``_admit_request``) sees a plain prefix hit — ATTACH when
        paged, the copy program otherwise.  Uploads only the blocks the
        trie did NOT already hold (the cross-replica dedup), batched
        under ONE supervised dispatch.  Returns the seeded nodes, each
        carrying one reference the caller drops once its own acquire
        has pinned the hit.  Malformed/partial payloads import less —
        the suffix prefill covers the rest, never a correctness
        dependency."""
        import jax

        cfg = self.serve_config
        payload = request.handoff
        if int(payload.get("block_tokens") or 0) != cfg.prefix_block_tokens:
            return []
        keys = list(payload.get("keys") or ())
        payloads = list(payload.get("payloads") or ())
        usable = 0
        for i, key in enumerate(keys):
            if (i < len(payloads) and payloads[i] is not None
                    and len(key) == cfg.prefix_block_tokens):
                usable += 1
            else:
                break
        if not usable:
            return []
        with tracing.span(
            "serve/kv_handoff",
            **_trace_attrs(request, direction="import", blocks=usable),
        ) as span:
            with self._demote_burst():
                held, created = self._prefix.seed_blocks(keys[:usable])
            span.set_attribute("seeded", len(held))
            span.set_attribute("uploaded", len(created))
            if created:
                cell = self._upload_cell()
                created_ids = {id(node) for node in created}
                uploads = [
                    (int(node.block), payloads[i])
                    for i, node in enumerate(held)
                    if id(node) in created_ids
                ]
                # One scatter for the whole batch, padded to the
                # config's fixed batch size so every import reuses one
                # executable; pad rows carry an out-of-range block
                # index and are dropped in-program.
                n = len(uploads)
                bucket = max(self._handoff_batch_blocks(), n)
                pad = bucket - n
                drop = self.serve_config.prefix_cache_blocks
                block_ids = np.asarray(
                    [b for b, _ in uploads] + [drop] * pad, np.int32
                )
                stacked = {}
                for name in uploads[0][1]:
                    arr = np.stack([p[name] for _, p in uploads])
                    if pad:
                        arr = np.concatenate([
                            arr,
                            np.zeros((pad,) + arr.shape[1:], arr.dtype),
                        ])
                    stacked[name] = arr

                def dispatch():
                    # Upload to the pool's own device: on multi-device
                    # hosts (one virtual device per replica) a bare
                    # device_put would land on the process default
                    # device and conflict with the committed pool.
                    return cell(self._prefix_pool,
                                jax.device_put(stacked,
                                               self._pool_device()),
                                block_ids)

                self._prefix_pool = self._supervised(
                    "serve/kv_handoff", dispatch
                )
        with self._stats_lock:
            self._stats["handoff_imports"] += 1
            self._stats["handoff_import_blocks"] += len(held)
        metrics.counter_inc("serve/handoff_imports")
        metrics.counter_inc("serve/handoff_import_blocks", len(held))
        return held

    def _activate_or_retire(self, slot: int, request: _Request,
                            tok0: int) -> None:
        """Post-prefill slot accounting, shared by the one-shot insert
        and the chunked finalize (mirrors the programs' active0 gate)."""
        with self._stats_lock:
            self._stats["inserts"] += 1
            self._stats["decode_slot_steps"] += 1  # the prefill emission
            self._stats["useful_decode_tokens"] += 1
        metrics.counter_inc("serve/slot_inserts")
        eos = self.serve_config.sample.eos_id
        if request.max_new_tokens == 1 or (eos is not None and tok0 == eos):
            # Finished at insert (mirrors the program's active0 gate).
            self._retire_slot(slot)
        else:
            if self._spec:
                # The slot will decode: give the draft its prompt KV
                # before the next proposal round (a retired-at-insert
                # slot never needs one).
                self._dispatch_draft_prefill(request, slot)
            self._active_slots.add(slot)

    def _insert_rows(self, request: _Request) -> int:
        """The rows the insert program computes for ``request``: its
        own rule, asked of it."""
        from cloud_tpu.models import generation

        return generation.prefill_rows_computed(
            request.bucket_len, request.prompt_len, self.rules, self.mesh)

    def _insert_request(self, request: _Request, slot: int) -> None:
        start = request.admitted = time.perf_counter()
        from cloud_tpu.models import generation

        computed = self._insert_rows(request)
        pairs_run, pairs_width = generation.prefill_flash_tiles(
            self.config, request.bucket_len, request.prompt_len,
            self.rules, self.mesh)
        with self._stats_lock:
            self._stats["insert_rows_bucket"] += request.bucket_len
            self._stats["insert_rows_computed"] += computed
            self._stats["flash_pairs_run"] += pairs_run
            self._stats["flash_pairs_width"] += pairs_width
        tracing.record_span(
            "serve/queue_wait", request.submitted, start,
            **_trace_attrs(request, bucket=request.bucket_len, slot=slot),
        )
        tokens = np.zeros((1, request.bucket_len), np.int32)
        tokens[0, :request.prompt_len] = request.prompt
        cell = self._insert_cell(request.bucket_len)
        insert_rng = self._split_rng()

        def dispatch():
            faults.fault_point("serve.prefill")
            return cell(
                self.params, self._grid_cache, self._slot_state, tokens,
                np.int32(request.prompt_len), np.int32(slot),
                np.int32(request.max_new_tokens), insert_rng,
            )

        with tracing.span(
            "serve/prefill",
            **_trace_attrs(request, bucket=request.bucket_len, slot=slot,
                           **{"pass": self._pass_seq}),
        ):
            self._grid_cache, self._slot_state, tok0, *routing = (
                self._supervised("serve/prefill", dispatch))
            tok0, *routing = self._to_host("insert_tok0", tok0, *routing)
            tok0 = int(tok0)
        self._note_routing(routing, decode_steps=0)
        entry = _Slot(
            request=request, tokens=[tok0],
            first_token_ts=time.perf_counter(),
        )
        self._slot_table[slot] = entry
        self._feed_entry(entry)
        self._save_prefix_blocks(request, slot)
        self._export_handoff(request, slot)
        self._activate_or_retire(slot, request, tok0)

    def _active_trace_map(self) -> Optional[Dict[str, str]]:
        """slot -> trace_id for the requests a multi-slot dispatch
        serves (chunk/verify spans carry it as the ``traces`` attribute,
        since one dispatch advances MANY requests).  None when tracing
        is off.  JSON object keys must be strings, hence
        ``str(slot)``."""
        if not tracing.enabled():
            return None
        traces = {}
        for slot in sorted(self._active_slots):
            entry = self._slot_table[slot]
            if entry is not None and entry.request.trace is not None:
                traces[str(slot)] = entry.request.trace.trace_id
        return traces or None

    def _dispatch_chunk(self) -> None:
        cfg = self.serve_config
        num_slots, chunk = cfg.num_slots, cfg.chunk_tokens
        chunk_rng = self._split_rng()

        def dispatch():
            faults.fault_point("serve.chunk")
            return self._chunk_step(
                self.params, self._grid_cache, self._slot_state, chunk_rng,
                *self._paged_extra(),
            )

        span_attrs = dict(
            slots=num_slots, chunk=chunk, active=len(self._active_slots),
            **{"pass": self._pass_seq},
        )
        if self._slice_chips > 1:
            span_attrs["slice"] = (
                f"{self._slice_shape[0]}x{self._slice_shape[1]}"
            )
            span_attrs["slice_chips"] = self._slice_chips
        traces = self._active_trace_map()
        if traces:
            span_attrs["traces"] = traces
        self._note_kv_rows()
        self._note_dispatch_gap(time.perf_counter())
        with tracing.span("serve/chunk", **span_attrs) as chunk_span:
            self._grid_cache, self._slot_state, toks, valid, *routing = (
                self._supervised("serve/chunk", dispatch)
            )
            self._last_chunk_dispatch_end = time.perf_counter()
            toks, valid, *routing = self._to_host(
                "chunk_tokens", toks, valid, *routing)
            emitted = int(valid.sum())
            occupancy = emitted / float(num_slots * chunk)
            chunk_span.set_attribute("tokens", emitted)
            chunk_span.set_attribute("occupancy", round(occupancy, 4))
        metrics.counter_inc("serve/chunks")
        metrics.gauge_set("serve/slot_occupancy", occupancy)
        with self._stats_lock:
            self._stats["chunks"] += 1
            self._stats["decode_slot_steps"] += num_slots * chunk
            self._stats["useful_decode_tokens"] += emitted
        self._note_routing(routing, decode_steps=chunk)
        self._commit_emissions(toks, valid, chunk)

    def _note_routing(self, routing, decode_steps: int) -> None:
        """Add what a program's dropless expert layers counted on the
        device (``moe.ROUTING_HEAD``; ``routing`` is empty for a model
        without them) to the ``expert_*`` counters.  A chunk of
        ``decode_steps`` steps also counts, per step and expert layer,
        the held experts against those that got a token; an insert
        (``decode_steps=0``) counts assignments and loads alone."""
        if not routing:
            return
        from cloud_tpu.models import moe as moe_lib

        counted = np.asarray(routing[0], np.int64)
        made, here, touched = counted[:moe_lib.ROUTING_HEAD]
        config = self.config
        expert_layers = config.num_layers - config.leading_dense_layers
        self._pass_assignments_here += int(here)
        with self._stats_lock:
            self._stats["expert_assignments"] += int(made)
            self._stats["expert_assignments_here"] += int(here)
            if decode_steps:
                self._stats["expert_steps"] += (
                    decode_steps * expert_layers * config.moe.held)
                self._stats["expert_steps_touched"] += int(touched)
            self._expert_loads += counted[moe_lib.ROUTING_HEAD:]

    def _feed_entry(self, entry: _Slot) -> None:
        """Deliver a slot's not-yet-streamed emissions to its request's
        stream / ``on_token`` hook (no-op for plain futures — the FIFO
        path pays one attribute check).  Capped at the request's budget
        so the streamed view is exactly the final result row's prefix;
        the future's done-callback closes the stream and back-fills
        anything this path never saw (crash paths)."""
        request = entry.request
        if request.stream is None and request.on_token is None:
            return
        limit = min(len(entry.tokens), request.max_new_tokens)
        while entry.streamed < limit:
            i = entry.streamed
            token = entry.tokens[i]
            if request.stream is not None:
                request.stream.feed(i, token)
            if request.on_token is not None:
                try:
                    request.on_token(i, token)
                except Exception:  # noqa: BLE001 — a consumer's bug must
                    # not take the scheduler (and every other slot) down.
                    logger.exception("on_token hook failed")
                    request.on_token = None
            entry.streamed = i + 1

    def _commit_emissions(self, toks, valid, width: int) -> None:
        """Mirror one dispatch's [slots, width] emissions into the host
        slot table and retire what finished — shared verbatim by the
        decode-chunk and verify paths (``valid`` is a per-row prefix in
        both).  Streaming requests get each committed token the moment
        it lands here (host-side delivery; the dispatch is unchanged).
        Spanned as ``serve/commit``: token append, stream/``on_token``
        delivery and the retires."""
        eos = self.serve_config.sample.eos_id
        tokens = retired = 0
        with tracing.span("serve/commit",
                          **{"pass": self._pass_seq}) as span:
            for slot in sorted(self._active_slots):
                entry = self._slot_table[slot]
                entry.passes += 1
                for i in range(width):
                    if not valid[slot, i]:
                        break
                    entry.tokens.append(int(toks[slot, i]))
                    tokens += 1
                self._feed_entry(entry)
                hit_eos = eos is not None and entry.tokens[-1] == eos
                if (hit_eos or len(entry.tokens)
                        >= entry.request.max_new_tokens):
                    self._retire_slot(slot)
                    retired += 1
            span.set_attribute("tokens", tokens)
            span.set_attribute("retired", retired)

    def _dispatch_spec_chunk(self) -> None:
        """One draft-and-verify round: the draft proposes a ``spec_k``
        window per slot over its own cache (``serve/draft``), then the
        target scores the whole window in ONE dispatch and commits the
        accepted prefix (``serve/verify``).  Host-side emission
        handling is byte-for-byte the chunk path's — only the token
        source changed."""
        cfg = self.serve_config
        num_slots, k = cfg.num_slots, cfg.draft.spec_k
        active_n = len(self._active_slots)
        self._note_kv_rows()
        self._note_dispatch_gap(time.perf_counter())

        def draft_dispatch():
            faults.fault_point("serve.draft")
            return self._draft_step(
                self._draft_params, self._draft_cache, self._slot_state
            )

        with tracing.span("serve/draft", slots=num_slots, spec_k=k,
                          active=active_n):
            self._draft_cache, window = self._supervised(
                "serve/draft", draft_dispatch
            )

        def verify_dispatch():
            faults.fault_point("serve.verify")
            return self._verify_step(
                self.params, self._grid_cache, self._slot_state, window,
                *self._paged_extra(),
            )

        span_attrs = dict(slots=num_slots, spec_k=k, active=active_n,
                          **{"pass": self._pass_seq})
        if self._slice_chips > 1:
            span_attrs["slice"] = (
                f"{self._slice_shape[0]}x{self._slice_shape[1]}"
            )
            span_attrs["slice_chips"] = self._slice_chips
        traces = self._active_trace_map()
        if traces:
            span_attrs["traces"] = traces
        with tracing.span("serve/verify", **span_attrs) as verify_span:
            self._grid_cache, self._slot_state, toks, valid = (
                self._supervised("serve/verify", verify_dispatch)
            )
            self._last_chunk_dispatch_end = time.perf_counter()
            toks, valid = self._to_host("verify_tokens", toks, valid)
            emitted = int(valid.sum())
            # Every active slot commits >= 1 token (the first-mismatch
            # position's target token); the surplus is accepted drafts.
            accepted = max(emitted - active_n, 0)
            proposed = active_n * (k - 1)
            occupancy = emitted / float(num_slots * k)
            verify_span.set_attribute("tokens", emitted)
            verify_span.set_attribute("accepted", accepted)
            verify_span.set_attribute("proposed", proposed)
            verify_span.set_attribute("occupancy", round(occupancy, 4))
        metrics.counter_inc("serve/spec_chunks")
        metrics.counter_inc("serve/spec_accepted_tokens", accepted)
        metrics.gauge_set("serve/slot_occupancy", occupancy)
        with self._stats_lock:
            self._accept_window.append((accepted, proposed))
            self._stats["spec_chunks"] += 1
            self._stats["spec_emitted"] += emitted
            self._stats["spec_accepted"] += accepted
            self._stats["spec_proposed"] += proposed
            self._stats["decode_slot_steps"] += num_slots * k
            self._stats["useful_decode_tokens"] += emitted
        metrics.gauge_set(
            "serve/spec_accept_rate", self._rolling_acceptance()
        )
        self._commit_emissions(toks, valid, k)

    def _rolling_acceptance(self) -> float:
        """Acceptance over the last <=64 verify dispatches (health()'s
        number; stats() carries the cumulative quotient).  Reads under
        ``_stats_lock``: health() iterates from router threads while
        the scheduler appends, and a deque raises on concurrent
        mutation during iteration."""
        with self._stats_lock:
            accepted = sum(a for a, _ in self._accept_window)
            proposed = sum(p for _, p in self._accept_window)
        return accepted / proposed if proposed else 0.0

    # -- pipelined scheduling (pipeline_depth=2) ---------------------------

    def _decode_page(self) -> Optional[int]:
        """The page the decode read fetches K/V rows by where it goes
        through the paged kernel — by default wherever the kernel would
        run (``generation._scan_layers``' rule: on a TPU, an eligible
        shape), with ``decode_kernel != "xla"`` by the kernel's own
        dispatch at the pool's block size — or None where it reads every
        row of the grid.  What ``kv_row_steps_read`` counts by."""
        import jax

        from cloud_tpu.models import generation
        from cloud_tpu.ops import paged_attention

        cfg, config = self.serve_config, self.config
        if config.latent is not None:
            from cloud_tpu.ops import latent_attention

            rows = self._grid_cache[generation.LATENT_LEAF]
            return latent_attention.kernel_page(jax.ShapeDtypeStruct(
                (cfg.num_slots, config.num_heads, rows.shape[-1]),
                config.dtype), rows)
        q = jax.ShapeDtypeStruct(
            (cfg.num_slots, 1, config.num_heads, config.head_dim),
            config.dtype)
        kv = {"k": self._grid_cache["k"]}
        if self._paged:
            return paged_attention.kernel_page(
                q, kv, page_tokens=cfg.prefix_block_tokens,
                use_pallas=self._paged_use_pallas)
        if paged_attention.would_use_kernel(q, kv):
            return paged_attention.kernel_page(q, kv, use_pallas=True)
        return None

    def _note_kv_rows(self) -> None:
        """KV accounting at a chunk dispatch: the rows the grid (and
        the prefix pool) reserve against the rows that hold a live
        token right now, added to the ``kv_row_steps_*`` counters.  A
        decoding slot holds ``prompt_len + tokens so far`` rows, a slot
        mid-prefill the positions prefilled; a pool block counts once
        while any live slot references it, and the rows a paged slot
        reads from attached pool blocks are not counted twice.  At
        ``pipeline_depth=2`` the host's token counts trail the device
        by the chunk in flight.  ``kv_row_steps_read`` adds the rows
        the chunk's decode steps each fetch: every row of the grid, or,
        through the paged kernel, each decoding slot's rows rounded up
        to the kernel's page (a slot that does not decode, empty or
        mid-prefill, costs grid steps and no rows; a verify window reads
        a page more at most, not counted).  A recurrent state's rows
        are counted beside them: one a layer for every slot in the
        chunk, and as ``state_row_steps_read`` the rows a decode step
        fetches: those, where the state kernel advances them in place,
        else every reserved row."""
        rows = sum(task.next_pos for task in self._prefill_tasks)
        page = self._decode_read_page
        read = 0 if page else self.serve_config.num_slots * self._max_len
        for slot in self._active_slots:
            entry = self._slot_table[slot]
            held = entry.request.prompt_len + len(entry.tokens)
            rows += held
            if page:
                read += min(-(-held // page) * page, self._max_len)
        if self._prefix is not None:
            block_tokens = self.serve_config.prefix_block_tokens
            rows += block_tokens * len({
                id(node) for entry in self._slot_table if entry is not None
                for node in entry.prefix_nodes
            })
            if self._block_table is not None:
                rows -= block_tokens * int((self._block_table >= 0).sum())
        self._kv_rows_in_use = rows
        self._pass_active = len(self._active_slots)
        self._state_rows_in_use = (
            self._pass_active * self.config.num_layers
            if self._state_rows_reserved else 0
        )
        with self._stats_lock:
            self._stats["kv_row_steps_reserved"] += self._kv_rows_reserved
            self._stats["kv_row_steps_in_use"] += rows
            self._stats["kv_row_steps_read"] += read
            self._stats["state_row_steps_reserved"] += (
                self._state_rows_reserved
            )
            self._stats["state_row_steps_in_use"] += self._state_rows_in_use
            self._stats["state_row_steps_read"] += (
                self._state_rows_in_use if self._state_read_in_place
                else self._state_rows_reserved
            )

    def _note_dispatch_gap(self, start: float) -> None:
        """Record the host gap between the previous chunk dispatch and
        this one — the scheduling bubble the pipeline exists to hide.
        Deque-only at depth 1 (the default path emits no new spans); at
        depth 2 also recorded as a ``serve/dispatch_gap`` span so the
        report's serve breakdown can attribute the residual bubble."""
        last = self._last_chunk_dispatch_end
        if last is None:
            return
        with self._stats_lock:
            # Under the lock: health()/stats() snapshot the deque from
            # router threads while the scheduler appends.
            self._dispatch_gaps.append((start - last) * 1000.0)
        if self._pipe_depth > 1:
            tracing.record_span("serve/dispatch_gap", last, start)

    def _predict_survivors(self) -> bool:
        """Host-side liveness prediction, no device sync: can ANY
        active slot still be decoding after every chunk already in the
        in-flight ring lands?

        The host knows each slot's budget exactly (``max_new_tokens``
        minus tokens committed so far) and each ring entry's maximum
        per-slot progress (its emission ``width``), so budget
        exhaustion is predictable at dispatch time.  Eos is not — but
        eos only retires a slot EARLIER than its budget, so a ``True``
        here can at worst admit a partially-dead chunk (the device
        active mask zeroes those rows, exactly as at depth 1), never
        suppress a live one.  Used by the pipelined pass to stop
        dispatching ahead once the work in flight provably finishes
        every slot — the all-dead trailing chunk a naive
        dispatch-ahead loop would waste at each wave end."""
        pending = sum(rec.width for rec in self._inflight)
        for slot in self._active_slots:
            entry = self._slot_table[slot]
            if entry is None:  # pragma: no cover - retire races
                continue
            if entry.request.max_new_tokens - len(entry.tokens) > pending:
                return True
        return False

    def _start_host_copy(self, *arrays) -> None:
        """Kick off non-blocking device→host copies for a dispatched
        chunk's emission buffers, so the drain's blocking ``_to_host``
        one pass later finds the bytes already (or nearly) resident.
        Best effort: backends/array types without the method simply
        fall back to the blocking copy at drain."""
        for arr in arrays:
            try:
                arr.copy_to_host_async()
            except (AttributeError, RuntimeError):
                return

    def _dispatch_chunk_async(self) -> None:
        """Dispatch half of the pipelined decode pass: enqueue one
        chunk against the current device-resident grid and push its
        *unmaterialized* emission arrays onto the in-flight ring — no
        host sync here.  ``_drain_inflight`` commits them one pass
        later, after the NEXT chunk is already running, so the commit/
        retire/insert host work overlaps device compute.  Metrics and
        stats move to the drain with the emissions: a disposed (never
        drained) chunk is never counted."""
        cfg = self.serve_config
        num_slots, chunk = cfg.num_slots, cfg.chunk_tokens
        chunk_rng = self._split_rng()

        def dispatch():
            faults.fault_point("serve.chunk")
            return self._chunk_step(
                self.params, self._grid_cache, self._slot_state, chunk_rng,
                *self._paged_extra(),
            )

        span_attrs = dict(
            slots=num_slots, chunk=chunk, active=len(self._active_slots),
            **{"pass": self._pass_seq},
        )
        if self._slice_chips > 1:
            span_attrs["slice"] = (
                f"{self._slice_shape[0]}x{self._slice_shape[1]}"
            )
            span_attrs["slice_chips"] = self._slice_chips
        traces = self._active_trace_map()
        if traces:
            span_attrs["traces"] = traces
        self._note_kv_rows()
        start = time.perf_counter()
        self._note_dispatch_gap(start)
        (self._grid_cache, self._slot_state, toks, valid, summary,
         *routing) = self._supervised("serve/chunk", dispatch)
        end = time.perf_counter()
        self._last_chunk_dispatch_end = end
        self._start_host_copy(toks, valid, summary, *routing)
        self._inflight.append(_InflightChunk(
            toks=toks, valid=valid, summary=summary, width=chunk,
            kind="chunk", active=len(self._active_slots),
            span_attrs=span_attrs, dispatch_start=start, dispatch_end=end,
            routing=tuple(routing),
        ))

    def _dispatch_spec_chunk_async(self) -> None:
        """Pipelined draft-and-verify round: both dispatches enqueue
        back to back (the verify consumes the draft's window as a
        device operand — no host sync between them) and the verify's
        emissions ride the in-flight ring exactly like a decode
        chunk's.  The ``serve/draft`` span brackets only the enqueue
        here; the ``serve/verify`` span is recorded at drain over the
        full dispatch→drain interval."""
        cfg = self.serve_config
        num_slots, k = cfg.num_slots, cfg.draft.spec_k
        active_n = len(self._active_slots)

        def draft_dispatch():
            faults.fault_point("serve.draft")
            return self._draft_step(
                self._draft_params, self._draft_cache, self._slot_state
            )

        self._note_kv_rows()
        start = time.perf_counter()
        self._note_dispatch_gap(start)
        with tracing.span("serve/draft", slots=num_slots, spec_k=k,
                          active=active_n):
            self._draft_cache, window = self._supervised(
                "serve/draft", draft_dispatch
            )

        def verify_dispatch():
            faults.fault_point("serve.verify")
            return self._verify_step(
                self.params, self._grid_cache, self._slot_state, window,
                *self._paged_extra(),
            )

        span_attrs = dict(slots=num_slots, spec_k=k, active=active_n,
                          **{"pass": self._pass_seq})
        if self._slice_chips > 1:
            span_attrs["slice"] = (
                f"{self._slice_shape[0]}x{self._slice_shape[1]}"
            )
            span_attrs["slice_chips"] = self._slice_chips
        traces = self._active_trace_map()
        if traces:
            span_attrs["traces"] = traces
        self._grid_cache, self._slot_state, toks, valid, summary = (
            self._supervised("serve/verify", verify_dispatch)
        )
        end = time.perf_counter()
        self._last_chunk_dispatch_end = end
        self._start_host_copy(toks, valid, summary)
        self._inflight.append(_InflightChunk(
            toks=toks, valid=valid, summary=summary, width=k,
            kind="verify", active=active_n,
            span_attrs=span_attrs, dispatch_start=start, dispatch_end=end,
        ))

    def _drain_inflight(self) -> None:
        """Drain half of the pipelined pass: materialize the OLDEST
        in-flight chunk's emissions (the blocking host copy overlaps
        the device running the chunk dispatched after it — the wait
        actually paid is recorded as ``serve/host_bubble``), then run
        the exact metrics/stats/commit sequence of the synchronous
        path.  The terminal ``serve/chunk``/``serve/verify`` span
        covers dispatch→drain, so the report's serve breakdown keeps
        aggregating occupancy the same way at any depth."""
        rec = self._inflight.popleft()
        cfg = self.serve_config
        num_slots = cfg.num_slots
        wait0 = time.perf_counter()
        toks, valid, summary, *routing = self._to_host(
            f"{rec.kind}_tokens", rec.toks, rec.valid, rec.summary,
            *rec.routing
        )
        wait1 = time.perf_counter()
        self._note_routing(routing, decode_steps=rec.width)
        tracing.record_span("serve/host_bubble", wait0, wait1,
                            kind=rec.kind, width=rec.width)
        emitted = int(summary[0])
        occupancy = emitted / float(num_slots * rec.width)
        attrs = dict(rec.span_attrs)
        attrs["tokens"] = emitted
        attrs["occupancy"] = round(occupancy, 4)
        if rec.kind == "verify":
            accepted = max(emitted - rec.active, 0)
            proposed = rec.active * (cfg.draft.spec_k - 1)
            attrs["accepted"] = accepted
            attrs["proposed"] = proposed
            tracing.record_span("serve/verify", rec.dispatch_start,
                                wait1, **attrs)
            metrics.counter_inc("serve/spec_chunks")
            metrics.counter_inc("serve/spec_accepted_tokens", accepted)
            metrics.gauge_set("serve/slot_occupancy", occupancy)
            with self._stats_lock:
                self._accept_window.append((accepted, proposed))
                self._stats["spec_chunks"] += 1
                self._stats["spec_emitted"] += emitted
                self._stats["spec_accepted"] += accepted
                self._stats["spec_proposed"] += proposed
                self._stats["decode_slot_steps"] += num_slots * rec.width
                self._stats["useful_decode_tokens"] += emitted
            metrics.gauge_set(
                "serve/spec_accept_rate", self._rolling_acceptance()
            )
        else:
            tracing.record_span("serve/chunk", rec.dispatch_start,
                                wait1, **attrs)
            metrics.counter_inc("serve/chunks")
            metrics.gauge_set("serve/slot_occupancy", occupancy)
            with self._stats_lock:
                self._stats["chunks"] += 1
                self._stats["decode_slot_steps"] += num_slots * rec.width
                self._stats["useful_decode_tokens"] += emitted
        self._commit_emissions(toks, valid, rec.width)

    def _dispose_inflight(self) -> None:
        """Abandon the in-flight ring without committing (abort/crash
        paths): block until every pending dispatch and its async
        device→host copy actually completed — ``close(drain=False)``
        must never leave a computation or copy running against state
        being torn down — then drop the results.  Errors are logged,
        not raised: disposal must not mask the failure that got us
        here, and the slots' futures are failed by the caller."""
        while self._inflight:
            rec = self._inflight.popleft()
            try:
                self._to_host(f"{rec.kind}_dispose", rec.toks, rec.valid,
                              rec.summary)
            except Exception:  # noqa: BLE001
                logger.exception("disposing in-flight chunk failed")

    def _dispatch_draft_prefill(self, request: _Request, slot: int) -> None:
        """Mirror a just-armed slot's prompt into the draft model's
        cache row so the next proposal round attends over real context
        (one-shot whatever the target side did — prefix hits and
        chunked prefills stay target-only)."""
        tokens = np.zeros((1, request.bucket_len), np.int32)
        tokens[0, :request.prompt_len] = request.prompt
        cell = self._draft_prefill_cell(request.bucket_len)

        def dispatch():
            faults.fault_point("serve.draft_prefill")
            return cell(
                self._draft_params, self._draft_cache, tokens,
                np.int32(request.prompt_len), np.int32(slot),
            )

        with tracing.span("serve/draft_prefill",
                          bucket=request.bucket_len, slot=slot):
            self._draft_cache = self._supervised(
                "serve/draft_prefill", dispatch
            )
        metrics.counter_inc("serve/draft_prefills")
        with self._stats_lock:
            self._stats["draft_prefills"] += 1

    def _retire_slot(self, slot: int, exc: Optional[BaseException] = None
                     ) -> None:
        """Free a slot and resolve its request's future — with the
        result (the emitted row padded to the request's length) or, on
        abort, the given exception."""
        cfg = self.serve_config
        entry = self._slot_table[slot]
        self._slot_table[slot] = None
        self._active_slots.discard(slot)
        if self._block_table is not None:
            # Detach before the pins below release: a stale table row
            # must never outlive the references that made its pool
            # blocks immutable.
            self._block_table[slot, :] = -1
        if entry.prefix_nodes and self._prefix is not None:
            # Drop this slot's references; blocks shared with another
            # in-flight slot stay pinned until IT retires too.
            self._prefix.release(entry.prefix_nodes)
        with self._cond:
            self._free_slots.append(slot)
        request = entry.request
        if exc is not None:
            try:
                request.future.set_exception(exc)
            except InvalidStateError:
                # Already resolved elsewhere (e.g. the insert-failure
                # handler beat us to it, or the caller cancelled): don't
                # double-count the failure.
                return
            with self._stats_lock:
                self._stats["failed"] += 1
            return
        m = request.max_new_tokens
        num = min(len(entry.tokens), m)
        row = np.full((m,), cfg.sample.pad_id, np.int32)
        row[:num] = entry.tokens[:num]
        done = time.perf_counter()
        first = entry.first_token_ts if entry.first_token_ts else done
        result = ServeResult(
            tokens=row,
            num_generated=num,
            bucket_len=request.bucket_len,
            batch_size=cfg.num_slots,
            latency_seconds=done - request.submitted,
            ttft_seconds=first - request.submitted,
            trace_id=request.trace_id,
            handoff=entry.handoff,
        )
        metrics.distribution_record(
            "serve/latency_seconds", result.latency_seconds
        )
        metrics.counter_inc("serve/slot_retires")
        metrics.counter_inc("serve/generated_tokens", num)
        eos = cfg.sample.eos_id
        hit_eos = eos is not None and num > 0 and int(row[num - 1]) == eos
        if not hit_eos:
            # The per-slot max_new_tokens cap (not eos) ended the slot.
            metrics.counter_inc("serve/slot_expired")
        self._qps.add(done, 1)
        self._tokens_rate.add(done, num)
        with self._stats_lock:
            self._stats["retires"] += 1
            if not hit_eos:
                self._stats["expired"] += 1
            self._stats["completed"] += 1
            self._stats["generated_tokens"] += num
            if self._qos is not None:
                self._class_completed[request.priority] += 1
        self._record_request_spans(request, result, first, done,
                                   slot=slot, passes=entry.passes)
        try:
            request.future.set_result(result)
        except InvalidStateError:  # pragma: no cover - cancelled
            pass

    def _record_request_spans(self, request: _Request, result: ServeResult,
                              first: float, done: float, *, slot: int,
                              passes: int) -> None:
        """The two spans that close a served request: ``serve/request``
        (submit to the last token, with where its time went) and
        ``serve/ttft`` (submit's own stamp to the first token on the
        host: TTFT as the engine sees it)."""
        if not tracing.enabled():
            return
        attrs = {
            "ttft_s": round(result.ttft_seconds, 6),
            "queue_wait_s": round(request.admitted - request.submitted, 6),
            "decode_s": round(done - first, 6),
            "tokens": result.num_generated,
            "prompt_len": request.prompt_len,
            "bucket": request.bucket_len,
            "slot": slot, "passes": passes,
        }
        if request.priority is not None:
            attrs["priority"] = request.priority
        tracing.record_span("serve/request", request.submitted, done,
                            **_trace_attrs(request, **attrs))
        tracing.record_span("serve/ttft", request.submitted, first,
                            **_trace_attrs(request))

    def _fail_live_slots(self, exc: BaseException) -> None:
        for slot, entry in enumerate(self._slot_table):
            if entry is not None:
                self._retire_slot(slot, exc=exc)

    # -- introspection -----------------------------------------------------

    def health(self) -> dict:
        """Readiness/liveness snapshot (the shape a /healthz endpoint or
        an external supervisor polls; cheap, lock-bounded, any thread).

        ``healthy`` — no watchdog fire, no scheduler crash (a cleanly
        closed engine is still healthy: it stopped, it didn't break).
        ``ready`` — accepting new ``submit()`` calls right now.
        ``live`` — the scheduler thread exists and is running.
        ``reason`` — why ``healthy`` is False, else None.  Plus the
        load signal a fleet router reads per routing decision —
        ``queue_depth`` (waiting requests; same value as the legacy
        ``waiting`` key), ``active_slots`` (OCCUPIED slots on the device
        right now — decoding or mid-prefill), ``num_slots`` (the
        engine's slot capacity, so occupancy is ``active/num``) — the
        grid's ``free_slots``, orphaned dispatch count, and
        seconds since the last device dispatch (None before the first)
        for staleness alerting.
        """
        with self._cond:
            waiting = self._waiting
            closed = self._closed
            thread = self._thread
            free_slots = len(self._free_slots)
            class_backlog = self._class_backlog_locked()
        live = thread is not None and thread.is_alive()
        reason = self._unhealthy_reason
        last = self._last_dispatch_ts
        snap = {
            "healthy": reason is None,
            "ready": live and not closed and reason is None,
            "live": live,
            "reason": reason,
            "closed": closed,
            "waiting": waiting,
            "queue_depth": waiting,
            # OCCUPIED slots, not merely decoding ones: a slot claimed
            # by a mid-prefill task (chunked prefill can hold it for
            # many passes) is load a router must see — it left the
            # queue-depth count the moment it was popped.
            "active_slots": self.serve_config.num_slots - free_slots,
            "num_slots": self.serve_config.num_slots,
            # The slice this replica spans: (tp, sp) and total chips.
            # (1, 1)/1 on the single-chip path — stable schema, so a
            # fleet can sum chips without probing.  Router load math
            # deliberately ignores these: load is queued + in-flight
            # REQUESTS, whatever the slice width serving them.
            "slice_shape": self._slice_shape,
            "slice_chips": self._slice_chips,
            "orphaned_dispatches": len(self._orphan_dispatches),
            "last_dispatch_age_s": (
                None if last is None else time.perf_counter() - last
            ),
            # Speculative decoding (stable schema — zeros when off):
            # the rolling acceptance over recent verify dispatches, and
            # the armed window width.
            "spec_acceptance_rate": (
                self._rolling_acceptance() if self._spec else 0.0
            ),
            "spec_k": (
                self.serve_config.draft.spec_k if self._spec else 0
            ),
            # Per-class queued requests (QoS): all-zeros when qos=None
            # (requests are classless on the FIFO path) — stable
            # schema, so the fleet's per-class backlog aggregation and
            # the autoscaler's class signal read without probing.
            "class_backlog": class_backlog,
            # The armed decode-attention path ("xla" default).
            "decode_kernel": self.serve_config.decode_kernel,
            # Disaggregated serving (stable schema — "both" and zeros
            # with roles off): the role the fleet router steers legs
            # by, plus the KV handoff counters.
            "role": self._role,
            # Pipelined scheduling (0.0 before the first two chunks):
            # the effective depth and the rolling mean host gap
            # between consecutive chunk dispatches, the bubble depth 2
            # exists to hide — a supervisor alert on it regressing is
            # the cheapest "pipelining stopped helping" signal.
            "pipeline_depth": self._pipe_depth,
            "dispatch_gap_ms": self._dispatch_gap_mean(),
        }
        with self._stats_lock:
            snap["handoff_exports"] = self._stats["handoff_exports"]
            snap["handoff_export_blocks"] = (
                self._stats["handoff_export_blocks"]
            )
            snap["handoff_imports"] = self._stats["handoff_imports"]
            snap["handoff_import_blocks"] = (
                self._stats["handoff_import_blocks"]
            )
        snap.update(self._prefix_snapshot())
        snap.update(self._kv_snapshot())
        snap["free_slots"] = free_slots
        return snap

    def _class_backlog_locked(self) -> Dict[str, int]:
        """Queued requests per QoS class (caller holds ``_cond``).
        Zeros for every class when QoS is off — the FIFO path never
        classes its queue."""
        backlog = {name: 0 for name in self._class_names}
        if self._qos is not None:
            for queue_ in self._pending.values():
                for request in queue_:
                    backlog[request.priority] += 1
        return backlog

    def _prefix_snapshot(self) -> dict:
        """The prefix-cache keys ``health()`` and ``stats()`` both
        carry (ONE spelling — the fleet router pins the schema): zeros
        when the cache is off, so callers read a stable shape.  The
        ``prefix_dram_*`` keys are the host-DRAM tier's (zeros with
        ``prefix_dram_blocks`` unset), and ``cached_prefixes`` is the
        router-facing hot-prefix summary ({} when off) the cost-model
        router scores candidates by."""
        prefix = self._prefix.stats() if self._prefix is not None else None
        return {
            "prefix_cache_blocks": (
                prefix["blocks_in_use"] if prefix else 0
            ),
            "prefix_hit_tokens": prefix["hit_tokens"] if prefix else 0,
            "evictions": prefix["evictions"] if prefix else 0,
            "prefix_dram_blocks": (
                prefix["dram_blocks_in_use"] if prefix else 0
            ),
            "prefix_dram_hits": prefix["dram_hits"] if prefix else 0,
            "prefix_dram_hit_tokens": (
                prefix["dram_hit_tokens"] if prefix else 0
            ),
            "prefix_dram_demotions": prefix["demotions"] if prefix else 0,
            "prefix_dram_evictions": (
                prefix["dram_evictions"] if prefix else 0
            ),
            "prefix_dram_swapin_failures": (
                prefix["swapin_failures"] if prefix else 0
            ),
            # Pipelined save-backs (0 at pipeline_depth=1): the parity
            # tests assert the deferred-ordering path was exercised.
            "prefix_deferred_saves": (
                prefix["deferred_saves"] if prefix else 0
            ),
            "cached_prefixes": (
                self._prefix.hot_prefixes()
                if self._prefix is not None else {}
            ),
        }

    def placement(self) -> dict:
        """Where the engine's state lives, read from array metadata only
        (no device access; any thread): the ids of the devices holding
        the params and the slot-grid KV, with the KV leaves' global
        shapes and one device's shard of each."""
        import jax

        leaves = jax.tree_util.tree_leaves

        def device_ids(arrays):
            return sorted({d.id for x in arrays
                           for d in x.sharding.device_set})

        kv = leaves(self._grid_cache)
        return {
            "param_devices": device_ids(leaves(self.params)),
            "kv_devices": device_ids(kv),
            "kv_shapes": sorted({tuple(x.shape) for x in kv}),
            "kv_shard_shapes": sorted(
                {tuple(x.sharding.shard_shape(x.shape)) for x in kv}),
        }

    def stats(self) -> dict:
        """Counters snapshot plus the occupancy quotient.

        ``mean_slot_occupancy`` — useful emitted tokens / dispatched
        token slots: it charges a chunk for every slot lane, so it is
        the number iteration-level scheduling is judged by.
        ``batches``, ``slots``, ``real_rows`` and
        ``mean_batch_occupancy`` are always zero: the slot grid forms no
        batches, and the keys stay for the readers of the schema.
        """
        with self._stats_lock:
            snap = dict(self._stats)
            # Per-class service accounting (QoS): zeros when qos=None —
            # stable schema next to brownout_shed above.
            snap["class_completed"] = dict(self._class_completed)
            snap["class_shed"] = dict(self._class_shed)
            loads = (() if self._expert_loads is None
                     else tuple(int(n) for n in self._expert_loads))
        # Dropless experts: the tokens each held expert got so far and
        # the busiest one's over their mean (0.0 without experts, or
        # before any token reached one).
        snap["expert_loads"] = loads
        snap["expert_load_max_over_mean"] = (
            max(loads) * len(loads) / sum(loads) if sum(loads) else 0.0)
        snap["role"] = self._role
        with self._cond:
            snap["class_backlog"] = self._class_backlog_locked()
        snap["mean_batch_occupancy"] = 0.0
        snap["mean_slot_occupancy"] = (
            snap["useful_decode_tokens"] / snap["decode_slot_steps"]
            if snap["decode_slot_steps"] else 0.0
        )
        snap["slice_shape"] = self._slice_shape
        snap["slice_chips"] = self._slice_chips
        # Cumulative acceptance (health() carries the rolling one);
        # 0.0 with draft=None — stable schema.
        snap["spec_acceptance_rate"] = (
            snap["spec_accepted"] / snap["spec_proposed"]
            if snap["spec_proposed"] else 0.0
        )
        # Pipelined scheduling: dispatch-gap percentiles over the
        # rolling window.
        snap["pipeline_depth"] = self._pipe_depth
        gaps = self._dispatch_gap_window()
        snap["dispatch_gap_ms_p50"] = (
            float(np.percentile(gaps, 50)) if gaps else 0.0
        )
        snap["dispatch_gap_ms_p99"] = (
            float(np.percentile(gaps, 99)) if gaps else 0.0
        )
        snap.update(self._prefix_snapshot())
        snap.update(self._kv_snapshot())
        return snap

    def _kv_snapshot(self) -> dict:
        """The KV bytes ``health()`` and ``stats()`` both carry: what
        the slot grid and the prefix pool reserve (constant), and what
        held a live token at the last chunk dispatch (rows in use at
        the grid's bytes a row), and the same for a recurrent state's
        rows."""
        return {
            "kv_bytes_reserved": self._kv_bytes_reserved,
            "kv_bytes_in_use": (
                self._kv_bytes_reserved * self._kv_rows_in_use
                // self._kv_rows_reserved
            ),
            "state_bytes_reserved": self._state_bytes_reserved,
            "state_bytes_in_use": (
                self._state_bytes_reserved * self._state_rows_in_use
                // max(self._state_rows_reserved, 1)
            ),
        }

    def _dispatch_gap_window(self) -> List[float]:
        """Snapshot of the rolling dispatch-gap window (ms), empty
        before the first two chunk dispatches."""
        with self._stats_lock:
            return list(self._dispatch_gaps)

    def _dispatch_gap_mean(self) -> float:
        """health()'s rolling mean dispatch gap in ms (0.0 when the
        window is empty)."""
        gaps = self._dispatch_gap_window()
        return float(sum(gaps) / len(gaps)) if gaps else 0.0

    @property
    def chunk_traces(self) -> int:
        """Python-trace count of the chunk program: 1 after any amount
        of traffic == one compile served the run."""
        return self._chunk_traces

    @property
    def verify_traces(self) -> int:
        """Python-trace count of the speculative verify program: 1
        after any amount of traffic == one compile served the run (0
        with ``draft=None``)."""
        return self._verify_traces
