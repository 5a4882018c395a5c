"""ServingEngine: slot-based in-flight (continuous) batching.

The throughput lever the fixed-batch serving loop leaves on the table:
``greedy_generate_kv`` decodes every request in a batch for the full
``num_steps`` and a new batch cannot start until the slowest sequence
finishes — on mixed-length traffic most slot-steps are wasted padding.
This engine keeps ONE persistent jitted step function advancing a
fixed-capacity slot slab (``serving.slots.SlotDecoder``); the moment a
slot's request hits EOS or its token budget the slot is freed, the next
queued request is prefilled directly into that cache region, and the
step keeps running — the device stays saturated at request granularity
(the same overlap-and-saturate principle as the PR 4 feed plane, and
the batching story of arXiv:2011.03641).

Greedy decode only, and per-request outputs are BIT-IDENTICAL to the
single-request ``greedy_generate_kv`` decode of the same prompt: rows
are independent in every einsum, per-slot cursors mask each lane to its
own length, and prefill chunking changes which einsum computes a value
but not the value (pinned by tests/test_serving.py).

The engine is SELF-HEALING (docs/ROBUSTNESS.md):

* admission control — the queue is bounded by request count AND
  queued-token mass; ``submit`` raises a structured
  :class:`~tensorflowonspark_tpu.serving.scheduler.ServingOverloaded`
  with a retry-after hint derived from the live tokens/s rate instead
  of growing without bound;
* deadlines & cancellation — a per-request ``deadline``/``ttl`` is
  checked at admission (an expired queued request fails with
  ``DeadlineExceeded`` without ever taking a slot) and at every horizon
  boundary; ``cancel(rid)`` frees an in-flight slot exactly like EOS;
* crash-replay recovery — an exception in the loop thread no longer
  kills the engine: the slab is rebuilt and every in-flight request is
  transparently replayed from its prompt (greedy ⇒ bit-identical;
  stream consumers see no duplicates because the already-emitted prefix
  is suppressed), with capped consecutive restarts + backoff and poison
  detection (a request blamed across N consecutive crashes is failed,
  not replayed);
* graceful drain — ``drain(timeout)`` stops admission, finishes every
  accepted request, then stops, so rolling restarts shed zero work.

Usage::

    eng = ServingEngine(params, cfg, num_slots=8, eos_id=2).start()
    rid = eng.submit(prompt_ids, max_new_tokens=128, ttl=30.0)
    tokens = eng.result(rid, timeout=60)        # prompt + generated
    # or: for tok in eng.stream(rid): ...
    eng.drain(timeout=30)                       # or eng.stop()

The engine is FAST (the decode-speed stack, each stage gated on bit
parity in tests/test_serving.py, composable with the self-healing surface):

* paged KV slab — ``page_size > 0`` swaps the per-slot ``max_seq_len``
  HBM reservation for a page pool + per-slot page tables
  (``serving.slots``): a request holds only the pages its
  prompt+budget token mass needs, so ``num_slots`` can exceed what
  contiguous reservation would fit; a request that cannot get pages
  waits in the queue (completions free pages) instead of failing;
* shared-prefix cache — ``prefix_pages > 0`` (requires paging) keeps a
  driver-side radix trie over prompt prefixes at page granularity
  (``serving.scheduler.PrefixCache``): requests sharing a prefix
  prefill it ONCE and fork read-only page references (the divergence
  page stays private — copy-on-write at page granularity), turning the
  system-prompt-heavy workload's O(requests × prefix) prefill into
  O(1) per distinct prefix; eviction is ref-counted LRU;
* self-speculative decode — ``spec_depth > 0`` drafts with a
  ``spec_layers``-deep shallow-exit prefix of the SAME model and
  verifies with one full-model step per round (``SlotDecoder
  .step_spec``): greedy verification keeps exactly the tokens
  ``greedy_generate_kv`` would emit, so bit-parity (and crash replay,
  which leans on it) survives the speedup.

A pass of the loop (``_pass``) dispatches in the order the device can run
and reads in the order the device finishes, and it keeps ONE decode step in
flight: the next ``step_many`` is dispatched, its lane state made on the
device from the outputs of the step before it, while that step is still
unread; then ONE admission's chunks and insert are queued behind it unread
(for a lane that is free, or that the budget arithmetic certifies free);
then the OLDER step's tokens are read and harvested, then the first token
of the admission whose insert preceded the new step. The host's dispatch
calls, its harvest and its reads pass while the chip works
(docs/PERFORMANCE.md). The paged pool and speculation keep one step at a
time: dispatch, queue one admission, read.

All waits are timeout-bounded (TOS001) and the loop thread is a daemon
(TOS007). Config knobs ride registered ``TOS_*`` env vars (TOS008):
``TOS_SERVE_SLOTS``, ``TOS_SERVE_BUCKETS``, ``TOS_SERVE_POLL``,
``TOS_SERVE_HORIZON``, ``TOS_SERVE_MAX_QUEUE``,
``TOS_SERVE_MAX_QUEUED_TOKENS``, ``TOS_SERVE_TTL``,
``TOS_SERVE_MAX_RESTARTS``, ``TOS_SERVE_RESTART_BACKOFF``,
``TOS_SERVE_POISON_CRASHES``, ``TOS_SERVE_PAGE_SIZE``,
``TOS_SERVE_NUM_PAGES``, ``TOS_SERVE_PREFIX_PAGES``,
``TOS_SERVE_SPEC_DEPTH``, ``TOS_SERVE_SPEC_LAYERS``.
"""

import contextlib
import logging
import os
import queue as std_queue
import threading
import time
from typing import List, Optional, Sequence

import numpy as np

from tensorflowonspark_tpu.obs import metrics as obs_metrics
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.serving import scheduler as sched
from tensorflowonspark_tpu.serving import slots as slots_lib

logger = logging.getLogger(__name__)

#: default slot capacity when the caller passes ``num_slots=None``
ENV_SERVE_SLOTS = "TOS_SERVE_SLOTS"
#: idle-loop poll interval (seconds) — the bound on every engine wait
ENV_SERVE_POLL = "TOS_SERVE_POLL"
#: decode horizon: how many tokens one fused step dispatch advances.
#: 1 = per-token dispatch (lowest admission latency); larger values
#: amortize dispatch + host-sync overhead over the horizon at the cost
#: of at most horizon-1 frozen slot-steps per finished request and
#: admission every horizon tokens (see SlotDecoder.step_many)
ENV_SERVE_HORIZON = "TOS_SERVE_HORIZON"
#: admission bound on queued request count (0 disables)
ENV_SERVE_MAX_QUEUE = "TOS_SERVE_MAX_QUEUE"
#: admission bound on queued token mass: sum of prompt+budget over the
#: backlog (0 disables; an oversized request still admits when the
#: queue is empty)
ENV_SERVE_MAX_QUEUED_TOKENS = "TOS_SERVE_MAX_QUEUED_TOKENS"
#: default per-request TTL in seconds applied when submit passes neither
#: ``deadline`` nor ``ttl`` (0 = no default deadline)
ENV_SERVE_TTL = "TOS_SERVE_TTL"
#: consecutive loop crashes (no successful decode between) tolerated
#: before the engine dies terminally
ENV_SERVE_MAX_RESTARTS = "TOS_SERVE_MAX_RESTARTS"
#: base restart backoff in seconds (doubles per consecutive crash,
#: capped at 2s; interruptible by stop())
ENV_SERVE_RESTART_BACKOFF = "TOS_SERVE_RESTART_BACKOFF"
#: a request blamed for this many consecutive crashes is failed
#: (PoisonedRequest), not replayed — the crash-loop breaker
ENV_SERVE_POISON_CRASHES = "TOS_SERVE_POISON_CRASHES"
#: paged KV slab: tokens per page (0 = contiguous per-slot reservation)
ENV_SERVE_PAGE_SIZE = "TOS_SERVE_PAGE_SIZE"
#: paged KV slab: pool size in pages, incl. the reserved trash page 0
#: (0 = auto: num_slots × ceil(max_seq_len/page_size) + 1, the
#: contiguous worst case — set lower to spend less HBM than
#: num_slots × max_seq_len)
ENV_SERVE_NUM_PAGES = "TOS_SERVE_NUM_PAGES"
#: shared-prefix cache budget in pages (0 = off; requires paging) —
#: ref-counted LRU eviction keeps the cache at/under this
ENV_SERVE_PREFIX_PAGES = "TOS_SERVE_PREFIX_PAGES"
#: self-speculative decode: draft-window depth per round (0 = off)
ENV_SERVE_SPEC_DEPTH = "TOS_SERVE_SPEC_DEPTH"
#: self-speculative decode: shallow-exit draft depth in layers
#: (0 = auto: num_layers // 2)
ENV_SERVE_SPEC_LAYERS = "TOS_SERVE_SPEC_LAYERS"
#: request-trace detail spans (``serve.decode.slot`` per lane per
#: dispatch + ``serve.prefill.chunk`` per bucket chunk): ``0`` keeps
#: request tracing on (queue/prefill/stream spans, trace ids, ledger)
#: but drops the high-volume detail records — the knob to reach for if
#: the span buffer's drop counter moves on a large deployment
ENV_OBS_TRACE_DETAIL = "TOS_OBS_TRACE_DETAIL"

_DEFAULT_SLOTS = 4
_DEFAULT_POLL = 0.05
_DEFAULT_HORIZON = 4
_DEFAULT_MAX_QUEUE = 1024
_DEFAULT_MAX_QUEUED_TOKENS = 1 << 20
_DEFAULT_MAX_RESTARTS = 5
_DEFAULT_RESTART_BACKOFF = 0.05
_DEFAULT_POISON_CRASHES = 2

#: ``SlotDecoder.step_many``'s fifth member (a model that counts) -> the
#: key of ``ServingEngine.stats`` each of its sums is added to
_STEP_COUNTERS = {"held": "moe_assignments_held",
                  "touched": "moe_experts_touched",
                  "group": "moe_group_hits",
                  "context": "live_context_tokens",
                  "exit_pass": "loop_exit_pass_sum",
                  "window_context": "window_context_tokens",
                  "sparse_kept": "sparse_rows_kept",
                  "sparse_candidates": "sparse_rows_candidate",
                  "sparse_limited": "sparse_queries_limited"}
#: restart backoff never exceeds this many seconds
_BACKOFF_CAP = 2.0
#: retry-after hint while the tokens/s EMA is still cold (no decode has
#: completed yet): a bounded default, never "retry immediately" — a cold
#: engine's first decode pass is at least a prefill + dispatch away
_COLD_RETRY_AFTER = 0.25
#: restart_log keeps this many most-recent recovery records
_RESTART_LOG_CAP = 64


def _env_int(name: str, default: int) -> int:
  return int(os.environ.get(name, str(default)))


def _env_float(name: str, default: float) -> float:
  return float(os.environ.get(name, str(default)))


class _Step(object):
  """One dispatched ``step_many`` until its tokens are harvested: what it
  returned (``out``), its number in the loop's device queue (``seq``) and,
  lane by lane, the request it was GIVEN live as far as the host knew
  (``reqs``: only these are harvested from it)."""

  __slots__ = ("out", "seq", "reqs")

  def __init__(self, out, seq: int, reqs):
    self.out, self.seq, self.reqs = out, seq, reqs


class _Admission(object):
  """One request between its pop and its lane: what the dispatches of its
  prefill left for the read and the insert that follow them."""

  __slots__ = ("req", "slot", "pages", "table", "shared_tokens", "row",
               "head", "seq", "inserted")

  def __init__(self, req: sched.Request, slot: int):
    self.req, self.slot = req, slot
    self.pages = self.table = None         # the paged pool's, in token order
    self.shared_tokens = 0                 # of them a cached prefix's
    self.row = self.head = self.seq = None  # SlotDecoder.prefill_chunks
    self.inserted = False


class ServingEngine(object):
  """Continuous-batching serving runtime over one model + param set."""

  def __init__(self, params, cfg, num_slots: Optional[int] = None,
               eos_id: Optional[int] = None, pad_id: int = 0,
               max_new_tokens: int = 64, buckets=None, mesh=None,
               poll_interval: Optional[float] = None,
               horizon: Optional[int] = None,
               max_queue: Optional[int] = None,
               max_queued_tokens: Optional[int] = None,
               default_ttl: Optional[float] = None,
               max_restarts: Optional[int] = None,
               restart_backoff: Optional[float] = None,
               poison_crashes: Optional[int] = None,
               page_size: Optional[int] = None,
               num_pages: Optional[int] = None,
               prefix_pages: Optional[int] = None,
               spec_depth: Optional[int] = None,
               spec_layers: Optional[int] = None):
    if eos_id is not None and int(eos_id) == int(pad_id):
      raise ValueError("eos_id and pad_id must differ (both %d)"
                       % int(pad_id))
    if num_slots is None:
      num_slots = _env_int(ENV_SERVE_SLOTS, _DEFAULT_SLOTS)
    if horizon is None:
      horizon = _env_int(ENV_SERVE_HORIZON, _DEFAULT_HORIZON)
    if horizon < 1:
      raise ValueError("horizon must be >= 1, got %d" % horizon)
    self.params = params
    self.cfg = cfg
    self.eos_id = None if eos_id is None else int(eos_id)
    self.pad_id = int(pad_id)
    self.horizon = horizon
    self.default_max_new_tokens = int(max_new_tokens)
    self.max_queue = int(max_queue if max_queue is not None
                         else _env_int(ENV_SERVE_MAX_QUEUE,
                                       _DEFAULT_MAX_QUEUE))
    self.max_queued_tokens = int(
        max_queued_tokens if max_queued_tokens is not None
        else _env_int(ENV_SERVE_MAX_QUEUED_TOKENS,
                      _DEFAULT_MAX_QUEUED_TOKENS))
    ttl = default_ttl if default_ttl is not None \
        else _env_float(ENV_SERVE_TTL, 0.0)
    self.default_ttl = float(ttl) if ttl and ttl > 0 else None
    self.max_restarts = int(max_restarts if max_restarts is not None
                            else _env_int(ENV_SERVE_MAX_RESTARTS,
                                          _DEFAULT_MAX_RESTARTS))
    self.restart_backoff = float(
        restart_backoff if restart_backoff is not None
        else _env_float(ENV_SERVE_RESTART_BACKOFF,
                        _DEFAULT_RESTART_BACKOFF))
    self.poison_crashes = max(1, int(
        poison_crashes if poison_crashes is not None
        else _env_int(ENV_SERVE_POISON_CRASHES, _DEFAULT_POISON_CRASHES)))
    # explicit arguments beat the env knobs (the num_slots rule)
    self.page_size = int(page_size if page_size is not None
                         else _env_int(ENV_SERVE_PAGE_SIZE, 0))
    self.num_pages = int(num_pages if num_pages is not None
                         else _env_int(ENV_SERVE_NUM_PAGES, 0))
    self.prefix_pages = int(prefix_pages if prefix_pages is not None
                            else _env_int(ENV_SERVE_PREFIX_PAGES, 0))
    self.spec_depth = int(spec_depth if spec_depth is not None
                          else _env_int(ENV_SERVE_SPEC_DEPTH, 0))
    spec_layers = int(spec_layers if spec_layers is not None
                      else _env_int(ENV_SERVE_SPEC_LAYERS, 0))
    if self.prefix_pages > 0 and cfg.loop_passes > 1:
      raise ValueError(slots_lib.tfm.loop_refusal(
          cfg, "prefix", "the shared-prefix cache (prefix_pages=%d)"
          % self.prefix_pages))
    if self.prefix_pages > 0 and cfg.ring_layers:
      raise ValueError(slots_lib.tfm.ring_refusal(
          "the shared-prefix cache (prefix_pages=%d)" % self.prefix_pages,
          "prefix"))
    if self.prefix_pages > 0 and cfg.sparse_topk:
      raise ValueError(slots_lib.tfm.sparse_refusal(
          cfg, "the shared-prefix cache (prefix_pages=%d)" % self.prefix_pages,
          "prefix"))
    if self.prefix_pages > 0 and cfg.non_kv_layers:
      raise ValueError(
          "the shared-prefix cache (prefix_pages=%d) reuses a prompt "
          "prefix's K/V PAGES by position; this model's %s layers cache no "
          "K/V pages, and a KDA state at the prefix's end is in no page "
          "(prefix reuse by state snapshot does not exist yet)"
          % (self.prefix_pages, "/".join(cfg.non_kv_layers)))
    if self.prefix_pages > 0 and self.page_size <= 0:
      raise ValueError(
          "the shared-prefix cache shares POOL PAGES — "
          "TOS_SERVE_PREFIX_PAGES > 0 requires TOS_SERVE_PAGE_SIZE > 0")
    self.decoder = slots_lib.SlotDecoder(
        cfg, num_slots, pad_id=pad_id, eos_id=self.eos_id, mesh=mesh,
        page_size=self.page_size, num_pages=self.num_pages,
        spec_depth=self.spec_depth, spec_layers=spec_layers)
    # explicit argument beats the env knob (the num_slots/horizon rule);
    # neither: the shapes of the decoder's own prefill plan
    self.buckets = tuple(buckets) if buckets is not None \
        else sched.buckets_from_env(self.decoder.buckets)
    # spec rounds per dispatch: each round emits 1..spec_depth tokens,
    # so this keeps the best-case tokens-per-dispatch near the horizon
    self._spec_rounds = max(1, -(-horizon // max(1, self.spec_depth)))
    self._poll = float(poll_interval if poll_interval is not None
                       else os.environ.get(ENV_SERVE_POLL, _DEFAULT_POLL))
    self._queue = sched.RequestQueue()
    self._lock = threading.Lock()
    self._stats_lock = threading.Lock()
    self._requests = {}                    # rid -> Request (in flight or done)
    self._slots: List[Optional[sched.Request]] = [None] * num_slots
    self._slabs = None                     # built lazily on start()
    # paged-KV host state — (re)built with the slab (_ensure_slabs): the
    # allocator/trie describe DEVICE pages, so a rebuilt slab resets them
    self._pool: Optional[sched.PagePool] = None
    self._prefix: Optional[sched.PrefixCache] = None
    self._req_pages = {}                   # rid -> [page ids] (one ref each)
    self._last = np.full((num_slots,), self.pad_id, np.int32)
    # how many decode steps may be dispatched ahead of the one being read:
    # one over the contiguous slab; none where the harvest itself changes
    # what the next step needs (the paged pool releases pages and resets
    # page tables in it) or where the host cannot count a lane's budget
    # before the read (speculation emits a data-dependent number a lane)
    self._run_ahead = not self.decoder.paged and self.spec_depth == 0
    self._flight: Optional[_Step] = None   # the step dispatched and unread
    self._stop_evt = threading.Event()
    self._thread: Optional[threading.Thread] = None
    self._loop_error: Optional[BaseException] = None
    self._draining = False
    # the requests popped and not yet in a lane (queue depth, drain and
    # _recover read both): the ONE whose admission's own calls are running,
    # and the admissions dispatched with their inserts and left unread
    # behind a decode step (_admit_behind), oldest first: one, and a second
    # only from a step's dispatch to the seat of the older. The request a
    # crash is blamed on is the first while its calls run, nobody's else
    self._admitting: Optional[sched.Request] = None
    self._unread: List[_Admission] = []
    self._blame: Optional[sched.Request] = None
    self._crash_streak = 0
    self._tok_rate = 0.0                   # EMA tokens/s over decode passes
    #: bounded record of crash recoveries: {t, duration_s, replayed,
    #: poisoned, streak, error} — tests/test_serving.py reads recovery
    #: latency off this
    self.restart_log: List[dict] = []
    # counters ONLY (monotonic): StatsSnapshot.delta subtracts these, so
    # a last-write gauge here would read as a bogus per-pass delta —
    # gauges (kv_pages_in_use/free) live on the obs registry and the
    # kv_pages_in_use/kv_pages_free properties instead
    self.stats = {"steps": 0, "live_slot_steps": 0, "emitted_tokens": 0,
                  "prefills": 0, "submitted": 0, "completed": 0,
                  "rejected": 0,
                  "expired": 0, "cancelled": 0, "replays": 0,
                  "engine_restarts": 0, "poisoned": 0,
                  "replay_mismatches": 0, "prefix_hits": 0,
                  "prefix_evictions": 0, "spec_accepted": 0,
                  "spec_rejected": 0,
                  # device dispatches: one per _decode_once, one per
                  # prefill chunk (SlotDecoder.prefill_chunks counts them, and
                  # beside them the tokens the chunks computed and how
                  # many of those were a padded tail's padding)
                  "decode_dispatches": 0, "prefill_chunks": 0,
                  "prefill_tokens": 0, "prefill_padded_tokens": 0,
                  # of decode_dispatches, the step_many dispatched while
                  # the one before it was unread: its lane state came from
                  # that step's outputs on the device (_carried)
                  "decode_dispatches_ahead": 0,
                  # of prefill_chunks, those dispatched while a decode step
                  # was unread (_admit_behind; a later admission of a pass
                  # that leaves its step in flight), and of
                  # prefills, the admissions into a lane that step was
                  # certain to free and had not freed yet
                  "prefill_chunks_behind_decode": 0, "admits_ahead": 0,
                  # a model with held experts (SlotDecoder.counted), summed
                  # over LIVE lanes by step_many on the device: (token,
                  # expert) assignments to experts held here, held experts
                  # that got a live token (a layer-step), and the tokens
                  # the live lanes' caches held when each step began
                  "moe_assignments_held": 0, "moe_experts_touched": 0,
                  "live_context_tokens": 0,
                  # under a router's group limit: live tokens (a layer-step)
                  # whose kept groups include one a held expert lies in
                  "moe_group_hits": 0,
                  # a looped model (likewise counted): the pass at which
                  # its exit gates let each live lane's token go, summed;
                  # over live_slot_steps it is the mean exit pass
                  "loop_exit_pass_sum": 0,
                  # a model whose window layers hold rings (likewise
                  # counted): the rows ONE window layer has to read for
                  # each live lane's step, min(cursor, window), summed
                  # (live_context_tokens is a full layer's)
                  "window_context_tokens": 0,
                  # a model whose attention SELECTS (sparse_topk; likewise
                  # counted): entries the live lanes' decode queries kept
                  # and chose among (a layer-step each), live decode queries
                  # with more candidates than the selection keeps (over
                  # live_slot_steps: the share it bites), the same two for
                  # real prompt tokens (counted on the host, a token and not
                  # a layer), decode reads under a keep mask and the rows of
                  # the index-key leaf those read (whole: slots x max_seq_len
                  # a read)
                  "sparse_rows_kept": 0, "sparse_rows_candidate": 0,
                  "sparse_queries_limited": 0,
                  "sparse_prefill_queries": 0, "sparse_prefill_limited": 0,
                  "decode_attn_reads_sparse": 0, "index_rows_read": 0,
                  # calls of a slab-returning program, and those after
                  # which the slab that went in is deleted: its donation
                  # was USED, the program ran in place (_on_slab)
                  "slab_dispatches": 0, "slab_in_place": 0,
                  # per-slot cursor writes of cache leaves the step_many
                  # dispatches made (leaves x horizon each), and those of
                  # them by the DMA kernel and not XLA's loop
                  # (SlotDecoder.cursor_writes, known when the program
                  # is traced)
                  "cursor_leaf_writes": 0, "cursor_leaf_writes_dma": 0,
                  # per-slot single-token cache reads of the same
                  # dispatches (layer applications x horizon each), and
                  # those of them by the attention kernel that stops at
                  # each slot's cursor (SlotDecoder.attn_reads)
                  "decode_attn_reads": 0, "decode_attn_reads_ragged": 0,
                  # those of them over a RING leaf (a window layer's): what
                  # splits a trace's %decode_attention calls by leaf kind
                  "decode_attn_reads_ring": 0,
                  # grouped products of held experts the prefill and
                  # step_many dispatches made (three a layer application),
                  # and those of them by ops.expert_product's kernel and not
                  # lax.ragged_dot (SlotDecoder.expert_products)
                  "expert_products": 0, "expert_products_kernel": 0,
                  # exact selections of an indexer the same dispatches made
                  # (one a layer application under sparse_topk), and those
                  # of them searched in ops.select_topk's kernel and not by
                  # XLA's passes (SlotDecoder.index_selections)
                  "index_selections": 0, "index_selections_kernel": 0,
                  # the loop thread's SELF seconds by phase, written by
                  # the regions below (obs.spans.region): the keys
                  # partition the loop thread's wall time
                  "t_reap_s": 0.0, "t_idle_s": 0.0, "t_admit_s": 0.0,
                  "t_prefill_s": 0.0, "t_prefill_sync_s": 0.0,
                  "t_insert_s": 0.0, "t_decode_prep_s": 0.0,
                  "t_decode_dispatch_s": 0.0, "t_decode_fetch_s": 0.0,
                  "t_decode_harvest_s": 0.0,
                  # beside each: the seconds of it in which the device was
                  # KNOWN drained (obs.spans.DeviceQueue): nothing the loop
                  # dispatched could still be running. Never over its
                  # t_*_s; their sum is a lower bound of the device's idle
                  # time (docs/OBSERVABILITY.md)
                  "empty_reap_s": 0.0, "empty_idle_s": 0.0,
                  "empty_admit_s": 0.0, "empty_prefill_s": 0.0,
                  "empty_prefill_sync_s": 0.0, "empty_insert_s": 0.0,
                  "empty_decode_prep_s": 0.0,
                  "empty_decode_dispatch_s": 0.0,
                  "empty_decode_fetch_s": 0.0,
                  "empty_decode_harvest_s": 0.0}
    # the loop thread is the only dispatcher of this engine's programs and
    # the only thread that waits for them: it marks every dispatch call's
    # return and every blocking read's return here, and the regions charge
    # the drained seconds between the two to their phase
    self._devq = obs_spans.DeviceQueue()
    self._slab_seq = 0                     # the dispatch self._slabs is of
    # obs seam (docs/OBSERVABILITY.md): every loop-thread phase is one
    # obs.spans.region — counter (stats above, always on), trace
    # annotation (while a jax.profiler session is live) and recorder
    # span (TOS_OBS=1; the per-dispatch phases only with TRACE_DETAIL)
    self._rec = obs_spans.active()
    self._detail = self._rec is not None and os.environ.get(
        ENV_OBS_TRACE_DETAIL, "1") not in ("0",)
    reg = obs_metrics.active()
    self._obs_m = None if reg is None else {
        "tokens": reg.counter("serve.tokens"),
        "submitted": reg.counter("serve.submitted"),
        "completed": reg.counter("serve.completed"),
        "prefills": reg.counter("serve.prefills"),
        "steps": reg.counter("serve.steps"),
        "rejected": reg.counter("serve.rejected"),
        "expired": reg.counter("serve.expired"),
        "cancelled": reg.counter("serve.cancelled"),
        "replays": reg.counter("serve.replays"),
        "engine_restarts": reg.counter("serve.engine_restarts"),
        "poisoned": reg.counter("serve.poisoned"),
        "prefix_hits": reg.counter("serve.prefix_hits"),
        "prefix_evictions": reg.counter("serve.prefix_evictions"),
        "spec_accepted": reg.counter("serve.spec_accepted"),
        "spec_rejected": reg.counter("serve.spec_rejected"),
        "occupancy": reg.gauge("serve.occupancy"),
        "queue_depth": reg.gauge("serve.queue_depth"),
        "slots_active": reg.gauge("serve.slots_active"),
        "kv_pages_in_use": reg.gauge("serve.kv_pages_in_use"),
        "kv_pages_free": reg.gauge("serve.kv_pages_free"),
        "decode_ms": reg.histogram("serve.decode_ms"),
    }
    # the SLO plane's latency objects (obs.quantiles): mergeable
    # streaming sketches — per-executor sketches ship whole over the OBS
    # verb and the driver MERGES them, so a cluster p99 is a real p99,
    # not an average of per-process ones (docs/OBSERVABILITY.md)
    self._obs_q = None if reg is None else {
        "ttft_ms": reg.quantiles("serve.ttft_ms"),
        "tpot_ms": reg.quantiles("serve.tpot_ms"),
        "e2e_ms": reg.quantiles("serve.e2e_ms"),
        "queue_wait_ms": reg.quantiles("serve.queue_wait_ms"),
    }

  def _count(self, key: str, n: int = 1) -> None:
    """Bump a stats key and its obs counter twin (when the plane is on).

    Locked: rejected/expired/cancelled are bumped from client threads
    (submit, cancel-on-a-dead-engine) AND the loop thread — a bare
    ``+=`` interleaving would drop increments."""
    with self._stats_lock:
      self.stats[key] += n
    if self._obs_m is not None and key in self._obs_m:
      self._obs_m[key].inc(n)

  def stats_snapshot(self) -> obs_metrics.StatsSnapshot:
    """Subtraction baseline over the LIVE ``stats`` dict — the safe way
    to read per-pass deltas while the loop thread keeps mutating it
    (obs.metrics.StatsSnapshot; benchmarks/runners/serve_engine.py)."""
    return obs_metrics.snapshot_stats(self.stats)

  # -- lifecycle ------------------------------------------------------------

  @property
  def num_slots(self) -> int:
    return self.decoder.num_slots

  @property
  def kv_pages_in_use(self) -> int:
    """Allocated pool pages (0 when paging is off / engine not started)."""
    pool = self._pool
    return 0 if pool is None else pool.in_use

  @property
  def kv_pages_free(self) -> int:
    pool = self._pool
    return 0 if pool is None else pool.free_pages

  def _ensure_slabs(self) -> None:
    """(Re)build the device slab AND the host page state describing it —
    a fresh slab means every old page id is meaningless, so the
    allocator, prefix trie and per-request page lists reset with it
    (crash recovery rebuilds everything; replayed requests re-allocate
    at re-admission)."""
    if self._slabs is not None:
      return
    self._slabs = self.decoder.init_slabs()
    self._slab_seq = self._devq.dispatched()
    if self.decoder.paged:
      self._pool = sched.PagePool(self.decoder.num_pages)
      self._prefix = sched.PrefixCache(self.page_size, self.prefix_pages) \
          if self.prefix_pages > 0 else None
      self._req_pages = {}

  def _on_slab(self, op):
    """Run ``op(slab)`` — one slab-returning ``SlotDecoder`` program — on
    the engine's slab and rebind ``self._slabs`` to the slab it returns
    (the result, or a tuple's first member). Every such program takes its
    slab donated; JAX only WARNS when a donation turns out unusable (an
    output whose shape or layout no longer matches) and then copies the
    slab in silence, so the counters say what happened: the slab that went
    in is deleted exactly when the program took it over. The call's
    return is a dispatch of the loop's device queue. Loop thread only: the
    slab is lifecycle-fenced (start() and stop() touch it before the
    thread exists and after its join), so no lock is held here."""
    old = self._slabs
    out = op(old)
    self._slab_seq = self._devq.dispatched()
    self._slabs = out[0] if isinstance(out, tuple) else out
    self.stats["slab_dispatches"] += 1
    self.stats["slab_in_place"] += slots_lib.consumed(old)
    return out

  def start(self) -> "ServingEngine":
    if self._thread is not None and self._thread.is_alive():
      return self
    self._stop_evt.clear()
    self._loop_error = None
    self._draining = False
    self._crash_streak = 0
    self._queue.reopen()
    self._devq.unknown()                   # a fresh loop vouches for nothing
    self._ensure_slabs()
    self._thread = threading.Thread(target=self._loop, daemon=True,
                                    name="tos-serving-engine")
    self._thread.start()
    return self

  def stop(self, timeout: float = 30.0) -> None:
    """Stop the loop thread; unfinished requests (queued AND in flight)
    are failed. Idempotent, and safe before :meth:`start`."""
    self._stop_evt.set()
    t = self._thread
    if t is not None:
      t.join(timeout=timeout)
      if t.is_alive():
        logger.warning("serving loop did not stop within %.1fs", timeout)
    err = RuntimeError("serving engine stopped")
    # close-and-drain is atomic under the queue's own lock: a submit
    # racing this stop either lands before (and is failed here) or
    # fails fast on the closed queue — never orphaned (the old
    # submit-vs-loop-death race, docs/ROBUSTNESS.md)
    for req in self._queue.close(err):
      req.finish(err)
    for req in self._take_in_flight():
      req.finish(err)                      # finish() is idempotent
    self._slabs = None                     # next start() gets a fresh slab
    # page ids described the dropped slab: the allocator/trie die with it
    self._pool = None
    self._prefix = None
    self._req_pages = {}

  def drain(self, timeout: float) -> bool:
    """Graceful shutdown: stop admission, finish every accepted request
    (queued and in flight), then stop. Returns True when all accepted
    work completed inside ``timeout`` (requests left at the deadline are
    failed by the final :meth:`stop`). Rolling restarts and the
    cached-engine rebuild in ``make_serving_predict_fn`` use this so
    zero accepted requests are shed. ``timeout`` is required — the
    wait parks on in-flight progress, so the deadline must be the
    caller's choice (TOS001, like ``wait_alert``)."""
    deadline = time.monotonic() + max(0.0, float(timeout))
    self._draining = True                  # submit() rejects from here on
    while time.monotonic() < deadline:
      if self._loop_error is not None:
        break
      t = self._thread
      if t is None or not t.is_alive():
        break
      if self._idle():
        break
      time.sleep(min(0.05, self._poll))
    completed = self._idle() and self._loop_error is None
    self.stop(timeout=max(1.0, deadline - time.monotonic()))
    return completed

  def _idle(self) -> bool:
    # order matters (drain's zero-shed contract): the queue is checked
    # FIRST. A pop marks the request as mid-admission while the queue
    # lock is held (pop_nowait's on_pop hook), so once we observe the
    # queue empty, any popped request is already visible in
    # _admitting or a slot — there is no in-neither window to misread
    # as idle.
    if len(self._queue) > 0:
      return False
    with self._lock:
      return not (any(r is not None for r in self._slots)
                  or self._admitting is not None or self._unread)

  def __enter__(self):
    return self.start()

  def __exit__(self, *exc):
    self.stop()

  # -- client API -----------------------------------------------------------

  def submit(self, prompt, max_new_tokens: Optional[int] = None,
             deadline: Optional[float] = None,
             ttl: Optional[float] = None,
             trace_id: Optional[str] = None) -> int:
    """Queue one prompt; returns the request id.

    ``deadline`` is an absolute ``time.monotonic()`` bound; ``ttl`` is
    seconds from now (pass one or the other). An admitted request whose
    deadline passes fails with ``DeadlineExceeded`` — while queued,
    without ever taking a slot; in flight, at the next horizon boundary.
    Raises ``ServingOverloaded`` (structured: queue depth, queued token
    mass, retry-after hint) instead of queueing without bound.
    ``trace_id`` joins an existing request-scoped trace (the fleet
    passes the FleetRequest's, so a cross-replica failover hop stays ONE
    trace); None mints a fresh one on the Request.
    """
    budget = int(max_new_tokens if max_new_tokens is not None
                 else self.default_max_new_tokens)
    if budget < 1:
      raise ValueError("max_new_tokens must be >= 1, got %d" % budget)
    now = time.monotonic()
    if deadline is not None and ttl is not None:
      raise ValueError("pass deadline OR ttl, not both")
    if ttl is None and deadline is None and self.default_ttl is not None:
      ttl = self.default_ttl
    if ttl is not None:
      deadline = now + float(ttl)
    req = sched.Request(prompt, budget, deadline=deadline,
                        trace_id=trace_id)
    if len(req.prompt) < 1:
      # reject here, not in the loop thread: a chunk_plan(0) crash there
      # would take every other in-flight request down with it
      raise ValueError("prompt must contain at least one token")
    if len(req.prompt) + budget > self.cfg.max_seq_len:
      raise ValueError(
          "prompt of %d tokens + budget %d exceeds the max_seq_len=%d "
          "slot cache" % (len(req.prompt), budget, self.cfg.max_seq_len))
    if self.decoder.paged:
      needed = -(-(len(req.prompt) + budget) // self.page_size)
      if needed > self.decoder.num_pages - 1:
        # reject here, not in the loop: a request no amount of
        # completions can ever page in would pin admission forever
        raise ValueError(
            "prompt of %d tokens + budget %d needs %d KV pages but the "
            "pool holds %d allocatable (TOS_SERVE_NUM_PAGES=%d minus "
            "the trash page)" % (len(req.prompt), budget, needed,
                                 self.decoder.num_pages - 1,
                                 self.decoder.num_pages))
    # past validation: this IS traffic — the availability SLO's
    # denominator (obs.slo: bad = rejected + poisoned over submitted).
    # Malformed requests (the ValueErrors above) are caller bugs, not
    # unavailability, and stay out of both sides of the ratio.
    self._count("submitted")
    if req.expired(now):
      self._count("expired")
      raise sched.DeadlineExceeded(
          "request dead on arrival: its deadline already passed at "
          "submit")
    if self._draining:
      self._count("rejected")   # drain-time turn-aways must be visible
      raise sched.ServingOverloaded(
          "serving engine is draining — admission is closed",
          queue_depth=len(self._queue),
          queued_tokens=self._queue.token_mass,
          retry_after=self._retry_after(self._queue.token_mass),
          draining=True)
    if self._loop_error is not None:
      raise RuntimeError("serving loop died") from self._loop_error
    with self._lock:
      self._requests[req.rid] = req
    try:
      self._queue.push_bounded(req, self.max_queue, self.max_queued_tokens)
    except sched.ServingOverloaded as e:
      with self._lock:
        self._requests.pop(req.rid, None)
      self._count("rejected")
      e.retry_after = self._retry_after(e.queued_tokens)
      raise
    except sched.QueueClosed:
      # the loop died (or the engine stopped) between our liveness check
      # and the push — the close happened under the queue's lock, so we
      # fail HERE instead of orphaning the request until its timeout
      with self._lock:
        self._requests.pop(req.rid, None)
      if self._loop_error is not None:
        raise RuntimeError("serving loop died") from self._loop_error
      raise RuntimeError("serving engine stopped")
    return req.rid

  def _retry_after(self, queued_tokens: int) -> float:
    """Backpressure hint: how long until the live decode rate clears the
    current backlog. Before the first decode completes the tokens/s EMA
    is 0 and the backlog estimate is undefined — a cold engine answers
    the bounded ``_COLD_RETRY_AFTER`` default instead of a
    retry-immediately hint that would have clients hammering an engine
    still compiling its first dispatch."""
    rate = self._tok_rate
    if rate <= 0:
      return round(max(self._poll, _COLD_RETRY_AFTER), 3)
    return round(min(60.0, max(self._poll, queued_tokens / rate)), 3)

  def cancel(self, rid: int, timeout: float) -> bool:
    """Cancel a request: queued → failed without taking a slot; in
    flight → its slot frees at the next horizon boundary, exactly like
    EOS. Blocks (bounded) until the request actually finished; returns
    True when it did. Already-finished requests return True unchanged.
    ``timeout`` is required — the wait parks on the slot release, so
    the deadline must be the caller's choice (TOS001).
    """
    req = self._req(rid)
    if req.done.is_set():
      return True
    req.cancelled.set()
    t = self._thread
    if t is None or not t.is_alive():
      # no loop to reap it: fail queued entries synchronously so the
      # caller is not parked on a dead engine
      self._reap_queue(time.monotonic())
    req.done.wait(timeout=timeout)
    return req.done.is_set()

  def _req(self, rid: int) -> sched.Request:
    with self._lock:
      try:
        return self._requests[rid]
      except KeyError:
        raise KeyError("unknown request id %r" % (rid,))

  def request(self, rid: int) -> sched.Request:
    """The live Request handle (timing/latency fields ride on it).

    Hold the handle before calling :meth:`result`/:meth:`poll` — those
    pop the registry entry once the output is delivered."""
    return self._req(rid)

  def poll(self, rid: int) -> Optional[np.ndarray]:
    """The finished output (prompt + generated), or None if in flight."""
    req = self._req(rid)
    if not req.done.is_set():
      return None
    return self._result_of(req, pop=True)

  def result(self, rid: int, timeout: float = 600.0) -> np.ndarray:
    """Block (bounded) for one request's output. Fails FAST — with the
    loop's root cause — when the engine is dead or was never started,
    instead of sitting out the full timeout."""
    req = self._req(rid)
    self._wait_done(req, timeout, "request %d" % rid)
    return self._result_of(req, pop=True)

  def _wait_done(self, req: sched.Request, timeout: float,
                 what: str) -> None:
    deadline = time.monotonic() + timeout
    chunk = max(0.05, self._poll)
    while True:
      remaining = deadline - time.monotonic()
      if req.done.wait(timeout=max(0.0, min(chunk, remaining))):
        return
      self._raise_if_dead(req, what)
      if deadline - time.monotonic() <= 0:
        raise TimeoutError("%s not finished within %.1fs"
                           % (what, timeout))

  def _raise_if_dead(self, req: Optional[sched.Request],
                     what: str) -> None:
    """Fail-fast check for waiters: a dead (or never-started) engine
    cannot finish anything — raise the root cause now, not at the
    caller's timeout."""
    if req is not None and req.done.is_set():
      return
    if self._loop_error is not None:
      raise RuntimeError("serving loop died; %s cannot finish"
                         % what) from self._loop_error
    t = self._thread
    if t is None:
      raise RuntimeError(
          "serving engine was never started — call start() before "
          "waiting on %s" % what)
    if not t.is_alive():
      raise RuntimeError("serving engine is stopped; %s cannot finish"
                         % what)

  def _result_of(self, req: sched.Request, pop: bool) -> np.ndarray:
    if pop:
      with self._lock:
        self._requests.pop(req.rid, None)
    err = req.error
    if isinstance(err, (sched.DeadlineExceeded, sched.RequestCancelled,
                        sched.PoisonedRequest)):
      raise err                  # structured verdicts surface as-is
    if err is not None:
      raise RuntimeError("request %d failed" % req.rid) from err
    return req.output()

  def stream(self, rid: int, timeout: float = 600.0):
    """Yield generated tokens as they are produced (EOS inclusive).

    Crash replays are invisible here: the engine suppresses the
    already-emitted prefix, so a consumer sees each position exactly
    once. Fails fast on a dead/never-started engine."""
    req = self._req(rid)
    deadline = time.monotonic() + timeout
    t0 = time.monotonic()
    emitted = 0
    while True:
      remaining = deadline - time.monotonic()
      if remaining <= 0:
        raise TimeoutError("stream for request %d stalled" % rid)
      try:
        tok = req.stream_q.get(timeout=min(remaining, self._poll * 10))
      except std_queue.Empty:
        self._raise_if_dead(req, "request %d" % rid)
        continue
      if tok is None:
        break
      emitted += 1
      yield tok
    if self._rec is not None:
      # the delivery phase of the waterfall: stream attach → sentinel
      self._rec.record_span("serve.stream", t0, time.monotonic() - t0,
                            trace=req.trace_id, rid=rid, tokens=emitted)
    with self._lock:
      self._requests.pop(rid, None)
    err = req.error
    if isinstance(err, (sched.DeadlineExceeded, sched.RequestCancelled,
                        sched.PoisonedRequest)):
      raise err
    if err is not None:
      raise RuntimeError("request %d failed after %d token(s)"
                         % (rid, emitted)) from err

  def generate(self, prompts: Sequence,
               max_new_tokens: Optional[int] = None,
               timeout: float = 600.0,
               detailed: bool = False) -> List:
    """Submit a batch of prompts and wait for all outputs (in order).

    If a mid-list submit is rejected (overload/validation), the
    already-submitted prefix is cancelled before re-raising — no
    orphaned work keeps burning slots for a caller that went away.

    ``detailed=True`` returns ``{"tokens": ndarray, "trace_id": str,
    "timing": dict}`` per prompt instead of the bare array — the
    per-request timing ledger (``Request.timing``: submitted/admitted/
    prefill_done/first_token/finished stamps + ttft/e2e/queue_wait/tpot)
    and the trace id for ``obs_report --request``."""
    rids = []
    try:
      for p in prompts:
        rids.append(self.submit(p, max_new_tokens=max_new_tokens))
    except BaseException:
      for rid in rids:
        with contextlib.suppress(Exception):
          self.cancel(rid, timeout=1.0)
      raise
    deadline = time.monotonic() + timeout
    outs = []
    for rid in rids:
      req = self._req(rid)      # hold the handle: result() pops the map
      out = self.result(rid, timeout=max(0.001,
                                         deadline - time.monotonic()))
      if detailed:
        outs.append({"tokens": out, "trace_id": req.trace_id,
                     "timing": req.timing()})
      else:
        outs.append(out)
    return outs

  @property
  def alive(self) -> bool:
    """False once the engine is terminally dead (loop exhausted its
    restart budget) or stopped — callers holding a cached engine must
    rebuild instead of reusing it. A transient crash mid-replay keeps
    ``alive`` True: the engine is healing, not dead. True before
    ``start()`` (a constructed engine is startable)."""
    if self._loop_error is not None:
      return False
    t = self._thread
    return t is None or t.is_alive()

  @property
  def occupancy(self) -> float:
    """Live-slot fraction over all decode steps so far (goodput proxy)."""
    steps = self.stats["steps"]
    if not steps:
      return 0.0
    return self.stats["live_slot_steps"] / float(steps * self.num_slots)

  # -- load telemetry (the fleet router's dispatch inputs) -------------------
  # The same numbers the HEALTH wire carries as serve.* gauges, exposed
  # as cheap properties so a driver-side router (serving.fleet) can
  # score replicas without the obs plane being on.

  @property
  def queue_depth(self) -> int:
    """Queued-or-admitting request count: a request the loop popped but
    has not finished prefilling into a slot is still BACKLOG — without
    counting it, a replica mid-prefill reads (queue 0, occupancy 0) and
    a load-aware router double-books exactly the replica that is busiest
    admitting (the drain _idle rule, applied to the scoring read)."""
    return len(self._queue) + len(self._popped())

  @property
  def queued_tokens(self) -> int:
    """Queued-or-admitting token mass: sum of prompt+budget over the
    backlog (same mid-admission rule as :attr:`queue_depth`)."""
    return self._queue.token_mass + sum(r.token_cost
                                        for r in self._popped())

  def _popped(self) -> List[sched.Request]:
    """The requests popped and not yet in a lane: unread behind a decode
    step, or mid-admission (any thread: a snapshot, never locked)."""
    adm = self._admitting
    return [a.req for a in list(self._unread)] \
        + ([adm] if adm is not None else [])

  def _take_in_flight(self) -> List[sched.Request]:
    """Every request in a lane or popped for one, in the order they were
    running, taken OUT of the engine's books with the step in flight (a
    crash, a stop or the loop's death owns them from here)."""
    with self._lock:
      live = [r for r in self._slots if r is not None]
      self._slots = [None] * self.num_slots
      live += self._popped()
      self._admitting, self._unread = None, []
    self._flight = None
    return live

  @property
  def tokens_per_sec(self) -> float:
    """Live tokens/s EMA over decode passes (0.0 before the first)."""
    return self._tok_rate

  @property
  def slots_in_use(self) -> int:
    with self._lock:
      return sum(1 for r in self._slots if r is not None)

  @property
  def occupancy_now(self) -> float:
    """Instantaneous occupied-slot fraction (vs the historical
    :attr:`occupancy` goodput proxy)."""
    return self.slots_in_use / float(self.num_slots)

  def kill(self, cause: Optional[BaseException] = None,
           timeout: float = 5.0) -> None:
    """Terminal-death injection seam: die AS IF the loop exhausted its
    restart budget — the loop thread exits, :attr:`alive` flips False,
    and every waiter (queued, in flight, future) fails fast with
    ``cause``. The fleet's chaos path (``TOS_CHAOS_FLEET`` kill actions,
    ``serving.fleet``) and the failover tests drive this; production
    code should use :meth:`stop`/:meth:`drain`."""
    err = cause if cause is not None else RuntimeError(
        "serving engine killed")
    self._stop_evt.set()                   # the loop exits its next pass
    self._die(err)
    t = self._thread
    if t is not None:
      t.join(timeout=timeout)

  # -- engine loop ----------------------------------------------------------

  def _loop(self) -> None:
    self._blame = None                     # a fresh loop blames nobody yet
    while not self._stop_evt.is_set():
      try:
        self._pass()
      except BaseException as e:  # noqa: BLE001 - crash-replay recovery;
        # terminal failures are forwarded to every waiter by _die
        if not self._recover(e):
          return

  def _pass(self) -> None:
    """One pass of the loop: it dispatches in the order the device can run
    and reads in the order the device finishes. With a live lane the decode
    step goes first, BEFORE the step in flight is read where one is
    (:meth:`_decode_once`), and ONE admission is queued behind it
    (:meth:`_admit_behind`); the older step's tokens are read and
    harvested, then the first token of the admission that was waiting
    (:meth:`_admit`), and further free lanes are admitted one at a time,
    behind the running step. With no live lane and nothing in flight there
    is no step to queue behind: the pass only admits, or waits for work."""
    self._ensure_slabs()                   # rebuilt after a crash
    # reap/admit/idle run every pass of an IDLE engine too: counter
    # and annotation only, never the bounded recorder
    with obs_spans.region("serve.reap", self.stats, "t_reap_s",
                          record=False, queue=self._devq):
      self._reap()
    seat = None
    if self._decoding():
      seat = self._decode_once()
      self._crash_streak = 0               # a full decode pass = healthy
    with obs_spans.region("serve.admit", self.stats, "t_admit_s",
                          record=False, queue=self._devq):
      self._admit(seat)
    if not self._decoding():
      # idle: bounded block until work arrives (TOS001)
      with obs_spans.region("serve.idle", self.stats, "t_idle_s",
                            record=False, queue=self._devq):
        self._vouch_idle()
        self._queue.wait_nonempty(timeout=self._poll)

  def _decoding(self) -> bool:
    """A lane is live, a step is unread, or an admission waits behind one:
    there is decode work for the next pass."""
    return self._flight is not None or bool(self._unread) \
        or any(r is not None for r in self._slots)

  def _vouch_idle(self) -> None:
    """An idle pass whose newest dispatch nobody read (a freed lane's
    ``reset_slots``, a slab rebuilt after a crash): its output is the
    slab, and once every leaf of that is ready the queue is drained.
    Looked at, never waited for. (Not before a dispatch: behind an
    ``insert`` the look comes too early to find it done, and costs 0.3 ms
    a pass over a slab of 108 leaves: PERF.md section 6, PR 36.)"""
    q = self._devq
    if not q.known_drained and self._slab_seq == q.seq \
        and slots_lib.ready(self._slabs):
      q.drained(q.seq)

  # -- crash-replay recovery -------------------------------------------------

  def _recover(self, error: BaseException) -> bool:
    """Heal from a loop crash: rebuild device state and transparently
    replay every in-flight request from its prompt (greedy ⇒ the
    regenerated stream is bit-identical; the already-emitted prefix is
    suppressed). Returns False when the engine must die instead
    (stopping, or the consecutive-restart budget is spent)."""
    if self._stop_evt.is_set():
      return False                         # stop() owns cleanup from here
    t_crash = time.monotonic()
    self._crash_streak += 1
    streak = self._crash_streak
    if streak > self.max_restarts:
      logger.exception("serving loop died terminally (%d consecutive "
                       "crashes > max_restarts=%d)",
                       streak, self.max_restarts)
      self._die(error)
      return False
    logger.warning("serving loop crashed (consecutive crash %d/%d), "
                   "recovering: %r", streak, self.max_restarts, error)
    self._count("engine_restarts")
    # collect the victims: in-flight slots in slot order, then the
    # requests popped for a lane (unread behind a step, or mid-admission
    # in the _admit prefill path): they are in neither the queue nor a
    # slot and must not be lost. The step in flight goes with them
    victims = self._take_in_flight()
    blame, self._blame = self._blame, None
    self._last[:] = self.pad_id
    self._devq.unknown()                   # whatever was running, or failed
    self._slabs = None                     # fresh slab next iteration
    # the crash took the slab's pages with it: allocator, prefix trie
    # and per-request page lists rebuild with the slab (_ensure_slabs);
    # replayed requests re-allocate at re-admission
    self._pool = None
    self._prefix = None
    self._req_pages = {}
    # blame: a crash during admission's own calls implicates exactly the
    # request being prefilled; a crash mid-decode (an admission may wait
    # unread behind the step) cannot be attributed and implicates every
    # in-flight lane and that admission
    for req in victims:
      if blame is None or req is blame:
        req.crash_count += 1
    now = time.monotonic()
    replay: List[sched.Request] = []
    poisoned = 0
    for req in victims:
      if req.done.is_set():
        continue
      if req.cancelled.is_set():
        self._count("cancelled")
        req.finish(sched.RequestCancelled(
            "request %d cancelled" % req.rid))
        continue
      if req.expired(now):
        self._count("expired")
        req.finish(sched.DeadlineExceeded(
            "request %d deadline passed during crash recovery" % req.rid))
        continue
      if req.crash_count >= self.poison_crashes:
        poisoned += 1
        self._count("poisoned")
        err = sched.PoisonedRequest(
            "request %d was in flight across %d consecutive engine "
            "crashes — failed, not replayed" % (req.rid, req.crash_count))
        err.__cause__ = error
        req.finish(err)
        continue
      replay.append(req)
    try:
      # ahead of the backlog, original order preserved: appendleft in
      # reverse puts victims back in the order they were running
      for req in reversed(replay):
        req.begin_replay()
        self._queue.push_front(req)
    except sched.QueueClosed:
      err = RuntimeError("serving engine stopped")
      for req in replay:
        req.finish(err)
      return False
    if replay:
      self._count("replays", len(replay))
      if self._rec is not None:
        for req in replay:
          # the crash-replay suppression window on the request's own
          # trace: the next len(tokens) emits re-derive delivered
          # positions (docs/ROBUSTNESS.md); the waterfall shows it as
          # an instant on the trace, streak-stamped
          self._rec.event("serve.replay", trace=req.trace_id,
                          rid=req.rid, suppressed=len(req.tokens),
                          streak=streak)
    if poisoned:
      # removing the suspected cause IS progress: don't let a healed
      # poison sequence burn the restart budget of a real crash loop
      self._crash_streak = 0
    backoff = min(_BACKOFF_CAP,
                  self.restart_backoff * (2 ** (streak - 1)))
    if backoff > 0:
      self._stop_evt.wait(backoff)         # interruptible by stop()
    rec = {"t": t_crash, "duration_s": time.monotonic() - t_crash,
           "replayed": len(replay), "poisoned": poisoned,
           "streak": streak, "error": repr(error)[:200]}
    self.restart_log.append(rec)
    del self.restart_log[:-_RESTART_LOG_CAP]
    if self._rec is not None:
      self._rec.event("serve.restart", replayed=len(replay),
                      poisoned=poisoned, streak=streak)
    return True

  def _die(self, error: BaseException) -> None:
    """Terminal loop death: mark the root cause, then fail every waiter
    — queued, in flight, and mid-admission — so nobody burns a timeout.
    The queue close is atomic with its drain (scheduler.RequestQueue),
    so a racing submit can never orphan a request behind it."""
    self._loop_error = error
    for req in self._queue.close(error):
      req.finish(error)
    for req in self._take_in_flight():
      req.finish(error)

  # -- reaping (deadlines & cancellation) ------------------------------------

  def _reap(self) -> None:
    """Fail expired/cancelled requests: queued ones without ever taking
    a slot, in-flight ones by freeing their slot at this horizon
    boundary — exactly the bookkeeping an EOS exit does."""
    now = time.monotonic()
    self._reap_queue(now)
    freed = []
    for slot in range(self.num_slots):
      req = self._slots[slot]
      if req is None:
        continue
      if not (req.cancelled.is_set() or req.expired(now)):
        continue
      self._fail_reaped(req, now)
      with self._lock:
        self._slots[slot] = None
      self._last[slot] = self.pad_id
      if self.decoder.paged:
        self._release_pages(req.rid)
        freed.append(slot)
    self._reset_freed(freed)

  def _reset_freed(self, freed: List[int]) -> None:
    """Point freed slots' page tables at the trash page BEFORE the next
    decode dispatch: a freed lane keeps computing (frozen), and its
    stale table would otherwise scribble into pages the allocator may
    already have handed to a new request."""
    if not freed:
      return
    mask = np.zeros((self.num_slots,), bool)
    mask[freed] = True
    self._on_slab(lambda slabs: self.decoder.reset_slots(slabs, mask))

  def _reap_queue(self, now: float) -> None:
    for req in self._queue.reap(
        lambda r: r.cancelled.is_set() or r.expired(now)):
      self._fail_reaped(req, now)

  def _fail_reaped(self, req: sched.Request, now: float) -> None:
    if req.cancelled.is_set():
      self._count("cancelled")
      req.finish(sched.RequestCancelled(
          "request %d cancelled" % req.rid))
    else:
      self._count("expired")
      req.finish(sched.DeadlineExceeded(
          "request %d missed its deadline by %.3fs"
          % (req.rid, now - (req.deadline or now))))

  # -- admission -------------------------------------------------------------

  def _alloc_pages(self, req: sched.Request):
    """Page in one request: ``(all pages in token order, shared prefix
    pages, shared token count)``, or None when the pool cannot host it
    right now (the caller requeues; completions free pages).

    Prefix-cache hits fork read-only references to the prefix's FULL
    pages (pinned before any eviction can free them); the divergence
    page and the tail/budget pages are fresh private allocations. A full
    pool shrinks the prefix cache LRU-first before giving up.
    """
    plen = len(req.prompt)
    shared_pages, shared_tokens = [], 0
    if self._prefix is not None:
      hit = self._prefix.lookup(req.prompt)
      # always leave >= 1 tail token: the last prompt token must run
      # through the model to yield g1, and the divergence page is never
      # shared (the copy-on-write boundary)
      usable = min(len(hit), (plen - 1) // self.page_size)
      shared_pages = hit[:usable]
      shared_tokens = usable * self.page_size
      for p in shared_pages:         # pin BEFORE eviction can free them
        self._pool.ref(p)
    need = -(-(plen + req.max_new_tokens) // self.page_size) \
        - len(shared_pages)
    fresh = self._pool.alloc(need)
    while fresh is None and self._prefix is not None \
        and self._prefix.pages_held > 0:
      # evict the whole deficit in one batched trie walk; STOP once a
      # round frees nothing (every evicted page still ref'd by live
      # readers) — grinding the trie to empty would destroy all prefix
      # sharing without ever satisfying this allocation
      if self._evict_prefix(need - self._pool.free_pages) == 0:
        break
      fresh = self._pool.alloc(need)
    if fresh is None:
      for p in shared_pages:
        self._pool.unref(p)
      return None
    return shared_pages + fresh, shared_pages, shared_tokens

  def _evict_prefix(self, n: int) -> int:
    """Evict up to ``n`` LRU prefix pages; returns how many actually
    came FREE (a page still ref'd by live readers leaves the cache but
    stays allocated until its last ref drops)."""
    freed = 0
    for p in self._prefix.evict(max(1, n)):
      self._count("prefix_evictions")
      freed += bool(self._pool.unref(p))
    return freed

  def _release_pages(self, rid: int) -> None:
    """Drop the request's page refs EXACTLY once (pop-then-unref: a
    second call for the same rid is a no-op, so reap/complete/drain
    paths cannot double-free; pages shared with the prefix cache or
    other readers stay allocated until their last ref drops)."""
    pages = self._req_pages.pop(rid, None)
    if pages:
      for p in pages:
        self._pool.unref(p)

  def _begin_admission(self, slot: int) -> Optional["_Admission"]:
    """Pop the next live request for lane ``slot`` and page it in. ``None``
    when the queue is empty, or the pool cannot host the request now (it is
    back at the head of the queue: the next completion frees pages)."""
    req = None
    while req is None:
      # on_pop marks the request mid-admission ATOMICALLY with the
      # pop (under the queue lock): crash-safe for _recover, and
      # drain's idle check can never observe the in-neither gap
      req = self._queue.pop_nowait(on_pop=self._mark_admitting)
      if req is None:
        return None
      now = time.monotonic()
      if req.cancelled.is_set() or req.expired(now):
        # the admission-time deadline check: fail WITHOUT a slot
        self._fail_reaped(req, now)
        self._admission_over()
        req = None
    adm = _Admission(req, slot)
    if self.decoder.paged:
      alloc = self._alloc_pages(req)
      if alloc is None:
        # pool exhausted: requeue AHEAD of the backlog (it was already
        # admitted; bounds don't re-apply) and stop admitting — the
        # next completion frees pages and admission resumes
        self._queue.push_front(req)
        self._admission_over()
        return None
      adm.pages, _, adm.shared_tokens = alloc
      adm.table = adm.pages + [0] * (self.decoder.pages_per_slot
                                     - len(adm.pages))
    if req.started_at is None:
      req.started_at = time.monotonic()
      if self._rec is not None:
        # the queue-wait phase of the waterfall: submit → admitted.
        # Recorded once, at FIRST admission (a crash-replay
        # re-admission is not a second client-visible queue wait)
        self._rec.record_span("serve.queue", req.submitted_at,
                              req.started_at - req.submitted_at,
                              trace=req.trace_id, rid=req.rid)
    return adm

  def _prefill_span(self, adm: "_Admission"):
    req = adm.req
    return obs_spans.region("serve.prefill", self.stats, "t_prefill_s",
                            trace=req.trace_id, queue=self._devq,
                            record=self._rec is not None, rid=req.rid,
                            prompt_len=len(req.prompt), slot=adm.slot,
                            shared_tokens=adm.shared_tokens)

  def _dispatch_chunks(self, adm: "_Admission", behind: bool) -> None:
    """Dispatch the admission's prefill (inside its ``serve.prefill``
    region) and read nothing. ``behind``: a decode step is dispatched and
    unread, so the chunks queue behind it (counted)."""
    req, resume = adm.req, None
    chunks = self.stats["prefill_chunks"]
    if adm.shared_tokens:
      # prefix hit: rebuild the warm row cache from the shared pages
      # and prefill only the tail — the O(prefix) work is skipped
      self._count("prefix_hits")
      row = self.decoder.gather_pages(self._slabs, adm.table,
                                      adm.shared_tokens)
      self._devq.dispatched()
      resume = (row, adm.shared_tokens)
    adm.row, adm.head, adm.seq = self.decoder.prefill_chunks(
        self.params, req.prompt, self.buckets, resume=resume,
        trace=self._chunk_trace(req), acc=self.stats, queue=self._devq)
    if behind:
      self.stats["prefill_chunks_behind_decode"] += \
          self.stats["prefill_chunks"] - chunks

  def _chunk_trace(self, req: sched.Request) -> Optional[str]:
    return req.trace_id if self._detail else None

  def _read_first(self, adm: "_Admission") -> int:
    return self.decoder.prefill_first(
        adm.head, adm.seq, trace=self._chunk_trace(adm.req), acc=self.stats,
        queue=self._devq)

  def _insert(self, adm: "_Admission") -> None:
    """Dispatch the row's insert into its lane. It needs the row and not
    the first token, so it may be queued behind the chunks unread."""
    with self._phase("serve.insert", "t_insert_s"):
      if self.decoder.paged:
        self._on_slab(lambda slabs: self.decoder.insert_pages(
            slabs, adm.row, adm.slot, adm.table, start=adm.shared_tokens))
      else:
        self._on_slab(lambda slabs: self.decoder.insert(
            slabs, adm.row, adm.slot))
    # the insert holds the row until it has run: an admission left unread
    # keeps its first token and no 0.3-0.5 GB row beside the next one's
    adm.row, adm.inserted = None, True

  def _seat(self, adm: "_Admission", first: int) -> None:
    """The admission's first token is read: emit it and hand the lane to
    the request (a request that ends at its first token leaves the lane
    free; a row already inserted for it is read by nobody, as a finished
    lane's is)."""
    req, slot = adm.req, adm.slot
    if req.prefill_done_at is None:     # replays keep the original stamp
      req.prefill_done_at = time.monotonic()
    self.stats["prefills"] += 1
    if self._obs_m is not None:
      self._obs_m["prefills"].inc()
    if not req.emit(first):
      self.stats["replay_mismatches"] += 1
    self.stats["emitted_tokens"] += 1
    if self._finished(req, first):
      self._complete(req)
      if adm.pages is not None:  # never inserted: nothing else holds them
        for p in adm.pages:
          self._pool.unref(p)
      self._admission_over(adm)
      return                     # the lane stays free for the next request
    if self._slots[slot] is not None:
      raise RuntimeError(
          "lane %d was taken ahead for request %d, but the dispatch it was "
          "queued behind did not free it" % (slot, req.rid))
    if not adm.inserted:
      self._insert(adm)
    if self.decoder.paged:
      if self._prefix is not None:
        # the prompt's full pages become shareable: the cache takes
        # its own ref on each newly cached page, outliving this
        # request; then the LRU budget is enforced
        for p in self._prefix.register(req.prompt, adm.pages):
          self._pool.ref(p)
        over = self._prefix.over_budget
        if over:
          self._evict_prefix(over)
      self._req_pages[req.rid] = adm.pages
    with self._lock:
      self._slots[slot] = req
    self._admission_over(adm)
    self._last[slot] = first

  def _admit(self, seat: Optional["_Admission"] = None) -> None:
    """Read the first token of ``seat`` (an admission :meth:`_admit_behind`
    left unread behind a step that has been harvested by now, so its lane
    is free: :meth:`_decode_once` says which) and seat it; then prefill queued
    requests into the lanes still free (EOS-freed or virgin) and not
    spoken for, one at a time: dispatch, read, insert. With a step in
    flight their chunks queue behind it, not into a drained device."""
    # with no step in flight there is nothing to run ahead of: every
    # admission left unread is seated before the next step, and is in it
    due = list(self._unread) if self._flight is None \
        else [seat] if seat else []
    for adm in due:
      self._blame = adm.req      # from here on a fault is the admission's
      self._seat(adm, self._read_first(adm))
    for slot in range(self.num_slots):
      if self._slots[slot] is not None or self._spoken_for(slot):
        continue
      adm = self._begin_admission(slot)
      if adm is None:
        return
      with self._prefill_span(adm):
        self._dispatch_chunks(adm, behind=self._flight is not None)
        first = self._read_first(adm)
      self._seat(adm, first)

  def _admit_behind(self, remaining, certain: int) -> None:
    """Queue ONE admission behind the decode dispatch that was just made
    and read nothing: the host's chunk and insert calls pass while the
    device runs the step, and on the in-order device they run after it.

    The lane is one that is free, or one the dispatch is CERTAIN to leave
    free: its request's budget (``remaining``, as the dispatch was GIVEN
    it: with a step in flight unread the host counts what that step spends
    at most, :meth:`_lanes_given`) ends within ``certain`` tokens, the
    least the dispatch emits for a live lane, whatever EOS does. An
    inactive lane of the step writes only where the next insert overwrites
    (``SlotDecoder._one_step``), so the row may be inserted behind the
    step before the old request has been harvested; ``_slots`` keeps the
    old request until the harvest frees the lane, and :meth:`_admit` seats
    the new one after it. The paged pool releases pages and resets page
    tables in the harvest, so it takes a free lane only and inserts after
    its read. The admission is left in ``_unread``: it is the only one
    there once the older one, dispatched a step earlier, has been seated
    in this same pass."""
    with obs_spans.region("serve.admit", self.stats, "t_admit_s",
                          record=False, queue=self._devq):
      slot = next((i for i, r in enumerate(self._slots)
                   if r is None and not self._spoken_for(i)), None)
      ahead = slot is None and not self.decoder.paged
      if ahead:
        slot = next((i for i in range(self.num_slots)
                     if remaining[i] <= certain
                     and not self._spoken_for(i)), None)
      adm = None if slot is None else self._begin_admission(slot)
      if adm is None:
        return
      with self._prefill_span(adm):
        self._dispatch_chunks(adm, behind=True)
      self.stats["admits_ahead"] += ahead
      if not self.decoder.paged:
        self._insert(adm)
      self._unread.append(adm)   # unread first, so it is never in neither
      self._admitting = None
      self._blame = None         # a fault in the step's read is nobody's

  def _spoken_for(self, slot: int) -> bool:
    """Whether an admission left unread is on its way into lane ``slot``."""
    return any(a.slot == slot for a in self._unread)

  def _phase(self, name: str, key: str):
    """A per-dispatch phase of the loop thread: counter and annotation
    always, recorder span only with ``TOS_OBS_TRACE_DETAIL``."""
    return obs_spans.region(name, self.stats, key, record=self._detail,
                            queue=self._devq)

  def _mark_admitting(self, req: sched.Request) -> None:
    self._admitting = self._blame = req

  def _admission_over(self, adm: Optional["_Admission"] = None) -> None:
    """``adm`` is seated or finished (``None``: the request just popped
    never got as far as an admission)."""
    if adm is not None and adm in self._unread:
      self._unread.remove(adm)
    else:
      self._admitting = None
    self._blame = None

  def _finished(self, req: sched.Request, token: int) -> bool:
    if self.eos_id is not None and int(token) == self.eos_id:
      return True
    return req.generated >= req.max_new_tokens

  def _complete(self, req: sched.Request) -> None:
    self.stats["completed"] += 1
    if self._obs_m is not None:
      self._obs_m["completed"].inc()
    req.finish(None)
    if self._obs_q is not None:
      # the request's timing ledger feeds the mergeable latency
      # sketches — the SLO plane's per-engine TTFT/TPOT/e2e/queue-wait
      # objects (completed requests only: a rejected request has no
      # latency, it has an availability verdict)
      q = self._obs_q
      if req.ttft is not None:
        q["ttft_ms"].observe(req.ttft * 1e3)
      if req.tpot is not None:
        q["tpot_ms"].observe(req.tpot * 1e3)
      if req.latency is not None:
        q["e2e_ms"].observe(req.latency * 1e3)
      if req.queue_wait is not None:
        q["queue_wait_ms"].observe(req.queue_wait * 1e3)

  def _decode_once(self) -> Optional[_Admission]:
    """One fused ``horizon``-step dispatch and one host-side harvest, with
    one admission queued behind the dispatch where a lane allows it.
    Returns the admission that was waiting unread when the pass began, for
    :meth:`_admit` to read and seat (with no step left in flight it seats
    every unread one).

    Where a step may run ahead (``_run_ahead``: the plain step over the
    contiguous slab) the harvest is of the step dispatched a pass EARLIER:
    the new step is dispatched before that one is read, its lane state made
    on the device (:meth:`_carried`), so the copy back, the harvest and the
    next dispatch call all pass while the device runs. The first step of a
    run is dispatched from the host's arrays and left unread; a pass in
    which no lane can still be live dispatches nothing and reads the last
    one. The admission that was waiting is due then: its insert preceded
    the new step and its lane went live in it. Elsewhere (the paged pool,
    speculation) the step is read in the pass that dispatched it, nothing
    stays in flight, and the admission just queued is due.

    The device scan carries each lane's EOS/budget done-mask; the host
    replays the identical stop rule over the returned ``[horizon,
    num_slots]`` token matrix, so the two views cannot diverge. A lane
    that stops mid-horizon idles (frozen) for the remaining scan steps —
    the bounded price of amortizing dispatch over the horizon."""
    tokens_before = self.stats["emitted_tokens"]
    with obs_spans.region("serve.decode", record=self._rec is not None,
                          horizon=self.horizon) as dec:
      with self._phase("serve.decode.prep", "t_decode_prep_s"):
        waiting = self._unread[0] if self._unread else None
        reqs, remaining = self._lanes_given(waiting)
        active = remaining > 0
        dec.attrs["active"] = int(active.sum())
      if self.spec_depth > 0:
        steps, lanes = self._decode_spec(active, remaining)
      else:
        steps, lanes = self._decode_plain(reqs, active, remaining, waiting)
      # the recorder's span is one a DISPATCH: a pass that only read the
      # last step of a run leaves its phases and its lanes' spans
      dec.recorded = dec.recorded and bool(active.any())
    t0, dt = dec.t0, dec.dur         # the one clock reading of the pass
    emitted = self.stats["emitted_tokens"] - tokens_before
    if dt > 0 and emitted:
      # live tokens/s EMA — the denominator of the retry-after hint
      rate = emitted / dt
      self._tok_rate = rate if self._tok_rate <= 0 \
          else 0.5 * self._tok_rate + 0.5 * rate
    # slot-attributed decode horizons: one span per lane that decoded in
    # this dispatch, on the serve.decode region's clock, carrying the
    # request's trace and its per-lane emitted count (from the harvest of
    # step_many's [horizon, slots] token matrix) — the decode phase of
    # the per-request waterfall (obs_report --request). TRACE_DETAIL
    # gated (lanes is empty without it): the one span family that scales
    # with slots × dispatches
    for slot, trace, emitted_lane in lanes:
      self._rec.record_span("serve.decode.slot", t0, dt, trace=trace,
                            slot=slot, tokens=emitted_lane)
    m = self._obs_m
    if m is not None:
      m["steps"].inc(steps)
      m["tokens"].inc(emitted)
      m["decode_ms"].observe(dt * 1e3)
      m["occupancy"].set(self.occupancy)
      m["queue_depth"].set(len(self._queue))
      m["slots_active"].set(sum(1 for r in self._slots if r is not None))
      if self._pool is not None:
        m["kv_pages_in_use"].set(self._pool.in_use)
        m["kv_pages_free"].set(self._pool.free_pages)
    return waiting

  def _lanes_given(self, waiting: Optional[_Admission]):
    """What the step about to be dispatched is GIVEN, as far as the host
    knows: ``(reqs, remaining)``, lane by lane the request that is live in
    it (``None``: the lane is off) and its unspent budget.

    With a step in flight unread, a lane that is live in that step has, at
    most, what it had less ``min(horizon, remaining)``: exact without an
    EOS id, an upper bound with one (the device's own carried mask is what
    the new step runs on; the host's count only certifies a lane free,
    :meth:`_admit_behind`, and says whether any lane can still be live).
    ``waiting``, an admission unread behind that step, goes live with its
    budget less the first token it has not emitted yet."""
    flight = self._flight
    reqs: List[Optional[sched.Request]] = [None] * self.num_slots
    remaining = np.zeros((self.num_slots,), np.int32)
    for slot, req in enumerate(self._slots):
      if req is None:
        continue
      left = req.max_new_tokens - req.generated
      if flight is not None and flight.reqs[slot] is req:
        left -= self.horizon
      if left > 0:
        reqs[slot], remaining[slot] = req, left
    if waiting is not None:
      left = waiting.req.max_new_tokens - waiting.req.generated - 1
      if left > 0:
        reqs[waiting.slot], remaining[waiting.slot] = waiting.req, left
    return reqs, remaining

  def _carried(self, flight: _Step, reqs, remaining,
               waiting: Optional[_Admission]):
    """The lane state of the next step, made on the device from the
    outputs of ``flight``, the step before it, unread: ``(last tokens,
    active, remaining)`` as ``SlotDecoder.step_many`` takes them.

    A lane whose request is the one ``flight`` was given stays the
    device's. What the host knows and the device does not goes in as the
    lane's override: a lane seated, cancelled, reaped or reset since that
    dispatch (its request is another, or none). ``waiting``'s lane takes
    its first token from the prefill's own output, still on the device."""
    whose = np.asarray([slots_lib.LANE_DEVICE if mine is was
                        else slots_lib.LANE_HOST
                        for mine, was in zip(self._slots, flight.reqs)],
                       np.int32)
    first = None
    if waiting is not None and reqs[waiting.slot] is waiting.req:
      whose[waiting.slot], first = slots_lib.LANE_FIRST, waiting.head
    lane = self.decoder.merge_lanes(
        flight.out[1], flight.out[2], flight.out[3],
        np.stack([whose, self._last, remaining]), first)
    self._devq.dispatched()
    return lane

  def _harvest(self, req, tok: int, slot: int, freed: List[int]) -> bool:
    """Record one emitted token; on the request's stop, free its slot
    (and pages) exactly like EOS. Returns True when the slot freed."""
    if not req.emit(tok):
      self.stats["replay_mismatches"] += 1
    self.stats["emitted_tokens"] += 1
    self.stats["live_slot_steps"] += 1
    if not self._finished(req, tok):
      return False
    self._complete(req)
    with self._lock:
      self._slots[slot] = None
    self._last[slot] = self.pad_id
    if self.decoder.paged:
      self._release_pages(req.rid)
      freed.append(slot)
    return True

  def _decode_plain(self, reqs, active, remaining,
                    waiting: Optional[_Admission]):
    """The non-speculative fused horizon (SlotDecoder.step_many).
    Returns ``(steps, lanes)`` — ``lanes`` is the slot-attributed
    ``(slot, trace_id, emitted)`` list for the per-request decode spans,
    built only while the recorder is live (zero work otherwise). The three
    phases of a step are regions: the dispatch call returning, the wait
    for the token matrix, and the host's harvest of it
    (:meth:`_read_step`) — with a step in flight, of the OLDER step."""
    flight, step = self._flight, None
    if active.any():
      step = self._dispatch_plain(flight, reqs, active, remaining, waiting)
      # a budget that ends within the horizon ends inside the scan
      self._admit_behind(remaining, self.horizon)
    elif flight is not None:
      # no lane can be live after the step in flight: nothing to dispatch,
      # but a lane that step is certain to leave free can be filled behind it
      self._admit_behind(remaining, 0)
    if not self._run_ahead:
      return self._read_step(step)
    self._flight = step
    return (0, []) if flight is None else self._read_step(flight)

  def _dispatch_plain(self, flight: Optional[_Step], reqs, active,
                      remaining, waiting: Optional[_Admission]) -> _Step:
    """Dispatch one ``step_many`` (the ``serve.decode.dispatch`` region):
    from the host's arrays where no step is in flight, as it always was;
    ahead of the unread ``flight`` from that step's outputs."""
    with self._phase("serve.decode.dispatch", "t_decode_dispatch_s"):
      lane = (self._last, active, remaining) if flight is None \
          else self._carried(flight, reqs, remaining, waiting)
      out = self._on_slab(lambda slabs: self.decoder.step_many(
          self.params, slabs, *lane, self.horizon))
      self.stats["decode_dispatches"] += 1
      self.stats["decode_dispatches_ahead"] += flight is not None
      writes, dma = self.decoder.cursor_writes[self.horizon]
      self.stats["cursor_leaf_writes"] += writes
      self.stats["cursor_leaf_writes_dma"] += dma
      reads, ragged, ring = self.decoder.attn_reads[self.horizon]
      self.stats["decode_attn_reads"] += reads
      self.stats["decode_attn_reads_ragged"] += ragged
      self.stats["decode_attn_reads_ring"] += ring
      sparse = self.decoder.sparse_reads[self.horizon]
      self.stats["decode_attn_reads_sparse"] += sparse
      self.stats["index_rows_read"] += sparse * self.decoder.num_slots \
          * self.cfg.max_seq_len
      products, kernel = self.decoder.expert_products["step", self.horizon]
      self.stats["expert_products"] += products
      self.stats["expert_products_kernel"] += kernel
      selections, kernel = self.decoder.index_selections[
          "step", self.horizon]
      self.stats["index_selections"] += selections
      self.stats["index_selections_kernel"] += kernel
    return _Step(out, self._slab_seq, reqs)

  def _read_step(self, step: _Step):
    """Wait for ``step``'s token matrix and harvest it: ``(steps,
    lanes)``. A lane is harvested for the request the step was GIVEN live,
    and only while that request still holds the lane: the tokens of one
    cancelled, reaped or ended at its first token since the dispatch are
    discarded, and a request seated since was not in the step."""
    with self._phase("serve.decode.fetch", "t_decode_fetch_s"):
      toks = np.asarray(step.out[1])              # [horizon, num_slots]
      # the step's read returned: the rest of the region is empty time,
      # unless a newer program (an admission's, the next step) is queued
      # behind it: the STEP's own number says which
      self._devq.drained(step.seq)
      if self.decoder.counted:       # the step's own sums, beside the tokens
        for name, value in step.out[4].items():
          self.stats[_STEP_COUNTERS[name]] += int(np.asarray(value))
    lanes: List[tuple] = []
    freed: List[int] = []
    # ONE region round the whole harvest: _harvest runs per token
    with self._phase("serve.decode.harvest", "t_decode_harvest_s"):
      self.stats["steps"] += self.horizon
      for slot, req in enumerate(step.reqs):
        if req is None or self._slots[slot] is not req:
          continue
        emitted = 0
        for j in range(self.horizon):
          emitted += 1
          if self._harvest(req, int(toks[j, slot]), slot, freed):
            break
        else:
          self._last[slot] = int(toks[self.horizon - 1, slot])
        if self._detail:
          lanes.append((slot, req.trace_id, emitted))
      self._reset_freed(freed)
    return self.horizon, lanes

  def _decode_spec(self, active, remaining):
    """The self-speculative fused dispatch (SlotDecoder.step_spec).

    ``counts[r, lane]`` bounds each lane's valid tokens per round (the
    device's accept/EOS/budget verdict); the host still replays the
    stop rule per token (the step_many contract), so the two views
    cannot diverge. Accepted/rejected draft verdicts feed the
    ``spec_accepted``/``spec_rejected`` counters. Returns ``(steps,
    lanes)`` like :meth:`_decode_plain`.
    """
    k, rounds = self.spec_depth, self._spec_rounds
    with self._phase("serve.decode.dispatch", "t_decode_dispatch_s"):
      _, toks, counts, acc, rej, _, _ = self._on_slab(
          lambda slabs: self.decoder.step_spec(
              self.params, slabs, self._last, active, remaining, rounds))
      self.stats["decode_dispatches"] += 1
    step_seq = self._slab_seq
    # every round emits at least one token a live lane: a budget of at
    # most ``rounds`` ends inside the dispatch
    self._admit_behind(remaining, rounds)
    with self._phase("serve.decode.fetch", "t_decode_fetch_s"):
      toks = np.asarray(toks)          # [rounds, spec_depth, num_slots]
      # the step's read returned: the rest of the region is empty time,
      # unless the admission's programs are queued behind the step
      self._devq.drained(step_seq)
      counts = np.asarray(counts)      # [rounds, num_slots]
      n_acc, n_rej = int(np.asarray(acc).sum()), int(np.asarray(rej).sum())
    lanes: List[tuple] = []
    freed: List[int] = []
    with self._phase("serve.decode.harvest", "t_decode_harvest_s"):
      # a round's slot-step opportunity is its verify window (k wide) —
      # occupancy then reads as useful-token fraction incl. rejections
      self.stats["steps"] += rounds * k
      self._count("spec_accepted", n_acc)
      self._count("spec_rejected", n_rej)
      for slot in range(self.num_slots):
        req = self._slots[slot]
        if req is None:
          continue
        done = False
        emitted = 0
        last_tok = None
        for r in range(rounds):
          for j in range(int(counts[r, slot])):
            last_tok = int(toks[r, j, slot])
            emitted += 1
            if self._harvest(req, last_tok, slot, freed):
              done = True
              break
          if done:
            break
        if not done and last_tok is not None:
          self._last[slot] = last_tok
        if self._detail:
          lanes.append((slot, req.trace_id, emitted))
      self._reset_freed(freed)
    return rounds * k, lanes
