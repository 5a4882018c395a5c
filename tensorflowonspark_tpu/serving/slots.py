"""Device side of continuous batching: slot slabs and their jitted ops.

The slab is ONE persistent KV-cache pytree with a fixed slot capacity.
Two layouts exist:

* CONTIGUOUS (default): per layer ``[num_slots, max_seq_len, kv_heads *
  head_dim]`` key/value buffers (heads folded into the minor axis: the
  layout decode attention computes on, lane-dense at every head_dim) plus
  a VECTOR cursor ``index: [num_slots]`` (the per-slot-cursor branch of
  ``models.transformer.Attention._decode_attend``). Every slot reserves
  ``max_seq_len`` of HBM whether it needs it or not.
* PAGED (``page_size > 0``): per layer a page POOL ``[num_pages,
  page_size, kv_heads, head_dim]`` plus a per-slot ``page_table
  [num_slots, pages_per_slot] int32`` and the vector cursor — a slot
  holds only the pages its token mass needs, so slot count scales with
  actual tokens instead of ``num_slots × max_seq_len`` worst case
  (``_decode_attend_paged``). Page 0 is the reserved TRASH page; the
  host-side allocator is ``serving.scheduler.PagePool``. Paging also
  unlocks the shared-prefix cache (``serving.scheduler.PrefixCache``):
  requests sharing a prompt prefix fork read-only references to the
  prefix's full pages and prefill only their tail.

Jitted functions owning the slab:

* :meth:`SlotDecoder.prefill` — run one request's prompt through the
  model on a fresh single-row cache, in bucket-shaped chunks so the jit
  cache holds at most ``len(buckets)`` prefill shapes (six for rows up to
  4096 positions, one more each time the row doubles past that:
  :func:`row_buckets`). A prompt is ONE program where it can be: whole
  chunks of the largest bucket, then the tail PADDED with ``pad_id`` up to
  the smallest bucket that holds it (:func:`padded_plan`), so a prompt is
  eight chunks at most in a power-of-two row; the program takes the tail's
  true length, leaves the cursor there and reads the first token off the
  last REAL row. Padding needs no other mask: a padded query sits after every
  real one, so under the causal mask no real position reads it, and its
  K/V land past the cursor, where decode masks them and overwrites them
  one by one before it attends them (``_set_cache_cursor``'s free
  rollback). A RECURRENT layer's cache (``cfg.recurrent_state``: a KDA
  state and convolution tail) has no position axis for the cursor to
  mask, so the layer itself takes the true length: a padded token neither
  decays nor writes and the tail is taken where the real tokens end
  (``models/kda.py``), and such a model pads like every other. The first
  chunk is a fresh-cache prefill (flash-eligible on TPU); later chunks
  ride the warm-cache ``idx > 0`` dense branch of the same cond.
* :meth:`SlotDecoder.insert` — scatter that row cache into the slab at a
  freed slot (``lax.dynamic_update_slice`` on every leaf) and set the
  slot's cursor to the prompt length.
* :meth:`SlotDecoder.step` — advance ALL live slots one token in one
  fixed-shape call: each slot writes at its own cursor, attends its own
  length, and inactive slots are frozen (their cursor write is undone,
  their emitted token forced to ``pad_id``) so freed capacity costs
  nothing but the lane's arithmetic.
* :meth:`SlotDecoder.step_many` — ``horizon`` of those steps fused into
  one jitted scan that carries the per-slot done-mask (EOS hit / budget
  spent) ON DEVICE: dispatch + host-sync overhead is paid once per
  ``horizon`` tokens instead of per token, at the cost of at most
  ``horizon - 1`` frozen slot-steps per completion (the same
  done-mask mechanics as ``greedy_generate_kv(eos_id=...)``, so the
  emitted stream stays bit-identical).
* :meth:`SlotDecoder.merge_lanes` — the lane state ``step_many`` takes
  (last token, active, remaining), made ON THE DEVICE from the outputs of
  the ``step_many`` before it, with what the host knows and the device does
  not laid over it lane by lane: one tiny program, so that the next step can
  be dispatched before the last one's tokens are read and ``step_many``'s
  own program stays what it was.
* :meth:`SlotDecoder.step_spec` — SELF-SPECULATIVE decode
  (``spec_depth > 0``): each fused round drafts ``spec_depth`` tokens
  with a shallow-exit prefix of the model's own layers
  (``Transformer(..., exit_layer=spec_layers)`` — shared params, shared
  slab), rolls the draft layers' cursors back, verifies the whole
  window with ONE full-model multi-token step, and accepts the longest
  per-lane prefix the target agrees with plus the target's own
  correction token. Greedy verification accepts exactly the tokens
  ``greedy_generate_kv`` would emit, so the bit-identical-decode
  contract (crash replay, parity tests) survives the speedup; rejected
  draft entries sit past the rewound per-lane cursor, masked and
  overwritten (the ``_set_cache_cursor`` rollback trick, vectorized).

Everything here is functional — the ``serving.engine.ServingEngine``
thread owns the slab value and the host-side bookkeeping (which slots
are live, per-request budgets/EOS, the page allocator / prefix trie).

The slab is ONE buffer for the life of its owner: every program that
returns a slab takes its slab argument DONATED and updates it in place, so
the value a caller passed in is deleted by the call — rebind the result
(``slabs = dec.insert(slabs, ...)``) and never read the old one again.
Row caches (``prefill``'s result, ``gather_pages``') are not donated.
"""

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.tree_util import tree_map_with_path

from tensorflowonspark_tpu.models import transformer as tfm
from tensorflowonspark_tpu.obs import device as obs_device
from tensorflowonspark_tpu.obs import spans as obs_spans
from tensorflowonspark_tpu.utils import chaos

#: chunk SHAPES of the bucketed prefill: the compiled prefill cache holds
#: at most one program a shape, so arbitrary prompt lengths never grow the
#: jit cache. These are the shapes a padded tail may take
#: (:func:`padded_plan`). Why these six (chip runs at gpt2-large's widths,
#: PERF.md section 6, PR 27): a dispatch costs the host 6.5 ms and the
#: device 3.3 ms at 16 tokens, 8.4 at 512, whatever is real in it, so a
#: prompt wants ONE chunk and nothing under 16; powers of two keep the
#: padding under half (30% of the benchmark's mix, 12.8 ms a prompt; the
#: four shapes 512/128/32/16: 49%, 13.9 ms); each shape is one more
#: 36-layer program to compile (11-20 s) or load at every start. This is the
#: BASE ladder: a long row's grows upward from it (:func:`row_buckets`).
DEFAULT_BUCKETS = (512, 256, 128, 64, 32, 16)

#: how much of a row the padded plan's largest chunk may be: the ladder is
#: doubled upward from 512 while a shape is at most ``max_seq_len //
#: ROW_CHUNK_DIVISOR``. A chunk of ANY size is one pass over the weights, so a
#: prompt is then never more than eight chunks however long the row (its
#: length a power of two: a 12288-token prompt of a 16384-long row is 6
#: programs of 2048 tokens and not 24 of 512), while each further shape is
#: one more program to trace and to compile or load at a start (1.7 s each,
#: warm), which only a long row repays: rows up to 4096 keep the six shapes
#: to the letter. Why 8 (chip runs at Trinity-Large-Preview's widths, 8.64 GB
#: of weights in 5 layers, rows of 16384; PERF.md section 6, PR 35): one
#: chunk at a cursor of 8192 takes the device 29.4 ms at 512 tokens, 39.6 at
#: 1024 and 61.6 at 2048 (57, 39 and 30 us a token); the serving cell
#: prefills 21,485 prompt tokens/s under a ladder that tops out at 512,
#: 29,405 at 1024 (a divisor of 16) and 37,220 at 2048.
ROW_CHUNK_DIVISOR = 8

#: chunk sizes of the EXACT decomposition (:func:`chunk_plan`: no padding,
#: what every model ran before the padded plan and the equality tests still
#: steer a decoder to); 1 must be reachable so every length decomposes.
EXACT_BUCKETS = (512, 128, 32, 16, 8, 4, 2, 1)


#: whose word a lane's state is in :meth:`SlotDecoder.merge_lanes`: the
#: device's own (what the step before carried out), the host's (a lane it
#: seated, reaped or reset since that step was dispatched), or an admission's
#: whose first token is the prefill's output, still unread
LANE_DEVICE, LANE_HOST, LANE_FIRST = 0, 1, 2


def row_buckets(max_seq_len: int):
  """The padded plan's chunk shapes for a row of ``max_seq_len`` positions:
  :data:`DEFAULT_BUCKETS`, extended upward by doubling while the shape is at
  most ``max_seq_len // ROW_CHUNK_DIVISOR``. ``row_buckets(4096)`` is
  ``DEFAULT_BUCKETS``; ``row_buckets(16384)`` adds 1024 and 2048 above
  it."""
  buckets, top = DEFAULT_BUCKETS, 2 * max(DEFAULT_BUCKETS)
  while top <= max_seq_len // ROW_CHUNK_DIVISOR:
    buckets, top = (top,) + buckets, 2 * top
  return buckets


def chunk_plan(plen: int, buckets: Sequence[int] = EXACT_BUCKETS):
  """Decompose a prompt length into descending bucket-sized chunks.

  Greedy largest-first: ``chunk_plan(37, (128, 32, 8, 4, 2, 1))`` →
  ``[32, 4, 1]``. A trailing 1 is appended to the bucket set if missing
  so every positive length has a plan.
  """
  if plen < 1:
    raise ValueError("prompt length must be >= 1, got %d" % plen)
  sizes = sorted({int(b) for b in buckets if int(b) > 0}, reverse=True)
  if not sizes or sizes[-1] != 1:
    sizes.append(1)
  plan, rem = [], plen
  for b in sizes:
    while rem >= b:
      plan.append(b)
      rem -= b
  return plan


def padded_plan(n: int, room: int, buckets: Sequence[int] = DEFAULT_BUCKETS):
  """The chunks ``n`` prompt tokens run as when the tail may be padded:
  ``[(shape, valid), ...]``, ``valid`` real tokens in a chunk of
  ``shape``.

  Whole chunks of the largest bucket while more than one is left, then
  ONE chunk: the remainder in the smallest bucket that holds it.
  ``padded_plan(521, 1024)`` → ``[(512, 512), (16, 9)]``; at most 512
  tokens are one program. ``room`` is what the row has left from the
  first token's position (``max_seq_len - offset``): a padded chunk must
  END inside the row, because ``lax.dynamic_update_slice`` clamps its
  start and would silently overwrite live entries below the cursor.
  Where no bucket both holds the remainder and fits, the largest bucket
  under the remainder goes exactly (1 if there is none) and the rest is
  tried again. A function of ``n`` and ``room`` alone, so warming one
  prompt of each length compiles every shape.
  """
  if n < 1:
    raise ValueError("prompt length must be >= 1, got %d" % n)
  sizes = sorted({int(b) for b in buckets if int(b) > 0})
  plan = []
  while n:
    fit = next((b for b in sizes if n <= b <= room), None)
    if fit is not None:
      plan.append((fit, n))
      break
    b = max((b for b in sizes if b <= n), default=1)
    plan.append((b, b))
    n, room = n - b, room - b
  return plan


def _is_index(path) -> bool:
  return bool(path) and getattr(path[-1], "key", None) == "index"


def _cursor_leaf(slabs):
  """The slab's per-slot cursor vector (the first ``index`` leaf — every
  layer carries the same value in steady state)."""
  from jax.tree_util import tree_flatten_with_path
  for path, leaf in tree_flatten_with_path(slabs)[0]:
    if _is_index(path):
      return leaf
  raise ValueError("slab pytree has no 'index' leaf")


def _sown(counters, name: str) -> list:
  """The arrays the expert layers ``sow``ed under ``name``, in layer order."""
  from jax.tree_util import tree_flatten_with_path
  return [leaf for path, leaf in tree_flatten_with_path(counters)[0]
          if any(getattr(k, "key", None) == name for k in path)]


def _with_cursor(slabs, vec):
  """Every layer's cursor set to ``vec`` (vectorized rollback: rejected
  speculative entries sit past the cursor, masked and overwritten — the
  same free-rollback property as ``transformer._set_cache_cursor``)."""
  return tree_map_with_path(
      lambda p, leaf: vec.astype(leaf.dtype) if _is_index(p) else leaf,
      slabs)


def consumed(slabs) -> bool:
  """Whether a program took ``slabs`` over: every buffer of a donated
  argument is deleted once the program that aliases it has been issued,
  and none is when the donation could not be used (JAX then only warns,
  and the program copies)."""
  return all(leaf.is_deleted() for leaf in jax.tree.leaves(slabs))


def ready(slabs) -> bool:
  """Whether the programs that produced ``slabs`` have all finished: every
  leaf's buffer is ready. Never blocks."""
  return all(leaf.is_ready() for leaf in jax.tree.leaves(slabs))


class SlotDecoder(object):
  """Jitted slab operations for one (config, num_slots) serving shape.

  Greedy decode only: continuous batching's contract is that every
  request's tokens are bit-identical to its own single-request decode,
  which sampling's batch-shaped rng draw cannot promise.

  ``page_size > 0`` switches the slab to the PAGED layout (``num_pages``
  pool pages of ``page_size`` tokens each, ``pages_per_slot`` table
  entries per slot — defaults cover the contiguous worst case so paging
  alone never shrinks capacity; set ``num_pages`` lower to spend less
  HBM than ``num_slots × max_seq_len``). ``spec_depth > 0`` enables
  :meth:`step_spec` with a ``spec_layers``-deep shallow-exit draft.

  Not every layer's cache is keys and values by position
  (``TransformerConfig.layer_types``): a KDA layer keeps a recurrent state
  and a convolution tail with NO position axis, an MLA layer one shared
  latent a token. The contiguous slab takes them as they are: ``insert``
  writes every ``[1, ...]`` row leaf into ``[slots, ...]``, whatever its
  rank. What cannot take them is refused at construction, by name: the
  paged pool (``TransformerConfig`` itself raises) and the prefix cache
  hold K/V pages per head, and speculative decoding's cursor rollback
  cannot unwind a recurrent state.

  A FROZEN lane's recurrent state needs no restore. A lane is frozen (its
  cursor bump undone, its token forced to pad) only once its request has
  stopped, mid-horizon, or while its slot is free: the engine never
  resumes a frozen lane, it hands the slot to the next request, and
  ``insert`` overwrites the whole row of every leaf, state and tail
  included. The garbage a frozen lane integrates meanwhile stays in its
  own row (no operation mixes lanes) and stays bounded (the delta rule is
  a contraction: unit keys, decay and write strength in (0, 1)).
  ``tests/test_kimi_linear.py`` stops a lane mid-horizon and reuses a slot.

  Not every leaf is ``max_seq_len`` rows long either. A model with
  PER-LAYER windows (``TransformerConfig.layer_windows``) keeps, in the one
  contiguous slab, a whole-context leaf pair for each full layer and a RING
  of the window's rows (``TransformerConfig.ring_rows``) for each window
  layer, under the one cursor a layer: the slab's model carries ``kv_ring``
  in its config as a paged slab's carries ``kv_page_size``. The prefill's
  one-row cache stays POSITIONAL (every position of ``max_seq_len``, the
  window a mask): a padded tail chunk writes rows past the cursor, which in
  a ring would be live rows of the window, and a multi-token chunk would
  have to read rows it is overwriting; ``insert`` moves the last rows of a
  window layer's row into the ring, position ``p`` to row ``p % rows``.
  What a ring cannot take is refused at construction, by name
  (``transformer.ring_refusal``): the paged pool and the prefix cache,
  speculative decoding, an int8 cache.
  """

  def __init__(self, cfg, num_slots: int, pad_id: int = 0, eos_id=None,
               mesh=None, page_size: int = 0, num_pages: int = 0,
               pages_per_slot: int = 0, spec_depth: int = 0,
               spec_layers: int = 0):
    if num_slots < 1:
      raise ValueError("num_slots must be >= 1, got %d" % num_slots)
    self.cfg = cfg
    self.num_slots = num_slots
    self.pad_id = int(pad_id)
    self.eos_id = None if eos_id is None else int(eos_id)
    self.mesh = mesh
    self.page_size = int(page_size)
    self.paged = self.page_size > 0
    #: the windows of the layers the slab holds as RINGS (empty: none)
    self.ring_windows = tuple(cfg.layer_windows[i] for i in cfg.ring_layers)
    #: whether the model counts (its expert layers, or its loop, sow
    #: ``counters``; its window layers hold rings): step_many then returns a
    #: fifth member
    self.counted = ("experts" in cfg.ffn_types or cfg.loop_passes > 1
                    or bool(self.ring_windows) or cfg.sparse_topk > 0)
    # what a layer that SELECTS its cached tokens cannot take is refused by
    # name: the paged pool and an int8 cache by TransformerConfig itself
    if cfg.sparse_topk and int(spec_depth) > 0:
      raise ValueError(tfm.sparse_refusal(
          cfg, "speculative decoding (spec_depth=%d)" % int(spec_depth),
          "draft"))
    if cfg.sparse_topk and mesh is not None and mesh.size > 1:
      raise ValueError(tfm.sparse_refusal(
          cfg, "a serving slab over a mesh of %d devices" % mesh.size,
          "mesh"))
    # what a ring cannot take is refused by name: the paged pool and an int8
    # cache by TransformerConfig itself (the slab's config, below)
    if self.ring_windows and int(spec_depth) > 0:
      raise ValueError(tfm.ring_refusal(
          "speculative decoding (spec_depth=%d)" % int(spec_depth), "draft"))
    if self.paged:
      pps = int(pages_per_slot) or -(-cfg.max_seq_len // self.page_size)
      pool = int(num_pages) or num_slots * pps + 1
      self.pages_per_slot = pps
      self.num_pages = pool
      # the slab model carries the paged cache layout in its config (the
      # jit-cache key), while prefill keeps the contiguous row layout
      self.slab_cfg = dataclasses.replace(
          cfg, kv_page_size=self.page_size, kv_num_pages=pool,
          kv_pages_per_slot=pps)
    else:
      self.pages_per_slot = 0
      self.num_pages = 0
      self.slab_cfg = dataclasses.replace(cfg, kv_ring=True) \
          if self.ring_windows else cfg
    self.spec_depth = int(spec_depth)
    if self.spec_depth < 0:
      raise ValueError("spec_depth must be >= 0, got %d" % self.spec_depth)
    if self.spec_depth and cfg.recurrent_state:
      raise ValueError(
          "speculative decoding (spec_depth=%d) rejects drafts by rolling "
          "the cache cursor back; this model's KDA layers keep a recurrent "
          "state with no position axis, which a cursor cannot unwind"
          % self.spec_depth)
    if self.spec_depth and "mla" in cfg.layer_types \
        and (self.spec_depth + 1) * cfg.num_heads > tfm._MXU_COLS:
      raise ValueError(
          "speculative decoding (spec_depth=%d) verifies %d tokens a lane in "
          "one step, and a latent layer of %d heads reads its leaf ABSORBED "
          "(as stored) only while tokens x heads <= %d; a wider block expands "
          "keys and values a head over the WHOLE slab (a verify window over a "
          "latent leaf of this many heads is not built)"
          % (self.spec_depth, self.spec_depth + 1, cfg.num_heads,
             tfm._MXU_COLS))
    if cfg.loop_passes > 1:
      # a looped model: what cannot take a cache a pass is refused by name
      # (the paged pool above, by TransformerConfig itself)
      if self.spec_depth:
        raise ValueError(tfm.loop_refusal(
            cfg, "draft",
            "speculative decoding (spec_depth=%d)" % self.spec_depth))
      if cfg.loop_exit_threshold < 1.0:
        raise ValueError(tfm.loop_refusal(
            cfg, "early_exit", "loop_exit_threshold=%r in a serving slab"
            % cfg.loop_exit_threshold))
    self.spec_layers = int(spec_layers) or max(1, cfg.num_layers // 2)
    if self.spec_depth and not 1 <= self.spec_layers <= cfg.num_layers:
      raise ValueError(
          "spec_layers must be in [1, num_layers=%d], got %d"
          % (cfg.num_layers, self.spec_layers))
    self.model = tfm.Transformer(cfg, mesh=mesh)
    self.slab_model = tfm.Transformer(self.slab_cfg, mesh=mesh) \
        if self.slab_cfg is not cfg else self.model
    # THE place the prefill plan is chosen, from what the config's layer
    # types say of the cache. Every kind there is can mask a padded tail: a
    # leaf indexed by position (K/V, int8 K/V with scales, the MLA latent)
    # by the cursor, a recurrent state and convolution tail by the true
    # length their layer takes (models/kda.py) -> the tail is padded. False
    # (the exact decomposition, whose program takes no n_valid) is what a
    # cache kind that can do neither would get here, and what the equality
    # tests steer a decoder to
    self.padded_prefill = True
    #: the chunk shapes :meth:`prefill` compiles when its caller names none:
    #: a padded plan's ladder follows the row's length
    self.buckets = row_buckets(cfg.max_seq_len)
    # jit caches retrace per chunk shape (bounded by the bucket set) /
    # once for insert+step (fixed slab shapes)
    self._prefill_fn = jax.jit(self._prefill_impl)
    self._insert_fn = jax.jit(self._insert_impl, donate_argnums=0)
    self._insert_pages_fn = jax.jit(self._insert_pages_impl,
                                    donate_argnums=0)
    self._gather_pages_fn = jax.jit(self._gather_pages_impl)
    self._reset_slots_fn = jax.jit(self._reset_slots_impl,
                                   donate_argnums=0)
    self._step_fn = jax.jit(self._step_impl, donate_argnums=1)
    self._merge_lanes_fn = jax.jit(self._merge_lanes_impl)
    self._step_many_jits = {}    # horizon -> jitted fused-scan step
    #: horizon -> (per-slot cursor writes of cache leaves a step_many
    #: dispatch makes, those of them by ops.cursor_write's DMA kernel):
    #: written while the program is traced, so there from its first call on
    self.cursor_writes = {}
    #: horizon -> (per-slot single-token cache reads a step_many dispatch
    #: makes: one a layer application a step, those of them by
    #: ops.decode_attention's kernel, which stops at each slot's cursor,
    #: those of them over a RING leaf: a reader of a device trace splits the
    #: kernel's calls by the leaf they read with it)
    self.attn_reads = {}
    #: horizon -> those of the same reads that lay under a selection's keep
    #: mask (``TransformerConfig.sparse_topk``): each also read its layer's
    #: index-key leaf WHOLE, ``num_slots x max_seq_len`` rows
    self.sparse_reads = {}
    #: program -> (grouped products of held experts one dispatch of it makes:
    #: three a layer application, those of them by ops.expert_product's
    #: kernel, which reads only the rows that have a group); a program is
    #: ("step", horizon) or ("prefill", the chunk's tokens)
    self.expert_products = {}
    #: program -> (exact selections of an indexer one dispatch of it makes:
    #: one a layer application of a model with ``sparse_topk``, those of them
    #: whose threshold search ran in ops.select_topk's kernel); programs as
    #: in ``expert_products``
    self.index_selections = {}
    self._step_spec_jits = {}    # rounds -> jitted fused spec-round scan
    self._zero_row = None        # memoized fresh [1, ...] cache (immutable)

  # -- slab construction ----------------------------------------------------

  def init_slabs(self):
    """A fresh all-zeros slab with VECTOR per-slot cursors (paged slabs
    are born vector-cursored with their page tables all-trash)."""
    cache = tfm._zero_cache(self.slab_model, self.num_slots)
    if self.paged:
      return cache                 # index is already [num_slots]

    def widen(path, leaf):
      if _is_index(path):
        return jnp.zeros((self.num_slots,), leaf.dtype)
      return leaf

    return tree_map_with_path(widen, cache)

  # -- prefill (single row, bucketed chunks) --------------------------------

  def _prefill_impl(self, params, cache, tokens, n_valid=None):
    """One chunk. ``n_valid`` (a traced int32 scalar; ``None`` in the
    exact plan, whose program is then what it always was) is how many of
    ``tokens [1, seg]`` are real: the rest is padding behind them."""
    # recompile sentinel seam: fires once per (re)trace — the prefill jit
    # cache must stay bounded by the bucket set (obs/device.py)
    obs_device.note_trace("serve.prefill")
    # padded: only the last REAL row goes through the final norm and the
    # head (a [seg, vocab] logits block is never built to pick one row)
    with tfm.expert_product_tally() as products, \
        tfm.index_select_tally() as selections:
      logits, mutated = self.model.apply(
          {"params": params, "cache": cache}, tokens, decode=True,
          mutable=["cache"],
          logits_at=None if n_valid is None else n_valid - 1,
          n_valid=n_valid)
    # on the host, while tracing: one program a chunk shape
    self.expert_products["prefill", tokens.shape[1]] = (
        products["products"], products["kernel"])
    self.index_selections["prefill", tokens.shape[1]] = (
        selections["selections"], selections["kernel"])
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    if n_valid is None:
      return mutated["cache"], nxt
    # every layer advanced its cursor by the chunk's SHAPE: set it to the
    # true length, so the padding's entries lie past it
    return _with_cursor(
        mutated["cache"],
        _cursor_leaf(cache).astype(jnp.int32) + n_valid), nxt

  def plan(self, n: int, offset: int = 0, buckets=None):
    """The chunks :meth:`prefill` runs ``n`` prompt tokens as, the first
    of them at position ``offset`` of the row: ``[(shape, valid), ...]``
    (:func:`padded_plan`; the exact :func:`chunk_plan`, ``valid ==
    shape``, for a decoder steered off the padded plan)."""
    buckets = self.buckets if buckets is None else buckets
    if self.padded_prefill:
      return padded_plan(n, self.cfg.max_seq_len - offset, buckets)
    return [(b, b) for b in chunk_plan(n, buckets)]

  def prefill(self, params, prompt, buckets=None, resume=None, trace=None,
              acc=None, queue=None) -> Tuple[object, int]:
    """Prefill one prompt into a fresh [1, ...] row cache and wait for it:
    :meth:`prefill_chunks`, then :meth:`prefill_first` of what it returns.
    ``(row_cache, first_token)``."""
    cache, head, seq = self.prefill_chunks(params, prompt, buckets, resume,
                                           trace, acc, queue)
    return cache, self.prefill_first(head, seq, trace, acc, queue)

  def prefill_chunks(self, params, prompt, buckets=None, resume=None,
                     trace=None, acc=None, queue=None):
    """Dispatch one prompt's prefill into a fresh [1, ...] row cache and
    read nothing.

    Returns ``(row_cache, head, seq)``: the warm cache (cursor at
    ``len(prompt)``), the last chunk's ``[1]`` token array still on the
    device (the first generated token g1: :meth:`prefill_first` reads it)
    and that chunk's number in ``queue`` (``None`` without one). The row
    needs no read to be inserted: a caller may queue the ``insert`` and
    whatever else behind the chunks and read the token later. Chunks follow
    :meth:`plan` over ``buckets`` (default: ``self.buckets``): the tail
    padded up to a bucket and masked by the cursor (in a recurrent layer,
    by the chunk's true length). Only the LAST chunk's token matters.
    Entries a padded chunk wrote past the cursor are harmless: nothing
    attends them before decode overwrites them.

    ``resume=(row_cache, start)`` skips the first ``start`` prompt
    tokens: the given warm cache already holds their KV (the
    shared-prefix path — ``gather_pages`` rebuilds such a cache from
    cached pool pages), so only the tail rides the chunked prefill.
    ``start`` must leave at least one tail token (the last prompt token
    must run through the model to yield g1).

    Each chunk dispatch (the host's slice-and-pad of its tokens included)
    is a ``serve.prefill.chunk`` region (``obs.spans.region``): a trace
    annotation always; a recorder span when ``trace`` (a request trace
    id) is given — the chunk-plan phase of the request waterfall. Chunk
    dispatches are async, so a chunk region measures dispatch-to-dispatch
    time (the benchmark's ``prefill_dispatch_ms.backlog`` is its mean, d
    ``t_prefill_s`` / d ``prefill_chunks``). ``acc`` (the engine's
    ``stats``) counts the dispatches in ``prefill_chunks``, the tokens they
    computed in ``prefill_tokens`` and the padding among them in
    ``prefill_padded_tokens`` (and, for a model that selects,
    ``sparse_prefill_queries`` / ``sparse_prefill_limited``). ``queue`` (the
    calling thread's
    ``obs.spans.DeviceQueue``) is told of each chunk's dispatch.
    """
    plen = len(prompt)
    if plen + 1 > self.cfg.max_seq_len:
      raise ValueError(
          "prompt of %d tokens leaves no decode room in the "
          "max_seq_len=%d cache" % (plen, self.cfg.max_seq_len))
    # deterministic fault site (TOS_CHAOS_SERVE, docs/ROBUSTNESS.md):
    # raise-or-stall here stands in for a device failure during prefill.
    # The index is the prompt length — the one identity a spec can pin
    # before request ids exist (per-length specs make poison requests)
    chaos.serve_fault("prefill", index=plen)
    if resume is not None:
      cache, off = resume
      off = int(off)
      if not 0 <= off < plen:
        raise ValueError(
            "prefill resume offset %d must be in [0, prompt_len=%d)"
            % (off, plen))
    else:
      if self._zero_row is None:
        # memoized: model.init is a full trace, far too slow to pay per
        # admitted request; jax arrays are immutable so one zero pytree
        # serves every prefill
        self._zero_row = tfm._zero_cache(self.model, 1)
      cache, off = self._zero_row, 0
    prompt = np.asarray(prompt, np.int32).reshape(plen)
    plan = self.plan(plen - off, off, buckets)
    if acc is not None:
      acc["prefill_chunks"] += len(plan)
      acc["prefill_tokens"] += sum(seg for seg, _ in plan)
      acc["prefill_padded_tokens"] += sum(seg - n for seg, n in plan)
      if self.cfg.sparse_topk:
        # real prompt tokens as queries of a layer that selects, and those
        # of them at a position with more candidates than it keeps
        acc["sparse_prefill_queries"] = acc.get(
            "sparse_prefill_queries", 0) + plen - off
        acc["sparse_prefill_limited"] = acc.get(
            "sparse_prefill_limited", 0) + max(
                0, plen - max(off, self.cfg.sparse_topk))
    nxt = seq = None
    for seg, n in plan:
      with obs_spans.region("serve.prefill.chunk", trace=trace,
                            record=trace is not None, chunk=seg,
                            offset=off):
        # sliced and padded on the host: the jit takes the numpy block as
        # it is (an eager slice would be a dispatch of its own a chunk)
        tokens = np.full((1, seg), self.pad_id, np.int32)
        tokens[0, :n] = prompt[off:off + n]
        cache, nxt = self._prefill_fn(
            params, cache, tokens,
            np.int32(n) if self.padded_prefill else None)
        products, kernel = self.expert_products["prefill", seg]
        if acc is not None and products:
          # a caller's own dict need not carry the keys
          acc["expert_products"] = acc.get("expert_products", 0) + products
          acc["expert_products_kernel"] = acc.get(
              "expert_products_kernel", 0) + kernel
        selections, kernel = self.index_selections["prefill", seg]
        if acc is not None and selections:
          acc["index_selections"] = acc.get(
              "index_selections", 0) + selections
          acc["index_selections_kernel"] = acc.get(
              "index_selections_kernel", 0) + kernel
        seq = None if queue is None else queue.dispatched()
      off += n
    return cache, nxt, seq

  def prefill_first(self, head, seq=None, trace=None, acc=None,
                    queue=None) -> int:
    """Wait for a prefill's last chunk and return its token, the first
    generated one: ``head`` and ``seq`` as :meth:`prefill_chunks` returned
    them. The wait is a ``serve.prefill.sync`` region (``acc``:
    ``t_prefill_sync_s``); ``queue`` is told of its end, and the region's
    tail after the read goes to ``empty_prefill_sync_s`` where the chunk
    is still the thread's newest dispatch (a program queued behind it,
    an ``insert``, may be running yet: the read then vouches for
    nothing)."""
    with obs_spans.region("serve.prefill.sync", acc, "t_prefill_sync_s",
                          trace=trace, record=trace is not None,
                          queue=queue):
      # fetched whole, because indexing the device array would be two more
      # eager programs (slice, squeeze)
      head = np.asarray(head)
      if queue is not None:
        queue.drained(seq)           # the wait is over: the rest is empty
      return int(head[0])

  # -- slot insert ----------------------------------------------------------

  def _insert_impl(self, slabs, row, slot):
    obs_device.note_trace("serve.insert")

    n = _cursor_leaf(row).astype(jnp.int32) if self.ring_windows else None

    def ins(s, r):
      if r.ndim == s.ndim:        # [1, ...] row leaf into [S, ...] slab
        if r.shape[1:] != s.shape[1:]:
          # a window layer's positional row into its ring: each ring row
          # takes the position it holds at cursor n (below 0: never written;
          # whatever lands there is not attended)
          r = jnp.take(r, jnp.maximum(
              tfm.ring_positions(n, s.shape[1]), 0), axis=1)
        return lax.dynamic_update_slice(
            s, r.astype(s.dtype), (slot,) + (0,) * (s.ndim - 1))
      # scalar cursor -> one element of the vector cursor
      return lax.dynamic_update_slice(
          s, r.astype(s.dtype).reshape(1), (slot,))

    return jax.tree.map(ins, slabs, row)

  def insert(self, slabs, row_cache, slot: int):
    """Write a prefilled row cache into slab position ``slot``."""
    return self._insert_fn(slabs, row_cache, jnp.asarray(slot, jnp.int32))

  # -- paged slab ops --------------------------------------------------------

  def _each_attn(self, slabs, row):
    """Yield matching (slab attn-cache dict, row attn-cache dict) pairs —
    the paged slab and the contiguous row cache have different leaf sets,
    so tree_map cannot pair them; this walks the shared dict spine."""
    if isinstance(slabs, dict) and "pages_k" in slabs:
      yield slabs, row
      return
    for key in slabs:
      for pair in self._each_attn(slabs[key],
                                  None if row is None else row[key]):
        yield pair

  def _map_attn(self, slabs, row, fn):
    """Rebuild ``slabs`` with ``fn(slab_attn, row_attn)`` applied at every
    attention-cache node (the dict holding ``pages_k``)."""
    if isinstance(slabs, dict) and "pages_k" in slabs:
      return fn(slabs, row)
    return {k: self._map_attn(slabs[k],
                              None if row is None else row[k], fn)
            for k in slabs}

  def _insert_pages_impl(self, slabs, row, slot, pages, start):
    """Scatter a prefilled row cache into pool pages.

    ``pages[i]`` receives prompt tokens ``[i·page_size, (i+1)·page_size)``;
    positions below ``start`` (already resident in shared prefix pages)
    and at/after the row's cursor are routed to the trash page. Sets the
    slot's page-table row and cursor as part of the same dispatch.
    """
    obs_device.note_trace("serve.insert_pages")
    ps, pp = self.page_size, self.pages_per_slot
    max_len = self.cfg.max_seq_len
    pos = jnp.arange(max_len)

    def ins(att_s, att_r):
      plen = att_r["index"].astype(jnp.int32)            # row cursor
      valid = jnp.logical_and(pos >= start, pos < plen)
      pg = jnp.where(valid, pages[jnp.clip(pos // ps, 0, pp - 1)], 0)
      off = pos % ps
      new = dict(att_s)
      for name in ("k", "v"):
        pool = att_s["pages_" + name]
        # the row cache folds heads into its minor axis; the pool does not
        new["pages_" + name] = pool.at[pg, off].set(
            att_r["cached_" + name][0].astype(pool.dtype).reshape(
                (max_len,) + pool.shape[-2:]))
      new["page_table"] = att_s["page_table"].at[slot].set(pages)
      new["index"] = att_s["index"].at[slot].set(plen)
      return new

    return self._map_attn(slabs, row, ins)

  def insert_pages(self, slabs, row_cache, slot: int, pages, start: int = 0):
    """Paged insert: write ``row_cache`` into ``pages`` (a
    ``pages_per_slot``-long int32 list, unused tail entries 0/trash) for
    slab position ``slot``, skipping the first ``start`` tokens (they
    live in shared read-only prefix pages the table also names)."""
    return self._insert_pages_fn(slabs, row_cache,
                                 jnp.asarray(slot, jnp.int32),
                                 jnp.asarray(pages, jnp.int32),
                                 jnp.asarray(start, jnp.int32))

  def _gather_pages_impl(self, slabs, pages, n_tokens):
    """Rebuild a contiguous [1, ...] row cache holding ``n_tokens``
    prefix tokens gathered from pool ``pages`` — the warm cache a
    shared-prefix tail prefill resumes from. Positions at/after
    ``n_tokens`` are garbage but sit past the cursor (masked, then
    overwritten by the tail prefill's writes before they are attended).
    """
    obs_device.note_trace("serve.gather_pages")
    ps, pp = self.page_size, self.pages_per_slot
    max_len = self.cfg.max_seq_len
    take = min(pp * ps, max_len)

    def build(att_s, _):
      hk, d = att_s["pages_k"].shape[-2:]
      row = {}
      for name in ("cached_k", "cached_v"):
        src = att_s["pages_" + name[-1]]
        # heads fold into the row cache's minor axis
        flat = src[pages].reshape(pp * ps, hk * d)
        buf = jnp.zeros((max_len, hk * d), src.dtype)
        row[name] = buf.at[:take].set(flat[:take])[None]
      row["index"] = n_tokens.astype(jnp.int32)
      return row

    return self._map_attn(slabs, None, build)

  def gather_pages(self, slabs, pages, n_tokens: int):
    return self._gather_pages_fn(slabs, jnp.asarray(pages, jnp.int32),
                                 jnp.asarray(n_tokens, jnp.int32))

  def _reset_slots_impl(self, slabs, freed):
    """Zero the page tables and cursors of freed slots: a freed slot's
    lane keeps computing (frozen), and its stale table would otherwise
    route garbage writes into pages the allocator has already handed to
    a NEW request — the reset points them at the trash page instead."""
    obs_device.note_trace("serve.reset_slots")

    def rst(att_s, _):
      new = dict(att_s)
      new["page_table"] = jnp.where(freed[:, None], 0,
                                    att_s["page_table"])
      new["index"] = jnp.where(freed, 0, att_s["index"])
      return new

    return self._map_attn(slabs, None, rst)

  def reset_slots(self, slabs, freed_mask):
    return self._reset_slots_fn(slabs, jnp.asarray(freed_mask, jnp.bool_))

  # -- decode step ----------------------------------------------------------

  def _one_step(self, params, slabs, tok, active, count: bool = False):
    """One token a lane: ``(new_slabs, next_tokens, counts)``. With
    ``count`` (a model that sows ``counters``) ``counts`` holds int32 sums
    over LIVE lanes, else it is ``None``: ``context`` tokens the live
    lanes' caches held before the step; of expert layers ``held``
    assignments to experts held here and ``touched`` held experts that got
    at least one live token (summed over expert layers) and, under a group
    limit, ``group`` tokens whose kept groups include one a held expert lies
    in; of a looped model
    ``exit_pass``, the pass at which its gates let each live lane's token
    exit; of a model whose window layers hold rings ``window_context``, the
    rows ONE window layer has to read for the step, ``min(cursor, window)``
    (the mean over the window layers, should their windows differ); of a
    model whose attention SELECTS (``TransformerConfig.sparse_topk``)
    ``sparse_kept`` entries the live lanes' queries kept and
    ``sparse_candidates`` they chose among (both summed over the layers) and
    ``sparse_limited`` live queries with more candidates than the selection
    keeps (a token, not a layer).

    An inactive lane (free, or finished inside this horizon) runs its pad
    token at cursor 0 of its own slot: the row it writes lands where the
    next insert overwrites, and an attention that stops at the cursor
    (``ops.decode_attention``) reads none of the rows the lane's last
    request left. The paged pool keeps its cursors: the first page of a
    lane's table may be a shared prefix's."""
    going_in = slabs if self.paged else tree_map_with_path(
        lambda p, leaf: jnp.where(active, leaf, 0) if _is_index(p) else leaf,
        slabs)
    logits, mutated = self.slab_model.apply(
        {"params": params, "cache": going_in}, tok[:, None], decode=True,
        mutable=["cache", "counters"] if count else ["cache"])
    new_cache = mutated["cache"]
    counts = None
    if count:
      sown = mutated.get("counters", {})   # rings alone sow nothing
      counts = {}
      if "experts" in self.cfg.ffn_types:
        held = _sown(sown, "held")                     # [slots] a layer
        hit = _sown(sown, "hit")                       # [slots, held]
        counts.update(
            held=sum(jnp.sum(jnp.where(active, x, 0)) for x in held),
            touched=sum(jnp.sum(jnp.any(
                jnp.logical_and(x, active[:, None]), axis=0),
                                dtype=jnp.int32) for x in hit))
        if self.cfg.experts_groups:
          counts["group"] = sum(
              jnp.sum(jnp.logical_and(x, active), dtype=jnp.int32)
              for x in _sown(sown, "group"))                # [slots] a layer
      cursor = _cursor_leaf(slabs).astype(jnp.int32)
      counts["context"] = jnp.sum(jnp.where(active, cursor, 0))
      if self.ring_windows:
        counts["window_context"] = sum(
            jnp.sum(jnp.where(active, jnp.minimum(cursor, w), 0))
            for w in self.ring_windows) // len(self.ring_windows)
      if self.cfg.loop_passes > 1:
        (exits,) = _sown(sown, "exit_pass")            # [slots, 1]
        counts["exit_pass"] = jnp.sum(jnp.where(active, exits[:, 0], 0))
      if self.cfg.sparse_topk:
        kept = _sown(sown, "sparse_kept")              # [slots] a layer
        counts.update(
            sparse_kept=sum(jnp.sum(jnp.where(active, x, 0)) for x in kept),
            # a query's candidates: the cache's rows and its own token
            sparse_candidates=len(kept) * jnp.sum(
                jnp.where(active, cursor + 1, 0)),
            sparse_limited=jnp.sum(jnp.logical_and(
                active, cursor + 1 > self.cfg.sparse_topk), dtype=jnp.int32))

    def freeze(path, new, old):
      # inactive slots must not advance: undo their cursor bump so the
      # garbage k/v their lane wrote stays masked and gets overwritten
      # by the next real token (or by the next prefill insert)
      if _is_index(path):
        return jnp.where(active, new, old)
      return new

    new_cache = tree_map_with_path(freeze, new_cache, slabs)
    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    nxt = jnp.where(active, nxt, jnp.int32(self.pad_id))
    return new_cache, nxt, counts

  def _step_impl(self, params, slabs, tok, active):
    obs_device.note_trace("serve.step")
    return self._one_step(params, slabs, tok, active)[:2]

  def step(self, params, slabs, last_tokens, active):
    """One token for every live slot: ``(new_slabs, next_tokens)``.

    ``last_tokens: [num_slots] int32`` (pad for inactive lanes),
    ``active: [num_slots] bool``. Inactive lanes compute but are frozen.
    """
    return self._step_fn(params, slabs, jnp.asarray(last_tokens, jnp.int32),
                         jnp.asarray(active, jnp.bool_))

  def step_many(self, params, slabs, last_tokens, active, remaining,
                horizon: int):
    """``horizon`` fused decode steps with on-device EOS/budget stops.

    Returns ``(new_slabs, tokens, active, remaining)`` where ``tokens``
    is ``[horizon, num_slots]`` — a lane's stream is valid up to ITS
    stop (EOS inclusive / budget exhausted), pad after; the host replays
    the same stop rule to harvest. ``remaining: [num_slots] int32`` is
    each lane's unspent token budget. One compile per distinct horizon.
    A model that counts (``self.counted``) returns a fifth member: the
    ``_one_step`` counters summed over the horizon, int32 scalars.
    """
    if horizon < 1:
      raise ValueError("horizon must be >= 1, got %d" % horizon)
    # deterministic fault site (TOS_CHAOS_SERVE): one count per fused
    # decode dispatch — "decode#N:raise" crashes the Nth horizon step
    chaos.serve_fault("decode")
    fresh = horizon not in self._step_many_jits
    fn = self.step_many_jit(horizon)
    if fresh:
      # the serving step's HLO cost (flops / bytes accessed), captured
      # once per horizon at first use — rides the OBS wire as gauges.
      # The horizon must live in the LABEL: it is a closed-over scan
      # length, invisible to the arg-shape fingerprint, and two horizons
      # have genuinely different costs
      obs_device.capture_cost(
          "serve.step_many.h%d" % horizon, fn, params, slabs,
          jnp.asarray(last_tokens, jnp.int32),
          jnp.asarray(active, jnp.bool_),
          jnp.asarray(remaining, jnp.int32))
    return fn(params, slabs, jnp.asarray(last_tokens, jnp.int32),
              jnp.asarray(active, jnp.bool_),
              jnp.asarray(remaining, jnp.int32))

  def step_many_jit(self, horizon: int):
    """The jitted ``horizon``-step scan behind :meth:`step_many` (built
    once per horizon) — also what the deviceless compile gate lowers."""
    fn = self._step_many_jits.get(horizon)
    if fn is None:
      def impl(params, slabs, tok, active, remaining, _h=horizon):
        obs_device.note_trace("serve.step_many")

        def body(carry, _):
          slabs, tok, active, remaining = carry
          with tfm.cursor_write_tally() as writes, \
              tfm.decode_attention_tally() as reads, \
              tfm.expert_product_tally() as products, \
              tfm.index_select_tally() as selections:
            slabs, nxt, counts = self._one_step(params, slabs, tok, active,
                                                count=self.counted)
          # on the host, while tracing: the body is one step of _h
          self.cursor_writes[_h] = (_h * writes["leaves"],
                                    _h * writes["dma"])
          self.attn_reads[_h] = tuple(
              _h * reads[k] for k in ("reads", "ragged", "ring"))
          self.sparse_reads[_h] = _h * reads.get("sparse", 0)
          self.expert_products["step", _h] = (_h * products["products"],
                                              _h * products["kernel"])
          self.index_selections["step", _h] = (
              _h * selections["selections"], _h * selections["kernel"])
          remaining = jnp.where(active, remaining - 1, remaining)
          done_now = remaining <= 0
          if self.eos_id is not None:
            done_now = jnp.logical_or(done_now, nxt == self.eos_id)
          new_active = jnp.logical_and(active, jnp.logical_not(done_now))
          tok = jnp.where(new_active, nxt, jnp.int32(self.pad_id))
          return (slabs, tok, new_active, remaining), (nxt, counts)

        (slabs, _, active, remaining), (toks, counts) = lax.scan(
            body, (slabs, tok, active, remaining), None, length=_h)
        if self.counted:
          return slabs, toks, active, remaining, \
              jax.tree.map(lambda x: jnp.sum(x, axis=0), counts)
        return slabs, toks, active, remaining

      fn = self._step_many_jits[horizon] = jax.jit(impl, donate_argnums=1)
    return fn

  # -- the lane state, carried on the device --------------------------------

  def _merge_lanes_impl(self, toks, active, remaining, host, first):
    obs_device.note_trace("serve.merge_lanes")
    whose, host_tok, host_rem = host[0], host[1], host[2]
    pad = jnp.int32(self.pad_id)
    mine = whose != LANE_DEVICE
    admitted = whose == LANE_FIRST
    # step_many's own carry: the last token of a lane still live, pad else
    tok = jnp.where(active, toks[-1], pad)
    tok = jnp.where(admitted, first[0], jnp.where(mine, host_tok, tok))
    live = host_rem > 0
    if self.eos_id is not None:
      # a first token that is EOS ended its request: the host learns it at
      # the read, the lane never goes live
      live = jnp.logical_and(live, jnp.logical_not(
          jnp.logical_and(admitted, tok == self.eos_id)))
    active = jnp.where(mine, live, active)
    remaining = jnp.where(mine, host_rem, remaining)
    return jnp.where(active, tok, pad), active, remaining

  def merge_lanes(self, toks, active, remaining, host, first=None):
    """The ``(last_tokens, active, remaining)`` the NEXT :meth:`step_many`
    takes, made on the device from the ``(tokens, active, remaining)`` the
    one before it returned, nothing read: a lane the device carried keeps
    ``where(active, tokens[-1], pad)``, its mask and its budget.

    ``host: [3, num_slots] int32`` lays the host's word over it: row 0 says
    whose each lane is (:data:`LANE_DEVICE`, :data:`LANE_HOST`,
    :data:`LANE_FIRST`), rows 1 and 2 the last token and the unspent budget
    of a lane that is not the device's (budget 0: the lane is off). A
    :data:`LANE_FIRST` lane's token is ``first[0]``, a prefill's ``[1]``
    output still on the device (:meth:`prefill_chunks`' ``head``), and the
    lane stays off where that token is EOS. One program whatever the mix."""
    if first is None:              # no admission to merge: the same program
      first = np.zeros((1,), np.int32)
    return self._merge_lanes_fn(toks, active, remaining,
                                np.asarray(host, np.int32), first)

  # -- self-speculative decode ----------------------------------------------

  def step_spec(self, params, slabs, last_tokens, active, remaining,
                rounds: int):
    """``rounds`` fused SELF-SPECULATIVE rounds (requires
    ``spec_depth > 0``). Each round per lane: draft ``spec_depth``
    tokens with the ``spec_layers``-deep shallow exit, roll the draft
    layers' cursors back, verify the window with ONE full-model
    multi-token step, keep the longest target-agreeing prefix plus the
    target's correction token, and advance that lane's cursor by
    exactly the kept count — so every kept token is the target's own
    greedy emission (bit-identical to ``greedy_generate_kv``) and a
    round emits 1..spec_depth tokens per live lane.

    Returns ``(new_slabs, tokens, counts, accepted, rejected, active,
    remaining)``: ``tokens [rounds, spec_depth, num_slots]`` (a lane's
    round is valid for its first ``counts[r, lane]`` positions, pad
    after — counts are REQUIRED for harvest: rejection padding is
    indistinguishable from an emitted pad token), ``accepted``/
    ``rejected [rounds, num_slots]`` draft-token verdicts for the
    telemetry counters. One compile per distinct ``rounds``.
    """
    if not self.spec_depth:
      raise ValueError("step_spec requires spec_depth > 0")
    if rounds < 1:
      raise ValueError("rounds must be >= 1, got %d" % rounds)
    # the same deterministic fault site as step_many: one count per
    # fused decode dispatch, so TOS_CHAOS_SERVE schedules hit spec and
    # non-spec engines alike
    chaos.serve_fault("decode")
    fn = self._step_spec_jits.get(rounds)
    if fn is None:
      k = self.spec_depth

      def impl(params, slabs, tok, active, remaining, _r=rounds):
        obs_device.note_trace("serve.step_spec")

        def round_body(carry, _):
          slabs, tok, active, remaining = carry
          cur0 = _cursor_leaf(slabs).astype(jnp.int32)

          def dstep(c, _):
            cache, t = c
            logits, mut = self.slab_model.apply(
                {"params": params, "cache": cache}, t[:, None],
                decode=True, mutable=["cache"],
                exit_layer=self.spec_layers)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (mut["cache"], nxt), nxt

          (cache_d, _), P = lax.scan(dstep, (slabs, tok), None, length=k)
          # rollback: only the shallow layers advanced; their draft
          # writes sit past the restored cursor, masked and overwritten
          cache_d = _with_cursor(cache_d, cur0)
          Pt = P.T                                         # [S, k]
          V = jnp.concatenate([tok[:, None], Pt[:, :k - 1]], axis=1)
          logits, mut = self.slab_model.apply(
              {"params": params, "cache": cache_d}, V, decode=True,
              mutable=["cache"])
          cache_v = mut["cache"]
          T = jnp.argmax(logits, -1).astype(jnp.int32)     # [S, k]
          ok = (Pt == T).astype(jnp.int32)
          m = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)     # [S] in [0,k]
          bonus = jnp.take_along_axis(
              T, jnp.minimum(m, k - 1)[:, None], axis=1)[:, 0]
          cols = jnp.arange(k)[None, :]
          # kept stream: m agreed proposals, then (m < k) the target's
          # correction — never more than k tokens, all target-greedy
          emit = jnp.where(cols == m[:, None], bonus[:, None], Pt)
          adv = jnp.where(m < k, m + 1, k)
          limit = jnp.minimum(adv, remaining)
          if self.eos_id is not None:
            iseos = jnp.logical_and(emit == self.eos_id,
                                    cols < limit[:, None])
            has_eos = jnp.any(iseos, axis=1)
            stop = jnp.where(has_eos, jnp.argmax(iseos, axis=1) + 1,
                             limit)
          else:
            has_eos = jnp.zeros_like(active)
            stop = limit
          stop = jnp.where(active, stop, 0)
          toks = jnp.where(cols < stop[:, None], emit,
                           jnp.int32(self.pad_id))
          new_rem = jnp.where(active, remaining - stop, remaining)
          done = jnp.logical_or(new_rem <= 0, has_eos)
          new_active = jnp.logical_and(active, jnp.logical_not(done))
          newlast = jnp.take_along_axis(
              emit, jnp.clip(stop - 1, 0, k - 1)[:, None], axis=1)[:, 0]
          new_tok = jnp.where(new_active, newlast,
                              jnp.int32(self.pad_id))
          slabs2 = _with_cursor(cache_v, cur0 + stop)
          accepted = jnp.minimum(stop, m)
          rejected = jnp.where(active, k - m, 0)
          return (slabs2, new_tok, new_active, new_rem), \
              (toks.T, stop, accepted, rejected)

        (slabs, tok, active, remaining), ys = lax.scan(
            round_body, (slabs, tok, active, remaining), None, length=_r)
        toks, counts, acc, rej = ys
        return slabs, toks, counts, acc, rej, active, remaining

      fn = self._step_spec_jits[rounds] = jax.jit(impl, donate_argnums=1)
      obs_device.capture_cost(
          "serve.step_spec.r%d" % rounds, fn, params, slabs,
          jnp.asarray(last_tokens, jnp.int32),
          jnp.asarray(active, jnp.bool_),
          jnp.asarray(remaining, jnp.int32))
    return fn(params, slabs, jnp.asarray(last_tokens, jnp.int32),
              jnp.asarray(active, jnp.bool_),
              jnp.asarray(remaining, jnp.int32))
