"""Checkpoint/resume: a thin manager over orbax with step tracking.

The reference delegated checkpointing to user TF callbacks and only
provided path plumbing + an export grace window (SURVEY.md §5
checkpoint/resume). This module keeps that division of labor but gives the
JAX path a ready-made manager: periodic saves keyed by step, latest-step
restore for resume-after-preemption, retention, and chief-only writes.
"""

import json
import logging
import os
from typing import Any, Optional

logger = logging.getLogger(__name__)

#: commit-marker file written next to each step after its save is durable.
#: Its presence IS the commit record: ``restore_or`` rejects a step with no
#: marker deterministically (torn save) instead of discovering the tear via
#: a deserialize failure, and its JSON body carries the save's manifest
#: (e.g. the elastic-training group topology — ``parallel.groups``).
_MARKER_FMT = ".commit-%d.json"
_MARKER_PREFIX = ".commit-"
_MARKER_SUFFIX = ".json"


def atomic_write_json(path: str, payload: dict) -> None:
  """Commit ``payload`` to ``path`` via write-to-temp + fsync + atomic
  rename — THE torn-write-proof marker protocol. A kill at any point
  leaves either no file or a complete one, never a half-written record.

  This is the single implementation behind the checkpoint commit markers
  and the model-registry publish markers (``serving.registry``): two
  independent torn-write protocols must not drift, so both call here.
  Raises ``OSError`` on failure — callers decide whether a marker-write
  failure fails the operation.
  """
  tmp = path + ".tmp"
  with open(tmp, "w") as f:
    json.dump(payload, f)
    f.flush()
    os.fsync(f.fileno())
  os.replace(tmp, path)


def params_fingerprint(tree: Any) -> str:
  """Cheap content fingerprint of a params pytree: crc32 over every
  leaf's bytes folded with its flattened path, shape, and dtype.

  Shared by the model registry (publish manifest / poisoned-candidate
  detection) and ``make_serving_predict_fn``'s engine-cache key, so "same
  weights" means the same thing on both sides of the train→serve loop.
  Not cryptographic — this guards against torn publishes and stale cache
  hits, not adversaries.
  """
  import zlib
  import jax
  import numpy as np
  acc = 0
  leaves, treedef = jax.tree_util.tree_flatten(tree)
  acc = zlib.crc32(repr(treedef).encode(), acc)
  for leaf in leaves:
    arr = np.asarray(leaf)
    acc = zlib.crc32(str((arr.shape, str(arr.dtype))).encode(), acc)
    acc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), acc)
  return "%08x" % (acc & 0xFFFFFFFF)


class CheckpointManager(object):
  """Periodic save / latest restore of a train-state pytree.

  Usage::

      mgr = CheckpointManager(args.model_dir, save_interval_steps=100)
      state, start_step = mgr.restore_or(state)     # resume if possible
      for step in range(start_step, num_steps):
          state, loss = train_step(state, batch)
          mgr.save(step, state, is_chief=ctx.is_chief)
      mgr.wait()

  With a checkpointable input pipeline (exact mid-epoch resume)::

      it = data.checkpointable_input(pattern, batch_size, seed=0)
      state, start_step = mgr.restore_or(state, data_iterator=it)
      for step, batch in enumerate(it, start=start_step):
          state, loss = train_step(state, batch)
          mgr.save(step, state, data_state=it.get_state())
  """

  def __init__(self, directory: str, save_interval_steps: int = 100,
               max_to_keep: int = 3, publish_hook: Optional[Any] = None):
    import orbax.checkpoint as ocp
    from tensorflowonspark_tpu.utils import paths

    self.directory = paths.for_io(directory)
    # commit markers are plain files: local directories only (remote URIs
    # keep the legacy deserialize-failure fallback in restore_or)
    self._local = not paths.is_remote_uri(self.directory)
    if self._local:
      os.makedirs(self.directory, exist_ok=True)
    self.save_interval_steps = save_interval_steps
    #: ``publish_hook(step, state, manifest)`` fires after a save COMMITS
    #: (marker durable) — the train→serve seam. A registry attaches one
    #: via ``serving.registry.ModelRegistry.publish_on_checkpoint`` so
    #: every committed checkpoint becomes a candidate serving version on
    #: the existing cadence. Best-effort: a publish failure is logged,
    #: never fails the save (the checkpoint itself is already durable).
    self.publish_hook = publish_hook
    self._mgr = ocp.CheckpointManager(
        self.directory,
        options=ocp.CheckpointManagerOptions(
            max_to_keep=max_to_keep,
            save_interval_steps=save_interval_steps))

  def save(self, step: int, state: Any, is_chief: bool = True,
           force: bool = False, data_state: Optional[dict] = None,
           manifest: Optional[dict] = None) -> bool:
    """Save if the step hits the interval.

    ``data_state`` (a small JSON-safe dict, e.g.
    ``data.indexed.CheckpointableInput.get_state()``) rides in the same
    checkpoint as a named item, so model and input-pipeline state stay
    atomically consistent — resume continues mid-epoch with exactly the
    batches the uninterrupted run would have seen (the reference got this
    from tf.train.Checkpoint over tf.data iterators; the feed mode had no
    equivalent).

    Role handling depends on the process topology: in a jax.distributed
    process group, orbax's save is a COLLECTIVE — every process must call
    it (orbax writes from the primary host only), so ``is_chief`` is
    ignored there. For independent single-process nodes (no process
    group), only the chief writes (parity with chief-only export,
    reference compat.py:10-17).

    The save decision is interval-CROSSING, not modulo: a fused train
    loop calls this once per slab with ``step`` jumping ``unroll`` at a
    time, and orbax's own ``step % interval == 0`` rule would silently
    stretch the cadence to the steps' common multiples (``unroll=8``
    with ``save_interval_steps=5`` would save every 40 steps — or
    never, for coprime pairs past max step). Here the save fires at the
    FIRST call whose step reached/passed an interval boundary since the
    last saved step — step-accurate at slab boundaries, and identical
    to the old behavior for dense per-step calls.
    """
    import jax
    if not is_chief and jax.process_count() <= 1:
      return False
    if not force and not self._due(step):
      return False
    import orbax.checkpoint as ocp
    items = {"state": ocp.args.StandardSave(state)}
    if data_state is not None:
      items["data"] = ocp.args.JsonSave(data_state)
    try:
      # force=True: the interval decision was made above (orbax's modulo
      # rule would re-filter boundary-crossing slab steps right back out)
      saved = self._mgr.save(step, args=ocp.args.Composite(**items),
                             force=True)
    except ValueError:
      # a directory written by the pre-composite manager pins orbax to
      # the single-unnamed-item layout; keep appending in that layout
      if data_state is not None:
        logger.warning("legacy checkpoint layout in %s cannot carry "
                       "data_state; saving model state only",
                       self.directory)
      saved = self._mgr.save(step, args=ocp.args.StandardSave(state),
                             force=True)
    if saved:
      self._write_marker(step, manifest)
      logger.info("checkpoint saved at step %d", step)
      if self.publish_hook is not None:
        try:
          self.publish_hook(step, state, manifest)
        except Exception as e:  # noqa: BLE001 # tosa: ignore[TOS004] - best-effort
          # publish is best-effort: the checkpoint committed; a
          # registry outage must not fail it (serving has watch/resume)
          logger.warning("publish hook at step %d failed: %s: %s",
                         step, type(e).__name__, e)
    return saved

  # -- commit markers (deterministic torn-save detection) ---------------------

  def _marker_path(self, step: int) -> str:
    return os.path.join(self.directory, _MARKER_FMT % step)

  def _write_marker(self, step: int, manifest: Optional[dict]) -> None:
    """Commit the save: wait for the (possibly async) write to be durable,
    then publish the marker via write-to-temp + atomic rename. A kill at
    any point leaves either no marker (torn save, rejected at restore) or
    a complete one — never a half-written marker next to half-written
    data. ``manifest`` (small, JSON-safe — e.g. the group topology from
    ``parallel.groups.GroupSet.save``) rides in the marker body."""
    if not self._local:
      return
    self._mgr.wait_until_finished()
    path = self._marker_path(step)
    try:
      atomic_write_json(path, {"step": int(step), "manifest": manifest or {}})
    except OSError as e:
      # the data is durable; a marker-write failure must not fail the save
      # (the step merely restores via nothing — same as a torn save)
      logger.warning("commit marker for step %d failed: %s", step, e)
      return
    # retention pruning: drop markers whose step orbax already deleted
    live = set(self._mgr.all_steps())
    try:
      names = os.listdir(self.directory)
    except OSError:
      return
    for name in names:
      if not (name.startswith(_MARKER_PREFIX)
              and name.endswith(_MARKER_SUFFIX)):
        continue
      try:
        s = int(name[len(_MARKER_PREFIX):-len(_MARKER_SUFFIX)])
      except ValueError:
        continue
      if s not in live:
        try:
          os.remove(os.path.join(self.directory, name))
        except OSError:  # tosa: ignore[TOS004] - retention pruning is
          pass           # best-effort; a leftover marker is harmless

  def _has_markers(self) -> bool:
    """True when this directory uses commit markers at all (any step has
    one). Marker-free directories predate the marker scheme and keep the
    legacy deserialize-failure fallback."""
    if not self._local:
      return False
    try:
      return any(n.startswith(_MARKER_PREFIX) and n.endswith(_MARKER_SUFFIX)
                 for n in os.listdir(self.directory))
    except OSError:
      return False

  def _read_marker(self, step: int) -> Optional[dict]:
    """The step's commit record, or None (missing or unparseable — both
    mean the save never committed)."""
    if not self._local:
      return None
    try:
      with open(self._marker_path(step)) as f:
        return json.load(f)
    except (OSError, ValueError):
      return None

  def manifest(self, step: Optional[int] = None) -> Optional[dict]:
    """The manifest committed with ``step`` (default: latest), or None."""
    step = step if step is not None else self._mgr.latest_step()
    if step is None:
      return None
    rec = self._read_marker(step)
    return rec.get("manifest") if rec else None

  def _due(self, step: int) -> bool:
    """True when ``step`` reached/crossed an interval boundary since the
    last saved step (always for the first save; never for non-advancing
    steps). A signalled preemption is always due — taking the interval
    decision out of orbax's hands must not lose its save-on-preemption
    behavior for mid-interval steps."""
    last = self._mgr.latest_step()
    if last is not None and step <= last:
      return False
    # the same call orbax's own should_save made on this path before the
    # crossing rule replaced it (getattr: older orbax lacks the method)
    reached = getattr(self._mgr, "reached_preemption", None)
    if reached is not None and reached(step):
      return True
    if last is None:
      return True
    interval = max(1, int(self.save_interval_steps))
    return (step // interval) > (last // interval)

  def latest_step(self, refresh: bool = False) -> Optional[int]:
    """Newest checkpointed step, or None.

    orbax caches the directory's step listing at construction and after
    its own saves — a manager that only READS (the evaluator-sidecar
    pattern: another process writes the checkpoints) must pass
    ``refresh=True`` to rescan, or it will report the world as of its
    own birth forever.
    """
    if refresh:
      self._mgr.reload()
    return self._mgr.latest_step()

  def restore(self, state_template: Any, step: Optional[int] = None,
              with_data: bool = False) -> Any:
    """Restore the given (or latest) step into the template's structure.

    ``with_data=True`` returns ``(state, data_state_or_None)`` — None when
    the checkpoint carries no input-pipeline item (legacy layout, or saved
    without ``data_state``).
    """
    import orbax.checkpoint as ocp
    step = step if step is not None else self._mgr.latest_step()
    if step is None:
      raise FileNotFoundError("no checkpoints in %s" % self.directory)
    try:
      out = self._mgr.restore(step, args=ocp.args.Composite(
          state=ocp.args.StandardRestore(state_template)))
      state = out["state"]
    except ValueError:
      # pre-composite layout: the whole checkpoint IS the model state
      state = self._mgr.restore(
          step, args=ocp.args.StandardRestore(state_template))
      return (state, None) if with_data else state
    if not with_data:
      return state
    try:
      data = self._mgr.restore(
          step, args=ocp.args.Composite(data=ocp.args.JsonRestore()))["data"]
    except KeyError:
      data = None
    return state, data

  def restore_or(self, state: Any, data_iterator: Any = None,
                 with_manifest: bool = False):
    """(state, next_step): restored latest if present, else the input.

    With ``data_iterator`` (anything exposing ``set_state``, e.g.
    ``CheckpointableInput``), a checkpointed input-pipeline state is
    pushed into it so the stream resumes mid-epoch. With
    ``with_manifest=True`` the return is ``(state, next_step, manifest)``
    — the commit marker's manifest dict (None when absent or fresh).

    Preemption-safe: this is the resume entry point for a node relaunched
    after a SIGKILL/preemption (the supervisor hands the restart count to
    the user fn via ``ctx.restart_count``). In a directory that carries
    commit markers, a step with NO marker never committed — it is
    rejected deterministically, without a restore attempt whose failure
    mode depends on how the storage layer surfaces the tear. Marker-free
    (legacy) directories keep the old behavior: a checkpoint left
    unreadable by a kill mid-save is skipped with a warning after its
    deserialize fails, falling back to the newest step that restores
    cleanly rather than wedging the relaunched node forever.
    """
    step = self._mgr.latest_step()
    last_error = None
    markers = self._has_markers()
    while step is not None:
      if markers and self._read_marker(step) is None:
        logger.warning("checkpoint step %d has no commit marker (torn "
                       "save); rejecting it without a restore attempt", step)
        last_error = RuntimeError(
            "checkpoint step %d in %s has no commit marker"
            % (step, self.directory))
        older = [s for s in self._mgr.all_steps() if s < step]
        step = max(older) if older else None
        continue
      logger.info("resuming from checkpoint step %d", step)
      try:
        if data_iterator is None:
          restored = self.restore(state, step=step)
        else:
          restored, data = self.restore(state, step=step, with_data=True)
          if data is not None:
            data_iterator.set_state(data)
          else:
            logger.warning("checkpoint step %d has no input-pipeline state; "
                           "the data iterator starts from its current "
                           "position", step)
        if with_manifest:
          return restored, step + 1, self.manifest(step)
        return restored, step + 1
      except Exception as e:  # noqa: BLE001 - torn/corrupt checkpoint
        logger.warning("checkpoint step %d unreadable (%s: %s); trying the "
                       "previous step", step, type(e).__name__, e)
        last_error = e
        older = [s for s in self._mgr.all_steps() if s < step]
        step = max(older) if older else None
    if last_error is not None:
      # EVERY step failed to restore: that is a systemic problem (template
      # mismatch, storage outage, bad credentials), not a torn checkpoint
      # — silently retraining from step 0 would discard real progress
      raise last_error
    return (state, 0, None) if with_manifest else (state, 0)

  def all_steps(self):
    """Every step with a checkpoint in this directory (ascending)."""
    return sorted(self._mgr.all_steps())

  def wait(self) -> None:
    """Block until async saves land (call before process exit)."""
    self._mgr.wait_until_finished()

  def close(self) -> None:
    self._mgr.close()
