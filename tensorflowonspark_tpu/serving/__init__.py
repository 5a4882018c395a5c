"""Continuous-batching serving runtime (slot-based in-flight decode).

Public surface:

* :class:`~tensorflowonspark_tpu.serving.engine.ServingEngine` — the
  runtime: submit/poll/stream/generate over a persistent slot slab,
  plus the self-healing surface — admission control
  (:class:`ServingOverloaded`), per-request deadlines
  (:class:`DeadlineExceeded`) and ``cancel(rid)``
  (:class:`RequestCancelled`), crash-replay recovery with poison
  detection (:class:`PoisonedRequest`), and graceful ``drain(timeout)``.
* :class:`~tensorflowonspark_tpu.serving.slots.SlotDecoder` /
  :func:`~tensorflowonspark_tpu.serving.slots.padded_plan` /
  :func:`~tensorflowonspark_tpu.serving.slots.chunk_plan` — the jitted
  device ops and the bucketed-prefill policy (a prompt's tail padded to
  a bucket and masked by the cursor, in a recurrent layer by the
  chunk's true length; the exact decomposition it replaced).
* :class:`~tensorflowonspark_tpu.serving.scheduler.Request` /
  :class:`~tensorflowonspark_tpu.serving.scheduler.RequestQueue` — the
  host-side bookkeeping (bounded, closable admission queue).
* :class:`~tensorflowonspark_tpu.serving.scheduler.PagePool` /
  :class:`~tensorflowonspark_tpu.serving.scheduler.PrefixCache` — the
  paged-KV host state: the ref-counted page allocator and the
  shared-prefix radix trie (page-granular, LRU-evicted).
* :class:`~tensorflowonspark_tpu.serving.fleet.ServingFleet` — the
  driver-side replica router: load-aware dispatch over N engines,
  retry-with-backoff on overload, health ejection + cross-replica
  failover replay (stream positions exactly-once), and zero-shed
  :meth:`rolling_swap` (docs/ROBUSTNESS.md §Fleet).
* :class:`~tensorflowonspark_tpu.serving.registry.ModelRegistry` — the
  durable train→serve seam: atomic versioned publish (the checkpoint
  commit-marker protocol), ``watch()``, quarantine, ref-counted GC.
* :class:`~tensorflowonspark_tpu.serving.deploy.DeploymentController`
  — SLO-gated canary rollout: CANARY → VERIFY (greedy parity +
  obs/SLO deltas) → PROMOTE or ROLLBACK+quarantine, zero-shed end to
  end, with :meth:`resume` converging the fleet after a controller
  death (docs/ROBUSTNESS.md §Continuous deployment).
* :class:`~tensorflowonspark_tpu.serving.host.ServingHost` /
  :class:`~tensorflowonspark_tpu.serving.remote.RemoteReplica` — the
  cross-host serving plane: executor-resident engines syncing over the
  rendezvous wire (SHREG/SHSYNC/SHBYE) with driver-side replica
  proxies, so the SAME fleet routes/ejects/failover-replays/swaps
  across process boundaries (docs/ROBUSTNESS.md §Cross-host serving).

Decode-speed stack (docs/PERFORMANCE.md §"Paged KV, prefix cache &
speculative decode"): ``TOS_SERVE_PAGE_SIZE`` pages the KV slab,
``TOS_SERVE_PREFIX_PAGES`` turns on prefix sharing over it, and
``TOS_SERVE_SPEC_DEPTH`` enables self-speculative decoding — each stage
independently gated on bit-parity in ``tests/test_serving.py``.

See docs/PERFORMANCE.md §Serving for the static-vs-continuous batching
story, docs/ROBUSTNESS.md for the failure model and chaos knobs, and
``BENCHMARK.json`` / ``PERF.md`` for the measurements on the chip.
"""

from tensorflowonspark_tpu.serving.engine import (            # noqa: F401
    ENV_SERVE_MAX_QUEUE, ENV_SERVE_MAX_QUEUED_TOKENS, ENV_SERVE_NUM_PAGES,
    ENV_SERVE_PAGE_SIZE, ENV_SERVE_POLL, ENV_SERVE_PREFIX_PAGES,
    ENV_SERVE_SLOTS, ENV_SERVE_SPEC_DEPTH, ENV_SERVE_SPEC_LAYERS,
    ENV_SERVE_TTL, ServingEngine)
from tensorflowonspark_tpu.serving.deploy import (            # noqa: F401
    ENV_DEPLOY_BAKE, ENV_DEPLOY_POLL, ENV_DEPLOY_SLICE,
    ENV_DEPLOY_SPOT_CHECKS, ENV_DEPLOY_SWAP_TIMEOUT,
    ENV_DEPLOY_TTFT_RATIO, ControllerKilled, DeploymentController)
from tensorflowonspark_tpu.serving.host import (              # noqa: F401
    ENV_HOST_BUILD, ENV_HOST_SYNC, ServingHost, build_engine_from_manifest,
    cfg_wire, make_serving_host_main, run_host_thread, start_host_process)
from tensorflowonspark_tpu.serving.remote import (            # noqa: F401
    ENV_HOST_ADMIT, ENV_HOST_CHUNK, ENV_HOST_START, ENV_HOST_TIMEOUT,
    RemoteReplica, RemoteRequest, ServingHostPlane, attach_serving_plane,
    remote_engine_factory, wire_health_probe)
from tensorflowonspark_tpu.serving.fleet import (             # noqa: F401
    ENV_FLEET_ADMIT_TIMEOUT, ENV_FLEET_MAX_FAILOVERS,
    ENV_FLEET_MAX_REPLICAS, ENV_FLEET_POLL, ENV_FLEET_PROBE_FAILS,
    ENV_FLEET_REPLICAS, FleetRequest, Replica, ServingFleet)
from tensorflowonspark_tpu.serving.registry import (          # noqa: F401
    ENV_REGISTRY_KEEP, ENV_REGISTRY_POLL, ModelRegistry)
from tensorflowonspark_tpu.serving.scheduler import (         # noqa: F401
    ENV_SERVE_BUCKETS, DeadlineExceeded, PagePool, PoisonedRequest,
    PrefixCache, Request, RequestCancelled, RequestQueue,
    ServingOverloaded)
from tensorflowonspark_tpu.serving.slots import (             # noqa: F401
    DEFAULT_BUCKETS, EXACT_BUCKETS, SlotDecoder, chunk_plan, padded_plan)
