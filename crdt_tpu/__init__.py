"""crdt_tpu — a TPU-native CRDT framework.

A from-scratch rebuild of the capability surface of ypear/crdt
(reference: /root/reference/crdt.js) designed TPU-first:

- The CRDT engine itself (Yjs semantics: last-writer-wins maps, YATA
  sequence ordering, state vectors, delete sets, v1 binary update codec)
  implemented on a columnar struct-of-arrays op model so the delta-merge
  hot path runs as vectorized JAX/Pallas kernels on TPU
  (reference delegates this to the `yjs` npm dep, package.json:14).
- A replica-sync protocol matching the router-cache contract of
  crdt.js:234-317 (ready/sync anti-entropy handshake, per-peer state
  vectors) with an in-process loopback router for N-replica tests and
  XLA collectives as the on-device gossip fabric.
- A persistence layer matching the LevelDB update-log keyspace of
  crdt.js:5-141, backed by a native C++ ordered-KV store, plus snapshot
  compaction (absent in the reference; SURVEY.md Q3).
- The public batched API of crdt.js:661-702 (map/set/del/array/insert/
  push/unshift/cut/execBatch/observe), with the reference's behavioral
  defects D1-D7 (SURVEY.md §6) fixed.
"""

__version__ = "0.1.0"

from crdt_tpu.utils.heap import pin_heap

# before the package's first large allocation: one allocator policy for
# every process that imports the package (crdt_tpu/utils/heap.py)
pin_heap()

from crdt_tpu.core.ids import ID, StateVector, DeleteSet  # noqa: E402,F401


def __getattr__(name):
    # lazy subpackage access without importing jax at package import
    if name in ("ReplicaFleet", "FleetStep", "ReplayResult", "replay_trace"):
        from crdt_tpu import models

        return getattr(models, name)
    if name == "Tracer":
        from crdt_tpu.utils import Tracer

        return Tracer
    raise AttributeError(name)
