"""garage_tpu — a TPU-native, S3-compatible, geo-distributed object store.

Re-architecture of the capability surface of Garage (reference:
/root/reference, deuxfleurs-org/garage): no-consensus placement from a
CRDT-replicated cluster layout, read/write-quorum consistency, CRDT merge +
Merkle anti-entropy convergence, content-addressed block storage — plus a
TPU-native compute plane: batched GF(2^8) Reed-Solomon erasure coding and
batched BLAKE3 integrity hashing running on XLA/TPU behind a BlockCodec
interface (`replication_mode = "ec:k:m"`).

Layer map (mirrors reference workspace crates, SURVEY.md §1):
  utils/   — ids, hashes, CRDTs, versioned migration, config, workers
  db/      — metadata KV abstraction (sqlite / memory engines)
  net/     — authenticated asyncio TCP mesh with typed RPC + streams + QoS
  rpc/     — membership, cluster layout (min-cost-flow assignment), quorum RPC
  table/   — replicated CRDT table engine (merkle anti-entropy, GC)
  block/   — content-addressed block store, resync/scrub, BlockCodec seam
  model/   — table schemas + composition root (S3, K2V, buckets, keys)
  api/     — S3 / K2V / admin HTTP APIs, SigV4
  web/     — static-website server
  cli/     — daemon + operator CLI
  ops/     — JAX/XLA kernels: GF(2^8) bitplane matmul EC, batched BLAKE3
  parallel/— device-mesh sharding for pod-level repair fan-out
"""

__version__ = "0.1.0"

# Optional-dependency fallbacks (zlib-backed `zstandard` shim, etc.) must
# be installed before any submodule import pulls the real names.
from .utils import depcompat as _depcompat  # noqa: E402,F401
