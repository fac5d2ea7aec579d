"""byteps_tpu: a TPU-native distributed-training communication framework.

A ground-up rebuild of the capabilities of BytePS (reference mounted at
/root/reference; see SURVEY.md) for JAX/XLA on TPU: a Horovod-style
``push_pull`` gradient-synchronization core with tensor partitioning,
priority-based communication scheduling, credit-based pipelining,
cross-barrier overlap, async/elastic modes, and a gradient-compression
engine — driving chunked XLA collectives over the ICI/DCN mesh instead of
NCCL + a ZMQ/RDMA parameter server.

Top-level API mirrors the reference's BytePSBasics surface
(byteps/common/__init__.py in the reference): init/shutdown, rank/size,
push_pull, declare, plus the framework adapters under byteps_tpu.jax and
byteps_tpu.torch.
"""

# The start-up record's first stamp (``metrics_snapshot()["startup"]``,
# common/telemetry.py): a clock read before anything is imported, and one
# more as the last statement — nothing else happens at import.
import time as _time

_IMPORT_BEGIN = _time.monotonic()

__version__ = "0.1.0"

from byteps_tpu.core.api import (  # noqa: F401
    init,
    shutdown,
    suspend,
    resume,
    rank,
    size,
    local_rank,
    local_size,
    push_pull,
    push_pull_async,
    poll,
    synchronize,
    declare,
    declare_update,
    push_pull_update,
    push_pull_update_async,
    get_pushpull_speed,
    membership_epoch,
    metrics_snapshot,
    cluster_metrics,
    start_serving,
    start_serving_tier,
    durable_kv_store,
)
from byteps_tpu.server import (  # noqa: F401
    KVStore,
    PullClient,
    ServingPlane,
    ServingTier,
    SnapshotStore,
)

_IMPORT_END = _time.monotonic()
