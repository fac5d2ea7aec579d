"""Typed configuration for the TPU-native BytePS rebuild.

The reference configures itself through ~30 ad-hoc environment variables read
with ``getenv`` at init time (reference ``docs/env.md``, ``common/global.cc``).
Here they are centralized into one typed, testable config object.  Environment
variable names are kept BYTEPS_*-compatible so launcher scripts written for the
reference keep working where the knob still makes sense on TPU.

Reference parity map (reference file:line):
  - BYTEPS_PARTITION_BYTES        global.cc:42,134-144  -> partition_bytes
  - BYTEPS_SCHEDULING_CREDIT      scheduled_queue.cc:35 -> scheduling_credit
  - BYTEPS_MIN_COMPRESS_BYTES     global.cc:43,137-139  -> min_compress_bytes
  - BYTEPS_LOG_LEVEL              logging.cc            -> log_level
  - BYTEPS_TRACE_ON/START/END/DIR global.cc:113-124     -> trace_*
  - BYTEPS_TELEMETRY_ON           global.cc:697-752     -> telemetry_on
  - BYTEPS_ENABLE_ASYNC           server.cc:417-419     -> enable_async
  - BYTEPS_FORCE_DISTRIBUTED     global.cc              -> force_distributed
  - DMLC_NUM_WORKER / DMLC_WORKER_ID (docs/env.md:11-17) -> num_hosts / host_id
  - BYTEPS_LOCAL_RANK/LOCAL_SIZE  launch.py:180-206     -> local_rank/local_size
  - BYTEPS_SERVER_ENGINE_THREAD   server.cc:407-439     -> server_engine_threads
  - BYTEPS_SERVER_ENABLE_SCHEDULE queue.h:31-104        -> server_enable_schedule
  - BYTEPS_SERVER_DEBUG_KEY       server.cc:421-425     -> server_debug_key
  - BYTEPS_KEY_HASH_FN            global.cc:159-176     -> key_hash_fn
  - BYTEPS_DEBUG_SAMPLE_TENSOR    core_loops.cc:37-67   -> debug_sample_tensor

Knobs that only exist because of the reference's CPU/GPU/NIC split (PCIe switch
size, NCCL rings, NUMA pinning, shm paths) have no TPU meaning and are
intentionally absent; unknown BYTEPS_* vars are ignored.
"""

from __future__ import annotations

import dataclasses
import os
import tempfile
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}")


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.strip().lower() not in ("0", "false", "no", "off", "")


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name} must be a number, got {v!r}")


# Page size used for alignment of partition bounds; the reference aligns
# partition bounds to its Align() rule (common.h:281-285).  On TPU we align to
# 512 lanes * 4 bytes so chunk boundaries respect (8,128) tiling of f32.
ALIGN_BYTES = 4096

# Reference default for BYTEPS_PARTITION_BYTES (global.cc:134-144).  ONE
# copy: the dataclass default, the env fallback, and the auto-tuner's
# pin detection (__post_init__) must agree, or changing the default
# would silently pin the planner.
PARTITION_BYTES_DEFAULT = 4096000


def _default_trace_dir() -> str:
    """Default trace output location when ``BYTEPS_TRACE_DIR`` is unset:
    a stable per-USER tmp subdir (the Tracer mkdirs it at flush).  The
    uid suffix matters on shared hosts: a bare /tmp/byteps_traces owned
    by the first user to trace would make every other user's best-effort
    flush fail silently."""
    try:
        who = str(os.getuid())
    except AttributeError:  # no getuid (non-POSIX)
        who = os.environ.get("USERNAME") or os.environ.get("USER") or "user"
    return os.path.join(tempfile.gettempdir(), f"byteps_traces_{who}")


def trace_dir_from_env() -> str:
    """``BYTEPS_TRACE_DIR`` if set and non-empty, else the per-user tmp
    default — the ONE derivation shared by the Config field default,
    ``Config.from_env`` and ``tools/bps_trace.py`` (a set-but-EMPTY var,
    e.g. a launch script's unset ``$VAR``, must not send traces to cwd)."""
    return os.environ.get("BYTEPS_TRACE_DIR") or _default_trace_dir()


def _default_flight_dir() -> str:
    """Default crash-dump location when ``BYTEPS_FLIGHT_DIR`` is unset:
    a stable per-USER tmp subdir, mirroring :func:`_default_trace_dir`.
    Dumping to cwd was the old default and it leaks ``bps_flight_*.json``
    files into whatever directory the process happened to start in
    (source trees included)."""
    try:
        who = str(os.getuid())
    except AttributeError:  # no getuid (non-POSIX)
        who = os.environ.get("USERNAME") or os.environ.get("USER") or "user"
    return os.path.join(tempfile.gettempdir(), f"byteps_flight_{who}")


def flight_dir_from_env() -> str:
    """``BYTEPS_FLIGHT_DIR`` if set and non-empty, else the per-user tmp
    default — the ONE derivation shared by the Config field default and
    ``Config.from_env`` (a set-but-EMPTY var must not send crash dumps
    to cwd)."""
    return os.environ.get("BYTEPS_FLIGHT_DIR") or _default_flight_dir()


def _parse_trace_sample(spec: str) -> int:
    """``BYTEPS_TRACE_SAMPLE`` grammar: '' / '0' = off; 'N' or '1/N' =
    capture every Nth push.  Lives here (not common/tracing.py) so
    Config validation needs no import of the tracer."""
    s = (spec or "").strip()
    if not s or s == "0":
        return 0
    if s.startswith("1/"):
        s = s[2:]
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"BYTEPS_TRACE_SAMPLE must be '1/N' or an integer N (0 = "
            f"off), got {spec!r}") from None
    if n < 0:
        raise ValueError(f"BYTEPS_TRACE_SAMPLE must be >= 0, got {spec!r}")
    return n


@dataclasses.dataclass
class Config:
    """Process-wide configuration, normally built once via :func:`get_config`."""

    # --- topology / bootstrap (DMLC-compatible names) ---
    num_hosts: int = 1              # DMLC_NUM_WORKER
    host_id: int = 0                # DMLC_WORKER_ID
    local_rank: int = 0             # BYTEPS_LOCAL_RANK (one proc per host on TPU)
    local_size: int = 1             # BYTEPS_LOCAL_SIZE
    coordinator_address: Optional[str] = None  # DMLC_PS_ROOT_URI:PORT equivalent
    force_distributed: bool = False  # BYTEPS_FORCE_DISTRIBUTED
    dcn_size: int = dataclasses.field(
        default_factory=lambda: _env_int("BYTEPS_DCN_SIZE", 0))
    #                                  BYTEPS_DCN_SIZE: ICI slices in the
    #                                  mesh (constructs the (dcn, ici)
    #                                  axes); 0 = derive from
    #                                  jax.process_count().  Env-backed
    #                                  default even for explicit
    #                                  Config(...) constructions — the
    #                                  mesh shape must follow the
    #                                  launcher's environment, not
    #                                  whichever cwd/env a Config()
    #                                  happened to be built under (same
    #                                  rationale as flight_dir)

    # --- partitioning / scheduling ---
    partition_bytes: int = PARTITION_BYTES_DEFAULT  # BYTEPS_PARTITION_BYTES
    scheduling_credit: int = 0       # BYTEPS_SCHEDULING_CREDIT; 0 = unlimited window
    enable_priority: bool = True     # priority ordering of chunk dispatch
    group_size: int = 4              # BYTEPS_GROUP_SIZE: tasks the dispatcher
    #                                  pops at a time where they are not a
    #                                  buffer-mode tensor's chunks: parts-mode
    #                                  chunks merged ACROSS tensors into one
    #                                  program, compressed chunks (reference
    #                                  BYTEPS_NCCL_GROUP_SIZE batching,
    #                                  nccl_manager.cc:130-134); a count: 0 is
    #                                  read as 1, negative is rejected.  It no
    #                                  longer caps a multi-chunk tensor's own
    #                                  run: that is one program per bucket's
    #                                  worth of queued columns, whatever this
    #                                  says (core/engine.py _unit_layout)
    autotune: bool = True            # BYTEPS_AUTOTUNE: online chunk-size /
    #                                  credit-window planner
    #                                  (common/scheduler.py ChunkPlanner).
    #                                  Pinning an explicit
    #                                  BYTEPS_PARTITION_BYTES or
    #                                  BYTEPS_SCHEDULING_CREDIT (env or a
    #                                  non-default Config value) disables
    #                                  tuning of that knob for
    #                                  reproducibility; multi-process runs
    #                                  never tune (SPMD processes must
    #                                  dispatch identical programs).
    buffer_min_bytes: int = 1 << 20  # BYTEPS_BUFFER_MIN_BYTES: single-chunk
    #                                  uncompressed tensors at or above this
    #                                  ride the reduce-scatter accumulator
    #                                  path (one RS program + one assemble)
    #                                  instead of the flat-psum parts path;
    #                                  smaller tensors keep parts mode, whose
    #                                  cross-tensor group batching wins for
    #                                  bursts of small gradients
    deferred_gather: bool = True     # BYTEPS_DEFERRED_GATHER: buffer-mode
    #                                  assembly emits the reduced tensor
    #                                  block-sharded over the mesh (XLA
    #                                  materializes the all-gather only
    #                                  where a consumer needs replicated
    #                                  values) when the output shape admits
    #                                  it; 0 = always replicate at assembly
    sharded_update: bool = False     # BYTEPS_SHARDED_UPDATE: pull leg
    #                                  returns the owner-updated PARAMETER
    #                                  update instead of the merged
    #                                  gradient — the reduce-scatter shard
    #                                  stays resident on its owner, a
    #                                  per-shard optax update (flat-shard
    #                                  optimizer state, AOT-warmed at
    #                                  declare time) runs before the
    #                                  all-gather, and assembly reuses the
    #                                  deferred-gather block-sharded emit.
    #                                  Steady-state wire bytes drop from
    #                                  2N (RS + AG of gradients) to
    #                                  N + N/R (core/sharded_update.py,
    #                                  docs/performance.md)
    sharded_update_fused: bool = False  # BYTEPS_SHARDED_UPDATE_FUSED:
    #                                  dispatch the whole per-shard
    #                                  optimizer step as ONE fused XLA
    #                                  program instead of the default
    #                                  eager op-by-op step wrapped in
    #                                  jitted layout legs. Faster (one
    #                                  dispatch per tensor per step) but
    #                                  XLA's FMA contraction makes the
    #                                  trajectory drift from the
    #                                  unsharded path by ~1 ulp/element
    #                                  per step; the default mode is
    #                                  bit-for-bit (docs/performance.md)
    sharded_param_codec: str = ""    # BYTEPS_SHARDED_PARAM_CODEC:
    #                                  optional codec for the parameter
    #                                  all-gather leg under sharded
    #                                  update, e.g. "onebit" or
    #                                  "randomk:64" ("" = full precision;
    #                                  "auto" = planner picks per size
    #                                  bucket). Gated by the same
    #                                  compress_error_ceiling quality
    #                                  gate as the gradient ladder

    # --- compression ---
    min_compress_bytes: int = 65536  # BYTEPS_MIN_COMPRESS_BYTES
    compress_autotune: bool = False  # BYTEPS_COMPRESS_AUTOTUNE: the
    #                                  planner's COMPRESSOR ladder — per
    #                                  tensor-size bucket, explore
    #                                  none/onebit/randomk/topk (with
    #                                  error feedback) round-robin and
    #                                  lock the fastest candidate whose
    #                                  codec-golden gradient error stays
    #                                  under compress_error_ceiling.
    #                                  Off by default (changing a codec
    #                                  changes gradient values, so the
    #                                  operator opts in); tensors pushed
    #                                  with explicit compression= kwargs
    #                                  are pinned and never tuned, and
    #                                  multi-process runs never tune
    #                                  (SPMD lockstep) — the same pin
    #                                  semantics as the chunk planner
    compress_error_ceiling: float = 0.55
    #                                  BYTEPS_COMPRESS_ERROR_CEILING:
    #                                  max codec-golden gradient error
    #                                  (compression.registry.golden_error
    #                                  — EF-corrected residual mass over
    #                                  8 repeated pushes) a ladder
    #                                  candidate may carry and still be
    #                                  explored; quality gate of the
    #                                  wall-time race

    # --- native core ---
    use_native: bool = True          # BYTEPS_NATIVE: C++ host reducer /
    #                                  partition arithmetic / Elias coder /
    #                                  CRC32C (server/, compression/elias.py,
    #                                  common/integrity.py); the engine's
    #                                  chunk queue is Python either way
    use_pallas: bool = True          # BYTEPS_PALLAS: TPU kernels for hot ops

    # --- modes ---
    enable_async: bool = False       # BYTEPS_ENABLE_ASYNC (async-PS weight deltas)

    # --- server engine (async-PS merge; reference server.cc) ---
    server_engine_threads: int = 4   # BYTEPS_SERVER_ENGINE_THREAD
    server_enable_schedule: bool = False  # BYTEPS_SERVER_ENABLE_SCHEDULE
    server_debug_key: str = ""       # BYTEPS_SERVER_DEBUG_KEY
    key_hash_fn: str = "djb2"        # BYTEPS_KEY_HASH_FN
    enable_mixed_mode: bool = False  # BYTEPS_ENABLE_MIXED_MODE: split key
    #                                  space between non-colocated and
    #                                  colocated servers (ServerAssigner,
    #                                  reference global.cc:566-596)
    mixed_mode_bound: int = 101      # BYTEPS_MIXED_MODE_BOUND (must be
    #                                  >= the server count)
    debug_sample_tensor: str = ""    # BYTEPS_DEBUG_SAMPLE_TENSOR substring

    # --- failure detection (utils/failure_detector.py) ---
    heartbeat_on: bool = False       # BYTEPS_HEARTBEAT_ON: auto-arm at init
    heartbeat_interval_s: float = 1.0   # BYTEPS_HEARTBEAT_INTERVAL
    heartbeat_timeout_s: float = 30.0   # BYTEPS_HEARTBEAT_TIMEOUT
    failure_exit_code: int = 17      # BYTEPS_FAILURE_EXIT_CODE: the
    #                                  detector's "restartable" exit; the
    #                                  launchers' --restart supervision
    #                                  treats exactly this code as worth
    #                                  restarting (a crash exits 1)
    sync_deadline_s: float = 0.0     # BYTEPS_SYNC_DEADLINE_S: per-unit
    #                                  deadline in the engine's sync loop
    #                                  (0 = off).  A unit blocked past it
    #                                  (the wedged-collective TPU failure
    #                                  mode: a dead peer blocks survivors
    #                                  silently) is reported as data-path
    #                                  failure evidence to the installed
    #                                  failure action (shrink/recover);
    #                                  os._exit stays the escalation of
    #                                  last resort when nothing is
    #                                  installed

    # --- gray-failure tolerance (utils/slowness.py, docs/gray_failures.md) ---
    straggler_policy: str = "wait"   # BYTEPS_STRAGGLER_POLICY: what the
    #                                  stack does about a slow-but-alive
    #                                  rank — wait (observe only: scores
    #                                  exported, nothing acts) | hedge
    #                                  (serving pulls fire a backup to a
    #                                  replica after the adaptive hedge
    #                                  delay) | demote (the membership
    #                                  bus moves a sustained straggler
    #                                  onto the probation list via
    #                                  shrink-to-survivors; it rejoins
    #                                  at a step boundary once healthy)
    slowness_phi: float = 8.0        # BYTEPS_SLOWNESS_PHI: phi-accrual
    #                                  suspicion threshold above which a
    #                                  peer counts as slow (8 = one in
    #                                  10^8 under healthy behavior)
    slowness_window: int = 64        # BYTEPS_SLOWNESS_WINDOW: latency
    #                                  samples retained per (site, peer)
    straggler_demote_after: int = 3  # BYTEPS_STRAGGLER_DEMOTE_AFTER:
    #                                  consecutive slow step barriers
    #                                  before the bus demotes (hysteresis
    #                                  against one-off stalls)
    straggler_min_lag_s: float = 0.25
    #                                  BYTEPS_STRAGGLER_MIN_LAG: absolute
    #                                  floor a rank's step-barrier lag
    #                                  must exceed to count as slow — the
    #                                  phi score self-calibrates, so
    #                                  without a floor microsecond jitter
    #                                  in an otherwise-idle world could
    #                                  score "astronomical"
    serve_hedge_ms: float = 0.0      # BYTEPS_SERVE_HEDGE_MS: fixed hedge
    #                                  delay for serving pulls; 0 =
    #                                  adaptive (p99 of recent winning
    #                                  pull latencies, the tail-tolerant
    #                                  default)

    # --- elastic membership (fault/membership.py) ---
    elastic: bool = False            # BYTEPS_ELASTIC: elastic-membership
    #                                  mode — survivors shrink in place and
    #                                  the launcher restarts only the dead
    #                                  rank (with BYTEPS_ELASTIC_REJOIN=1)
    membership_port: int = 0         # BYTEPS_MEMBERSHIP_PORT: membership
    #                                  bus TCP port on the coordinator host
    #                                  (0 = DMLC_PS_ROOT_PORT + 2)
    membership_hosts: str = ""       # BYTEPS_MEMBERSHIP_HOSTS: per-rank
    #                                  "host[:port]" list (comma-separated,
    #                                  indexed by rank) making the bus
    #                                  address VIEW-aware on multi-host:
    #                                  after a coordinator change the bus
    #                                  is re-resolved to the new
    #                                  coordinator's entry instead of the
    #                                  static env-derived address; empty =
    #                                  single fixed address (single-host
    #                                  failover re-binds the same one)
    membership_rendezvous_timeout_s: float = 10.0
    #                                  BYTEPS_MEMBERSHIP_RENDEZVOUS_TIMEOUT:
    #                                  how long the shrink rendezvous waits
    #                                  for every proposed survivor before
    #                                  dropping non-responders (the
    #                                  double-failure window)
    membership_sync_timeout_s: float = 60.0
    #                                  BYTEPS_MEMBERSHIP_SYNC_TIMEOUT: step
    #                                  barrier quorum window; a member
    #                                  missing past it is failure evidence
    bus_retries: int = 64            # BYTEPS_BUS_RETRIES: bus-client
    #                                  attempt ceiling (membership sync /
    #                                  shrink hello) — how long a worker
    #                                  rides out a coordinator failover
    #                                  before escalating; detection-vs-
    #                                  patience dial, was a hardcoded 64

    # --- gossip membership (fault/gossip.py) ---
    gossip_on: bool = False          # BYTEPS_GOSSIP_ON: SWIM-style
    #                                  gossip membership plane — per-rank
    #                                  table (incarnation/state/heartbeat)
    #                                  anti-entropy over the bus, and
    #                                  quorum-gated world agreement: a
    #                                  shrink commits only with a strict
    #                                  majority of the last agreed world
    #                                  reachable; the minority parks
    gossip_interval_s: float = 0.2   # BYTEPS_GOSSIP_INTERVAL_S:
    #                                  anti-entropy exchange period
    gossip_fanout: int = 3           # BYTEPS_GOSSIP_FANOUT: random peers
    #                                  contacted per gossip period (k)
    gossip_suspect_s: float = 1.0    # BYTEPS_GOSSIP_SUSPECT_S: no
    #                                  heartbeat progress for this long
    #                                  marks a rank suspect (refutable
    #                                  via incarnation bump)
    gossip_dead_s: float = 3.0       # BYTEPS_GOSSIP_DEAD_S: suspect for
    #                                  this long (beyond suspect onset)
    #                                  marks a rank dead; must exceed
    #                                  gossip_suspect_s

    # --- parameter serving (server/serving.py, server/serve_client.py) ---
    serve_replicas: int = 1          # BYTEPS_SERVE_REPLICAS: total shards
    #                                  a hot key is readable from (primary
    #                                  + N-1 replica mirrors); 1 = no
    #                                  replication, every pull is
    #                                  primary-served
    serve_retention: int = 8         # BYTEPS_SERVE_RETENTION: snapshots
    #                                  kept per SnapshotStore ring; a
    #                                  client whose last snapshot_id aged
    #                                  past retention falls back to a
    #                                  full-snapshot pull
    serve_hot_keys: int = 8          # BYTEPS_SERVE_HOT_KEYS: top-N keys
    #                                  (by pull-count histogram) eligible
    #                                  for replica mirroring; 0 disables
    #                                  hotness tracking's replica rebuild
    serve_max_staleness_s: float = 0.5
    #                                  BYTEPS_SERVE_MAX_STALENESS: default
    #                                  PullClient staleness bound —
    #                                  cache younger than this serves
    #                                  locally, older triggers a refresh
    serve_cut_interval_s: float = 0.05
    #                                  BYTEPS_SERVE_CUT_INTERVAL: minimum
    #                                  seconds between write-triggered
    #                                  snapshot cuts when a SnapshotStore
    #                                  subscribes to its KVStore (0 = cut
    #                                  on every consistent write point)

    # --- distributed serving tier (server/serving_tier.py) ---
    serve_tier_vnodes: int = 64      # BYTEPS_SERVE_TIER_VNODES: virtual
    #                                  nodes per serving host on the
    #                                  consistent-hash ring — more vnodes
    #                                  = smoother arc shares, slightly
    #                                  slower membership churn
    serve_tier_replicas: int = 2     # BYTEPS_SERVE_TIER_REPLICAS: hosts
    #                                  each key is shipped to (the owner
    #                                  + N-1 ring successors); reads fail
    #                                  over along the same arc
    serve_tier_rate: float = 0.0     # BYTEPS_SERVE_TIER_RATE: per-host
    #                                  admission token-bucket refill,
    #                                  pulls/s (0 = unlimited — only the
    #                                  queue watermark sheds)
    serve_tier_burst: float = 0.0    # BYTEPS_SERVE_TIER_BURST: token
    #                                  bucket capacity (0 = one second
    #                                  of refill)
    serve_tier_queue_high: int = 64  # BYTEPS_SERVE_TIER_QUEUE_HIGH:
    #                                  in-flight pulls per host above
    #                                  which new pulls shed to bounded
    #                                  staleness instead of queueing
    serve_tier_ttl_s: float = 10.0   # BYTEPS_SERVE_TIER_TTL: serving-
    #                                  host directory registration TTL;
    #                                  a host that stops re-registering
    #                                  ages out of the ring within it
    serve_tier_min_hosts: int = 1    # BYTEPS_SERVE_TIER_MIN_HOSTS:
    #                                  autoscaler floor
    serve_tier_max_hosts: int = 8    # BYTEPS_SERVE_TIER_MAX_HOSTS:
    #                                  autoscaler ceiling
    serve_tier_cooldown_s: float = 5.0
    #                                  BYTEPS_SERVE_TIER_COOLDOWN:
    #                                  minimum seconds between autoscaler
    #                                  decisions (flap damping)
    serve_tier_bus: str = ""         # BYTEPS_SERVE_TIER_BUS:
    #                                  "host:port" of the membership bus
    #                                  carrying the serving-host
    #                                  directory (serve_host.py reads it
    #                                  to register; empty = standalone)

    # --- fleet reconciler (launcher/reconciler.py, docs/serving.md) ---
    reconcile_interval_s: float = 0.5
    #                                  BYTEPS_RECONCILE_INTERVAL: seconds
    #                                  between reconcile passes (watch
    #                                  the directory, converge actual
    #                                  fleet to the serve_scale target)
    reconcile_flap_limit: int = 3    # BYTEPS_RECONCILE_FLAP_LIMIT:
    #                                  crashes inside the flap window
    #                                  after which a host id is BANNED
    #                                  (directory ban, arc re-homed to a
    #                                  fresh id) instead of restarted
    reconcile_flap_window_s: float = 30.0
    #                                  BYTEPS_RECONCILE_FLAP_WINDOW:
    #                                  sliding window (seconds) the flap
    #                                  limit counts crashes inside
    reconcile_drain_deadline_s: float = 10.0
    #                                  BYTEPS_RECONCILE_DRAIN_DEADLINE:
    #                                  seconds a DRAINING host gets to
    #                                  finish in-flight pulls and
    #                                  unregister before the reconciler
    #                                  escalates to SIGTERM/kill
    reconcile_ban_s: float = 30.0    # BYTEPS_RECONCILE_BAN: directory
    #                                  ban length for a flapping host id
    #                                  (refuses re-registration, so the
    #                                  crash-looper cannot rejoin the
    #                                  ring under the same identity)

    # --- TCP transport (comm/transport.py, docs/transport.md) ---
    transport_hosts: str = ""        # BYTEPS_TRANSPORT_HOSTS: per-rank
    #                                  "host[:port]" list (comma-separated,
    #                                  indexed by rank) naming where each
    #                                  rank's transport server listens —
    #                                  the data-plane analog of
    #                                  BYTEPS_MEMBERSHIP_HOSTS; empty =
    #                                  derive 127.0.0.1 + port base
    transport_port_base: int = 0     # BYTEPS_TRANSPORT_PORT_BASE: rank
    #                                  R's transport server listens on
    #                                  port_base + R when the host map
    #                                  is unset; 0 = ephemeral bind (the
    #                                  peer then needs the host map or
    #                                  an explicit address)
    transport_connect_timeout_s: float = 5.0
    #                                  BYTEPS_TRANSPORT_CONNECT_TIMEOUT:
    #                                  per-attempt TCP connect timeout;
    #                                  the supervisor retries with
    #                                  full-jitter backoff until closed
    transport_send_deadline_s: float = 10.0
    #                                  BYTEPS_TRANSPORT_SEND_DEADLINE:
    #                                  per-request reply deadline — a
    #                                  send unanswered past it surfaces
    #                                  as integrity.AckLost (the
    #                                  existing retry machinery), NEVER
    #                                  a hang
    transport_keepalive_s: float = 5.0
    #                                  BYTEPS_TRANSPORT_KEEPALIVE: idle
    #                                  keepalive interval per connection
    #                                  (a dead-but-ESTABLISHED socket is
    #                                  discovered within ~2 intervals);
    #                                  0 = no keepalives
    transport_max_inflight: int = 64 << 20
    #                                  BYTEPS_TRANSPORT_MAX_INFLIGHT:
    #                                  bound on unacknowledged request
    #                                  bytes per connection; past it the
    #                                  sender blocks (backpressure into
    #                                  the pushing thread — which holds
    #                                  the scheduler credit it consumed,
    #                                  so the credit window upstream
    #                                  throttles too), counted in
    #                                  transport.backpressure_stalls

    # --- data integrity (common/integrity.py) ---
    integrity_on: bool = True        # BYTEPS_INTEGRITY: CRC32C-checksummed
    #                                  envelopes + non-finite quarantine on
    #                                  every host-crossing payload (server
    #                                  pushes, KV deltas, membership bus,
    #                                  rejoin state); 0 = zero-overhead off
    integrity_loopback: bool = True  # BYTEPS_INTEGRITY_LOOPBACK: skip the
    #                                  seal->CRC->open round-trip on
    #                                  in-process hops when no chaos is
    #                                  armed (a CRC over the caller's own
    #                                  memory verifies bytes against
    #                                  themselves); the receiver still
    #                                  snapshots the contribution — one
    #                                  plain copy instead of frame build +
    #                                  two CRC passes; 0 forces the full
    #                                  envelope on every hop
    integrity_max_retransmits: int = 3
    #                                  BYTEPS_INTEGRITY_MAX_RETRANSMITS:
    #                                  bounded retransmit budget after a
    #                                  CRC NACK (from the sender's source
    #                                  copy; past it the push fails loudly)
    nonfinite_policy: str = "raise"  # BYTEPS_NONFINITE_POLICY: what a
    #                                  receiver does with NaN/Inf
    #                                  contributions/merges —
    #                                  raise | skip (quarantine the round,
    #                                  republish the previous merge) | zero
    bus_max_frame: int = 1 << 30     # BYTEPS_BUS_MAX_FRAME: membership-bus
    #                                  frame-size clamp; a corrupt length
    #                                  prefix fails the connection instead
    #                                  of parking a multi-petabyte recv

    # --- lock-order witness (common/lock_witness.py) ---
    lock_witness: bool = dataclasses.field(
        default_factory=lambda: _env_bool("BYTEPS_LOCK_WITNESS", False))
    #                                  BYTEPS_LOCK_WITNESS: wrap the
    #                                  high-traffic named locks (KV
    #                                  store, scheduler, planner,
    #                                  serving, membership bus, flight
    #                                  recorder, metrics registry) in a
    #                                  runtime acquisition-order witness
    #                                  that raises LockOrderError on a
    #                                  cycle (FreeBSD WITNESS style).
    #                                  Read at lock CONSTRUCTION time:
    #                                  witness_enabled() consults the
    #                                  INSTALLED config first (so
    #                                  set_config(Config(
    #                                  lock_witness=True)) arms every
    #                                  lock built after it), falling
    #                                  back to the env var for locks
    #                                  built before any config exists
    #                                  (module-level singletons like
    #                                  the metrics registry are only
    #                                  witnessed via the env var).  The
    #                                  env-backed default keeps an
    #                                  explicit Config(...) under the
    #                                  chaos lanes armed.  See
    #                                  docs/dev_invariants.md

    # --- fault injection (fault/injector.py) ---
    fault_spec: str = ""             # BYTEPS_FAULT_SPEC: chaos schedule
    #                                  (kill:rank=1:step=40, delay:site=dcn:
    #                                  p=0.01:ms=200, ...); validated
    #                                  eagerly at init(); empty = disabled
    #                                  (zero-overhead fast path)
    fault_seed: int = 0              # BYTEPS_FAULT_SEED: same spec + seed
    #                                  => identical injection schedule

    # --- durable state plane (server/wal.py) ---
    durable_dir: str = ""            # BYTEPS_DURABLE_DIR: root directory
    #                                  for the crash-consistent state
    #                                  plane (WAL segments + atomic
    #                                  snapshot cuts).  Empty = durability
    #                                  OFF (the in-memory-only behavior
    #                                  every release before ISSUE 19
    #                                  had); set = KVStore mutations are
    #                                  journaled and serve hosts persist
    #                                  their committed arc for
    #                                  restart-in-place
    wal_fsync: str = "always"        # BYTEPS_WAL_FSYNC: durability/
    #                                  latency policy — "always" fsyncs
    #                                  every append (crash loses nothing
    #                                  acked), "interval" fsyncs at most
    #                                  every wal_fsync_interval_s (crash
    #                                  loses at most one interval),
    #                                  "off" never fsyncs (OS page cache
    #                                  decides; torn tails still detected
    #                                  at replay, never trusted)
    wal_fsync_interval_s: float = 0.05
    #                                  BYTEPS_WAL_FSYNC_INTERVAL: max
    #                                  seconds between fsyncs under the
    #                                  "interval" policy
    wal_segment_bytes: int = 4 << 20
    #                                  BYTEPS_WAL_SEGMENT_BYTES: segment
    #                                  roll size — replay truncation and
    #                                  retention pruning operate on whole
    #                                  segments
    wal_retain_snapshots: int = 2    # BYTEPS_WAL_RETAIN: durable cuts
    #                                  kept on disk; older cuts and the
    #                                  WAL segments they cover are pruned

    # --- retry/backoff (common/retry.py) ---
    restart_limit: int = 0           # BYTEPS_RESTART_LIMIT: launcher
    #                                  restarts per worker (0 = none)
    retry_max_attempts: int = 3      # BYTEPS_RETRY_MAX_ATTEMPTS
    retry_base_delay_s: float = 0.1  # BYTEPS_RETRY_BASE_DELAY (seconds;
    #                                  doubles per attempt, full jitter)
    retry_max_delay_s: float = 2.0   # BYTEPS_RETRY_MAX_DELAY (backoff cap)
    retry_deadline_s: float = 60.0   # BYTEPS_RETRY_DEADLINE (total budget
    #                                  across attempts)

    # --- observability ---
    log_level: str = "WARNING"       # BYTEPS_LOG_LEVEL
    trace_on: bool = False           # BYTEPS_TRACE_ON
    trace_start_step: int = 10       # BYTEPS_TRACE_START_STEP
    trace_end_step: int = 20         # BYTEPS_TRACE_END_STEP
    trace_dir: str = dataclasses.field(
        default_factory=lambda: trace_dir_from_env())
    #                                  BYTEPS_TRACE_DIR: trace output
    #                                  directory.  Default is a tmp
    #                                  subdir, NOT cwd — bench/chaos
    #                                  runs from the repo root used to
    #                                  litter it with per-pid
    #                                  bps_trace_rank*.json files.  The
    #                                  env var backs the default even
    #                                  for explicit Config(...)
    #                                  constructions (a sampled trace
    #                                  must land where the operator or
    #                                  harness pointed, same rationale
    #                                  as flight_dir)
    trace_jax: bool = False          # BYTEPS_TRACE_JAX (device profiler)
    trace_sample: str = ""           # BYTEPS_TRACE_SAMPLE: '1/N' (or a
    #                                  bare N) keeps a sampled causal
    #                                  span stream live in production —
    #                                  every Nth push is captured end to
    #                                  end (enqueue → dispatch → wire →
    #                                  merge → retire, flow-linked) with
    #                                  NO step window armed; '' / '0' =
    #                                  off.  Resolved to trace_sample_n.
    trace_sample_n: int = -1         # resolved form of trace_sample
    #                                  (__post_init__); -1 = derive
    trace_capacity: int = 65536      # BYTEPS_TRACE_CAPACITY: in-memory
    #                                  event-buffer bound; past it the
    #                                  buffer spills to an ndjson side
    #                                  file (folded back in at flush) and
    #                                  unspillable events are counted in
    #                                  trace.events_dropped, never heap
    clock_sync_samples: int = 5      # BYTEPS_CLOCK_SYNC_SAMPLES: ping
    #                                  round-trips used to estimate this
    #                                  rank's wall-clock offset against
    #                                  the membership coordinator (best =
    #                                  min-RTT sample, NTP style) for the
    #                                  merged cluster timeline; 0 = off
    telemetry_on: bool = True        # BYTEPS_TELEMETRY_ON
    obs_port: Optional[int] = None   # BYTEPS_OBS_PORT: per-process HTTP
    #                                  observability endpoint (/metrics,
    #                                  /healthz, /debug/state); unset =
    #                                  off, 0 = OS-assigned ephemeral
    #                                  port.  Survives suspend/resume —
    #                                  one server per process lifetime.
    obs_host: str = "127.0.0.1"      # BYTEPS_OBS_HOST: bind address for
    #                                  the obs endpoint (0.0.0.0 to
    #                                  expose cluster-wide)
    flight_recorder_on: bool = True  # BYTEPS_FLIGHT_RECORDER: bounded
    #                                  in-memory ring of recent events,
    #                                  dumped to JSON on crash/SIGTERM/
    #                                  detector trip/quarantine/chaos
    #                                  kill (common/flight_recorder.py)
    flight_capacity: int = 4096      # BYTEPS_FLIGHT_CAPACITY: ring size
    flight_dir: str = dataclasses.field(default_factory=flight_dir_from_env)
    #                                  BYTEPS_FLIGHT_DIR: dump directory
    #                                  (unset/empty = a per-user tmp
    #                                  subdir, never cwd).  The env var
    #                                  backs the DEFAULT even for
    #                                  explicitly constructed
    #                                  Config(...) objects: a crash dump
    #                                  must land where the operator (or
    #                                  the test harness) pointed, not in
    #                                  whatever cwd a Config() happened
    #                                  to be built in
    flight_dump_on_exit: bool = False
    #                                  BYTEPS_FLIGHT_DUMP_ON_EXIT: also
    #                                  dump on engine shutdown / normal
    #                                  interpreter exit (once)
    ts_on: bool = True               # BYTEPS_TS_ON: background sampler
    #                                  feeding the per-rank time-series
    #                                  ring (common/timeseries.py); like
    #                                  the obs server it survives
    #                                  suspend/resume — one sampler per
    #                                  process lifetime
    ts_interval_s: float = 2.0       # BYTEPS_TS_INTERVAL_S: sampling
    #                                  cadence (seconds per window)
    ts_window: int = 256             # BYTEPS_TS_WINDOW: ring capacity in
    #                                  samples — the fixed memory bound
    #                                  and the history depth /timeseries
    #                                  and bps_doctor can see
    health_on: bool = True           # BYTEPS_HEALTH_ON: SLO rule engine
    #                                  (common/health.py) evaluated each
    #                                  sampling tick; firing rules flip
    #                                  /healthz to 503
    health_windows: int = 3          # BYTEPS_HEALTH_WINDOWS: hysteresis K
    #                                  — consecutive breaching windows to
    #                                  fire, consecutive clean windows to
    #                                  clear
    health_overlap_floor: float = 0.2
    #                                  BYTEPS_HEALTH_OVERLAP_FLOOR:
    #                                  overlap_fraction below this while
    #                                  steps complete breaches the
    #                                  overlap_floor rule
    health_burn_rate: float = 1.0    # BYTEPS_HEALTH_BURN_RATE: events/s
    #                                  threshold shared by the
    #                                  retransmit/shed/conn_reset burn
    #                                  rules (per-window delta over the
    #                                  sampling interval)
    health_skew_ratio: float = 4.0   # BYTEPS_HEALTH_SKEW_RATIO: a rank
    #                                  whose attrib-component window mean
    #                                  exceeds this multiple of the
    #                                  cluster median breaches attrib_skew

    # Pin markers for the auto-tuned planner (resolved in __post_init__
    # when left None): a knob explicitly set — env var present, or a
    # non-default value passed to Config(...) — stays exactly as given
    # and the planner never touches it (reproducibility contract).
    partition_pinned: Optional[bool] = None
    credit_pinned: Optional[bool] = None

    def __post_init__(self):
        if self.partition_bytes <= 0:
            raise ValueError("partition_bytes must be positive")
        if self.partition_pinned is None:
            self.partition_pinned = (self.partition_bytes
                                     != PARTITION_BYTES_DEFAULT)
        if self.credit_pinned is None:
            self.credit_pinned = self.scheduling_credit != 0
        if self.buffer_min_bytes < 0:
            raise ValueError("buffer_min_bytes must be >= 0")
        if self.group_size < 0:
            raise ValueError(
                f"group_size {self.group_size}: a negative value selected "
                "drain mode, which was removed; group_size "
                "(BYTEPS_GROUP_SIZE) is the count of chunks merged per "
                "device program, >= 1 (0 is read as 1)")
        if self.sharded_param_codec not in ("", "auto"):
            # "name" or "name:k" — structural check here; the codec name
            # and parameter are validated against the registry at declare
            # time (core/sharded_update.py), where the quality gate runs.
            parts = self.sharded_param_codec.split(":")
            if (len(parts) > 2 or not parts[0]
                    or any(ch.isspace() for ch in self.sharded_param_codec)):
                raise ValueError(
                    "sharded_param_codec must be '', 'auto', 'name' or "
                    f"'name:param', got {self.sharded_param_codec!r}")
        if self.sharded_param_codec and not self.sharded_update:
            raise ValueError(
                "sharded_param_codec requires sharded_update "
                "(BYTEPS_SHARDED_UPDATE=1) — the parameter all-gather "
                "leg only exists in sharded-update mode")
        if self.sharded_update_fused and not self.sharded_update:
            raise ValueError(
                "sharded_update_fused requires sharded_update "
                "(BYTEPS_SHARDED_UPDATE=1) — there is no update program "
                "to fuse outside sharded-update mode")
        # Round partition bound up to alignment so chunk boundaries stay tiled.
        r = self.partition_bytes % ALIGN_BYTES
        if r and self.partition_bytes < 2**31 - ALIGN_BYTES:
            self.partition_bytes += ALIGN_BYTES - r
        if self.num_hosts < 1:
            raise ValueError("num_hosts must be >= 1")
        if self.dcn_size < 0:
            raise ValueError("dcn_size must be >= 0 (0 = derive from "
                             "the process count)")
        if not 0 < self.failure_exit_code < 256:
            raise ValueError(
                f"failure_exit_code {self.failure_exit_code} is not "
                "restartable: it must survive a process exit status "
                "(1..255)")
        if self.failure_exit_code == 1:
            # 1 is the generic Python-crash code: supervision could not
            # tell a detector-requested restart from an ordinary crash,
            # so the "restartable" contract would silently break
            raise ValueError(
                "failure_exit_code 1 is not restartable: it is "
                "indistinguishable from a generic crash to the "
                "launcher's --restart supervision; pick a code in "
                "2..255")
        if self.restart_limit < 0:
            raise ValueError("restart_limit must be >= 0")
        if (self.membership_rendezvous_timeout_s <= 0
                or self.membership_sync_timeout_s <= 0):
            raise ValueError("membership timeouts must be positive")
        if self.bus_retries < 1:
            raise ValueError("bus_retries must be >= 1 (at least one "
                             "attempt)")
        if self.gossip_interval_s <= 0:
            raise ValueError("gossip_interval_s must be positive")
        if self.gossip_fanout < 1:
            raise ValueError("gossip_fanout must be >= 1")
        if self.gossip_suspect_s <= 0:
            raise ValueError("gossip_suspect_s must be positive")
        if self.gossip_dead_s <= self.gossip_suspect_s:
            raise ValueError(
                "gossip_dead_s must exceed gossip_suspect_s — a rank "
                "must pass through suspect (the refutation window) "
                "before it can be declared dead")
        if self.sync_deadline_s < 0:
            raise ValueError("sync_deadline_s must be >= 0 (0 = off)")
        if not 0 <= self.membership_port < 65536:
            raise ValueError("membership_port must be in 0..65535")
        if not 0 <= self.transport_port_base < 65536:
            raise ValueError("transport_port_base must be in 0..65535 "
                             "(0 = ephemeral)")
        if self.transport_connect_timeout_s <= 0:
            raise ValueError("transport_connect_timeout_s must be positive")
        if self.transport_send_deadline_s <= 0:
            raise ValueError(
                "transport_send_deadline_s must be positive — the "
                "per-send deadline is what turns a partitioned peer "
                "into AckLost instead of a hang")
        if self.transport_keepalive_s < 0:
            raise ValueError("transport_keepalive_s must be >= 0 (0 = "
                             "no keepalives)")
        if self.transport_max_inflight <= 0:
            raise ValueError("transport_max_inflight must be positive")
        if self.nonfinite_policy not in ("raise", "skip", "zero"):
            raise ValueError(
                f"BYTEPS_NONFINITE_POLICY must be raise, skip, or zero — "
                f"got {self.nonfinite_policy!r}")
        if self.integrity_max_retransmits < 0:
            raise ValueError("integrity_max_retransmits must be >= 0")
        if self.bus_max_frame <= 0:
            raise ValueError("bus_max_frame must be positive")
        if self.straggler_policy not in ("wait", "hedge", "demote"):
            raise ValueError(
                f"BYTEPS_STRAGGLER_POLICY must be wait, hedge, or demote "
                f"— got {self.straggler_policy!r}")
        if self.slowness_phi <= 0:
            raise ValueError("slowness_phi must be positive")
        if self.slowness_window < 8:
            raise ValueError("slowness_window must be >= 8")
        if self.straggler_demote_after < 1:
            raise ValueError("straggler_demote_after must be >= 1")
        if self.straggler_min_lag_s < 0:
            raise ValueError("straggler_min_lag_s must be >= 0")
        if self.serve_hedge_ms < 0:
            raise ValueError("serve_hedge_ms must be >= 0 (0 = adaptive)")
        if self.min_compress_bytes < 0:
            raise ValueError("min_compress_bytes must be >= 0")
        if not 0 < self.compress_error_ceiling <= 1.0:
            raise ValueError(
                "compress_error_ceiling must be in (0, 1] — it is a "
                "relative gradient-error bound")
        if self.serve_replicas < 1:
            raise ValueError("serve_replicas must be >= 1 (1 = primary "
                             "only, no replication)")
        if self.serve_retention < 1:
            raise ValueError("serve_retention must be >= 1 (at least the "
                             "latest snapshot must stay pullable)")
        if self.serve_hot_keys < 0:
            raise ValueError("serve_hot_keys must be >= 0")
        if self.serve_max_staleness_s < 0:
            raise ValueError("serve_max_staleness_s must be >= 0")
        if self.serve_cut_interval_s < 0:
            raise ValueError("serve_cut_interval_s must be >= 0")
        if self.serve_tier_vnodes < 1:
            raise ValueError("serve_tier_vnodes must be >= 1")
        if self.serve_tier_replicas < 1:
            raise ValueError("serve_tier_replicas must be >= 1 (the "
                             "owning host)")
        if self.serve_tier_rate < 0:
            raise ValueError("serve_tier_rate must be >= 0 (0 = no token "
                             "bucket, queue watermark only)")
        if self.serve_tier_burst < 0:
            raise ValueError("serve_tier_burst must be >= 0 (0 = one "
                             "second of refill)")
        if self.serve_tier_queue_high < 1:
            raise ValueError("serve_tier_queue_high must be >= 1")
        if self.serve_tier_ttl_s <= 0:
            raise ValueError("serve_tier_ttl_s must be positive — a "
                             "non-expiring directory entry would pin a "
                             "dead host in every client's ring forever")
        if self.serve_tier_min_hosts < 1:
            raise ValueError("serve_tier_min_hosts must be >= 1")
        if self.serve_tier_max_hosts < self.serve_tier_min_hosts:
            raise ValueError("serve_tier_max_hosts must be >= "
                             "serve_tier_min_hosts")
        if self.serve_tier_cooldown_s < 0:
            raise ValueError("serve_tier_cooldown_s must be >= 0")
        if self.reconcile_interval_s <= 0:
            raise ValueError("reconcile_interval_s must be positive")
        if self.reconcile_flap_limit < 1:
            raise ValueError("reconcile_flap_limit must be >= 1 (the "
                             "crash count that triggers the ban)")
        if self.reconcile_flap_window_s <= 0:
            raise ValueError("reconcile_flap_window_s must be positive")
        if self.reconcile_drain_deadline_s <= 0:
            raise ValueError("reconcile_drain_deadline_s must be "
                             "positive — a 0 deadline would kill every "
                             "drain before its first in-flight pull "
                             "finished")
        if self.reconcile_ban_s < 0:
            raise ValueError("reconcile_ban_s must be >= 0")
        if self.obs_port is not None and not 0 <= self.obs_port < 65536:
            raise ValueError("obs_port must be in 0..65535 (0 = ephemeral)")
        if self.flight_capacity <= 0:
            raise ValueError("flight_capacity must be positive")
        if self.trace_sample_n < 0:
            self.trace_sample_n = _parse_trace_sample(self.trace_sample)
        if self.trace_capacity < 256:
            raise ValueError("trace_capacity must be >= 256")
        if self.clock_sync_samples < 0:
            raise ValueError("clock_sync_samples must be >= 0 (0 = off)")
        if self.ts_interval_s <= 0:
            raise ValueError("ts_interval_s must be positive")
        if self.ts_window < 8:
            raise ValueError("ts_window must be >= 8 — the health rules "
                             "need at least a few windows of history to "
                             "judge a trend")
        if self.health_windows < 1:
            raise ValueError("health_windows must be >= 1")
        if not 0 <= self.health_overlap_floor <= 1:
            raise ValueError("health_overlap_floor must be in [0, 1] — "
                             "it is a fraction of the step wall")
        if self.health_burn_rate <= 0:
            raise ValueError("health_burn_rate must be positive")
        if self.health_skew_ratio <= 1:
            raise ValueError("health_skew_ratio must be > 1 — a ratio at "
                             "or below the median can never mean skew")
        if self.wal_fsync not in ("always", "interval", "off"):
            raise ValueError(
                "wal_fsync must be one of always|interval|off — an "
                "unknown policy would silently weaken the durability "
                "guarantee the operator thinks they have")
        if self.wal_fsync_interval_s <= 0:
            raise ValueError("wal_fsync_interval_s must be positive")
        if self.wal_segment_bytes < 4096:
            raise ValueError("wal_segment_bytes must be >= 4096 — a "
                             "sub-page segment rolls on every record")
        if self.wal_retain_snapshots < 1:
            raise ValueError("wal_retain_snapshots must be >= 1 (the "
                             "latest durable cut must survive pruning)")

    @classmethod
    def from_env(cls) -> "Config":
        uri = os.environ.get("DMLC_PS_ROOT_URI")
        port = os.environ.get("DMLC_PS_ROOT_PORT")
        coord = f"{uri}:{port}" if uri and port else None
        return cls(
            num_hosts=_env_int("DMLC_NUM_WORKER", 1),
            host_id=_env_int("DMLC_WORKER_ID", 0),
            local_rank=_env_int("BYTEPS_LOCAL_RANK", 0),
            local_size=_env_int("BYTEPS_LOCAL_SIZE", 1),
            coordinator_address=coord,
            force_distributed=_env_bool("BYTEPS_FORCE_DISTRIBUTED", False),
            dcn_size=_env_int("BYTEPS_DCN_SIZE", 0),
            partition_bytes=_env_int("BYTEPS_PARTITION_BYTES",
                                     PARTITION_BYTES_DEFAULT),
            scheduling_credit=_env_int("BYTEPS_SCHEDULING_CREDIT", 0),
            enable_priority=_env_bool("BYTEPS_ENABLE_PRIORITY", True),
            group_size=_env_int("BYTEPS_GROUP_SIZE",
                                _env_int("BYTEPS_NCCL_GROUP_SIZE", 4)),
            autotune=_env_bool("BYTEPS_AUTOTUNE", True),
            buffer_min_bytes=_env_int("BYTEPS_BUFFER_MIN_BYTES", 1 << 20),
            deferred_gather=_env_bool("BYTEPS_DEFERRED_GATHER", True),
            sharded_update=_env_bool("BYTEPS_SHARDED_UPDATE", False),
            sharded_update_fused=_env_bool("BYTEPS_SHARDED_UPDATE_FUSED",
                                           False),
            sharded_param_codec=_env_str("BYTEPS_SHARDED_PARAM_CODEC", ""),
            # presence of the env var IS the pin, whatever its value —
            # a launch script exporting the reference default must still
            # get exactly that value
            partition_pinned=("BYTEPS_PARTITION_BYTES" in os.environ
                              or None),
            credit_pinned=("BYTEPS_SCHEDULING_CREDIT" in os.environ
                           or None),
            min_compress_bytes=_env_int("BYTEPS_MIN_COMPRESS_BYTES", 65536),
            compress_autotune=_env_bool("BYTEPS_COMPRESS_AUTOTUNE", False),
            compress_error_ceiling=_env_float(
                "BYTEPS_COMPRESS_ERROR_CEILING", 0.55),
            use_native=_env_bool("BYTEPS_NATIVE", True),
            use_pallas=_env_bool("BYTEPS_PALLAS", True),
            enable_async=_env_bool("BYTEPS_ENABLE_ASYNC", False),
            server_engine_threads=_env_int("BYTEPS_SERVER_ENGINE_THREAD", 4),
            server_enable_schedule=_env_bool("BYTEPS_SERVER_ENABLE_SCHEDULE",
                                             False),
            server_debug_key=_env_str("BYTEPS_SERVER_DEBUG_KEY", ""),
            key_hash_fn=_env_str("BYTEPS_KEY_HASH_FN", "djb2"),
            enable_mixed_mode=_env_bool("BYTEPS_ENABLE_MIXED_MODE", False),
            mixed_mode_bound=_env_int("BYTEPS_MIXED_MODE_BOUND", 101),
            debug_sample_tensor=_env_str("BYTEPS_DEBUG_SAMPLE_TENSOR", ""),
            elastic=_env_bool("BYTEPS_ELASTIC", False),
            membership_port=_env_int("BYTEPS_MEMBERSHIP_PORT", 0),
            membership_rendezvous_timeout_s=_env_float(
                "BYTEPS_MEMBERSHIP_RENDEZVOUS_TIMEOUT", 10.0),
            membership_sync_timeout_s=_env_float(
                "BYTEPS_MEMBERSHIP_SYNC_TIMEOUT", 60.0),
            heartbeat_on=_env_bool("BYTEPS_HEARTBEAT_ON", False),
            heartbeat_interval_s=_env_float("BYTEPS_HEARTBEAT_INTERVAL",
                                            1.0),
            heartbeat_timeout_s=_env_float("BYTEPS_HEARTBEAT_TIMEOUT",
                                           30.0),
            failure_exit_code=_env_int("BYTEPS_FAILURE_EXIT_CODE", 17),
            sync_deadline_s=_env_float("BYTEPS_SYNC_DEADLINE_S", 0.0),
            membership_hosts=_env_str("BYTEPS_MEMBERSHIP_HOSTS", ""),
            bus_retries=_env_int("BYTEPS_BUS_RETRIES", 64),
            gossip_on=_env_bool("BYTEPS_GOSSIP_ON", False),
            gossip_interval_s=_env_float("BYTEPS_GOSSIP_INTERVAL_S", 0.2),
            gossip_fanout=_env_int("BYTEPS_GOSSIP_FANOUT", 3),
            gossip_suspect_s=_env_float("BYTEPS_GOSSIP_SUSPECT_S", 1.0),
            gossip_dead_s=_env_float("BYTEPS_GOSSIP_DEAD_S", 3.0),
            straggler_policy=_env_str("BYTEPS_STRAGGLER_POLICY",
                                      "wait").strip().lower(),
            slowness_phi=_env_float("BYTEPS_SLOWNESS_PHI", 8.0),
            slowness_window=_env_int("BYTEPS_SLOWNESS_WINDOW", 64),
            straggler_demote_after=_env_int(
                "BYTEPS_STRAGGLER_DEMOTE_AFTER", 3),
            straggler_min_lag_s=_env_float("BYTEPS_STRAGGLER_MIN_LAG",
                                           0.25),
            serve_hedge_ms=_env_float("BYTEPS_SERVE_HEDGE_MS", 0.0),
            serve_replicas=_env_int("BYTEPS_SERVE_REPLICAS", 1),
            serve_retention=_env_int("BYTEPS_SERVE_RETENTION", 8),
            serve_hot_keys=_env_int("BYTEPS_SERVE_HOT_KEYS", 8),
            serve_max_staleness_s=_env_float("BYTEPS_SERVE_MAX_STALENESS",
                                             0.5),
            serve_cut_interval_s=_env_float("BYTEPS_SERVE_CUT_INTERVAL",
                                            0.05),
            serve_tier_vnodes=_env_int("BYTEPS_SERVE_TIER_VNODES", 64),
            serve_tier_replicas=_env_int("BYTEPS_SERVE_TIER_REPLICAS", 2),
            serve_tier_rate=_env_float("BYTEPS_SERVE_TIER_RATE", 0.0),
            serve_tier_burst=_env_float("BYTEPS_SERVE_TIER_BURST", 0.0),
            serve_tier_queue_high=_env_int(
                "BYTEPS_SERVE_TIER_QUEUE_HIGH", 64),
            serve_tier_ttl_s=_env_float("BYTEPS_SERVE_TIER_TTL", 10.0),
            serve_tier_min_hosts=_env_int("BYTEPS_SERVE_TIER_MIN_HOSTS", 1),
            serve_tier_max_hosts=_env_int("BYTEPS_SERVE_TIER_MAX_HOSTS", 8),
            serve_tier_cooldown_s=_env_float(
                "BYTEPS_SERVE_TIER_COOLDOWN", 5.0),
            serve_tier_bus=_env_str("BYTEPS_SERVE_TIER_BUS", ""),
            reconcile_interval_s=_env_float("BYTEPS_RECONCILE_INTERVAL",
                                            0.5),
            reconcile_flap_limit=_env_int("BYTEPS_RECONCILE_FLAP_LIMIT",
                                          3),
            reconcile_flap_window_s=_env_float(
                "BYTEPS_RECONCILE_FLAP_WINDOW", 30.0),
            reconcile_drain_deadline_s=_env_float(
                "BYTEPS_RECONCILE_DRAIN_DEADLINE", 10.0),
            reconcile_ban_s=_env_float("BYTEPS_RECONCILE_BAN", 30.0),
            transport_hosts=_env_str("BYTEPS_TRANSPORT_HOSTS", ""),
            transport_port_base=_env_int("BYTEPS_TRANSPORT_PORT_BASE", 0),
            transport_connect_timeout_s=_env_float(
                "BYTEPS_TRANSPORT_CONNECT_TIMEOUT", 5.0),
            transport_send_deadline_s=_env_float(
                "BYTEPS_TRANSPORT_SEND_DEADLINE", 10.0),
            transport_keepalive_s=_env_float(
                "BYTEPS_TRANSPORT_KEEPALIVE", 5.0),
            transport_max_inflight=_env_int(
                "BYTEPS_TRANSPORT_MAX_INFLIGHT", 64 << 20),
            integrity_on=_env_bool("BYTEPS_INTEGRITY", True),
            integrity_loopback=_env_bool("BYTEPS_INTEGRITY_LOOPBACK", True),
            integrity_max_retransmits=_env_int(
                "BYTEPS_INTEGRITY_MAX_RETRANSMITS", 3),
            nonfinite_policy=_env_str("BYTEPS_NONFINITE_POLICY",
                                      "raise").strip().lower(),
            bus_max_frame=_env_int("BYTEPS_BUS_MAX_FRAME", 1 << 30),
            lock_witness=_env_bool("BYTEPS_LOCK_WITNESS", False),
            fault_spec=_env_str("BYTEPS_FAULT_SPEC", ""),
            fault_seed=_env_int("BYTEPS_FAULT_SEED", 0),
            durable_dir=_env_str("BYTEPS_DURABLE_DIR", ""),
            wal_fsync=_env_str("BYTEPS_WAL_FSYNC",
                               "always").strip().lower(),
            wal_fsync_interval_s=_env_float("BYTEPS_WAL_FSYNC_INTERVAL",
                                            0.05),
            wal_segment_bytes=_env_int("BYTEPS_WAL_SEGMENT_BYTES", 4 << 20),
            wal_retain_snapshots=_env_int("BYTEPS_WAL_RETAIN", 2),
            restart_limit=_env_int("BYTEPS_RESTART_LIMIT", 0),
            retry_max_attempts=_env_int("BYTEPS_RETRY_MAX_ATTEMPTS", 3),
            retry_base_delay_s=_env_float("BYTEPS_RETRY_BASE_DELAY", 0.1),
            retry_max_delay_s=_env_float("BYTEPS_RETRY_MAX_DELAY", 2.0),
            retry_deadline_s=_env_float("BYTEPS_RETRY_DEADLINE", 60.0),
            log_level=_env_str("BYTEPS_LOG_LEVEL", "WARNING"),
            trace_on=_env_bool("BYTEPS_TRACE_ON", False),
            trace_start_step=_env_int("BYTEPS_TRACE_START_STEP", 10),
            trace_end_step=_env_int("BYTEPS_TRACE_END_STEP", 20),
            trace_dir=trace_dir_from_env(),
            trace_jax=_env_bool("BYTEPS_TRACE_JAX", False),
            trace_sample=_env_str("BYTEPS_TRACE_SAMPLE", ""),
            trace_capacity=_env_int("BYTEPS_TRACE_CAPACITY", 65536),
            clock_sync_samples=_env_int("BYTEPS_CLOCK_SYNC_SAMPLES", 5),
            telemetry_on=_env_bool("BYTEPS_TELEMETRY_ON", True),
            obs_port=(_env_int("BYTEPS_OBS_PORT", 0)
                      if os.environ.get("BYTEPS_OBS_PORT") not in (None, "")
                      else None),
            obs_host=_env_str("BYTEPS_OBS_HOST", "127.0.0.1"),
            flight_recorder_on=_env_bool("BYTEPS_FLIGHT_RECORDER", True),
            flight_capacity=_env_int("BYTEPS_FLIGHT_CAPACITY", 4096),
            flight_dir=flight_dir_from_env(),
            flight_dump_on_exit=_env_bool("BYTEPS_FLIGHT_DUMP_ON_EXIT",
                                          False),
            ts_on=_env_bool("BYTEPS_TS_ON", True),
            ts_interval_s=_env_float("BYTEPS_TS_INTERVAL_S", 2.0),
            ts_window=_env_int("BYTEPS_TS_WINDOW", 256),
            health_on=_env_bool("BYTEPS_HEALTH_ON", True),
            health_windows=_env_int("BYTEPS_HEALTH_WINDOWS", 3),
            health_overlap_floor=_env_float(
                "BYTEPS_HEALTH_OVERLAP_FLOOR", 0.2),
            health_burn_rate=_env_float("BYTEPS_HEALTH_BURN_RATE", 1.0),
            health_skew_ratio=_env_float("BYTEPS_HEALTH_SKEW_RATIO", 4.0),
        )


_config: Optional[Config] = None


def get_config() -> Config:
    """Return the process-wide config, building it from env on first use."""
    global _config
    if _config is None:
        _config = Config.from_env()
    return _config


def set_config(cfg: Config) -> None:
    """Install an explicit config (tests, embedding applications)."""
    global _config
    _config = cfg


def reset_config() -> None:
    global _config
    _config = None
