"""Elastic fleets: checkpoint-portable resharding + preemption tolerance.

The production-operations counterpart to raw scale (ROADMAP item 5):

  * :mod:`oversim_tpu.elastic.reshard` — a checkpoint written at one
    topology restores at another: the replica axis of campaign-stacked
    state grows/shrinks by padding/slicing (grown slots re-seeded
    deterministically from the campaign's base seed), and placement is
    re-established via ``NamedSharding`` over whatever mesh is available
    at restore time.  Surviving replicas are bit-identical across the
    reshape.
  * :mod:`oversim_tpu.elastic.retry` — the failure classes: device
    errors classified transient vs fatal, jittered exponential backoff
    around device dispatch and backend acquisition; exhausted attempts
    raise (no degradation to another platform).
  * :mod:`oversim_tpu.elastic.fleet` — the host-side pieces of the
    fleet supervisor (``scripts/fleet_run.py``): replica-shard
    assignment, heartbeat files, seeded chaos schedules, and the
    per-shard artifact merge that reproduces the uninterrupted
    single-process ensemble exactly.

  * :mod:`oversim_tpu.elastic.autoscaler` — the closed loop: a
    hysteresis policy over the fleet's own gauges (backlog, p99
    latency, liveness) deciding when to grow/shrink the worker set;
    ``fleet.plan_resize`` + ``fleet.regroup_shard_leaves`` compute the
    resulting re-split of live replica rows.

See README.md "Elastic fleets" for the user guide.
"""

from oversim_tpu.elastic.autoscaler import (  # noqa: F401
    SCALE_DOWN,
    SCALE_UP,
    AutoscalePolicy,
    Autoscaler,
    Decision,
    Signals,
    parse_exposition_text,
    scrape_exposition,
)
from oversim_tpu.elastic.fleet import (  # noqa: F401
    chaos_schedule,
    decode_leaves,
    encode_leaves,
    heartbeat_age,
    merge_shard_leaves,
    plan_resize,
    read_json,
    regroup_shard_leaves,
    shard_replicas,
    write_heartbeat,
    write_json_atomic,
)
from oversim_tpu.elastic.reshard import (  # noqa: F401
    place_campaign,
    place_solo,
    replica_fingerprint,
    reshard_load,
    reshard_stacked,
)
from oversim_tpu.elastic.retry import (  # noqa: F401
    FATAL,
    TRANSIENT,
    RetryBudgetExceeded,
    RetryPolicy,
    acquire_backend,
    backoff_delays,
    classify,
    with_retry,
)
