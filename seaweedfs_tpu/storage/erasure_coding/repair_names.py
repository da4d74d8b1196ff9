"""The partial-sum repair's names and counters: rebuild modes, the typed
fallback and chain-restart reasons, the chunk states of a streaming
session, and the metric families they label. No mathematics and no numpy:
the admin shell plans and counts repairs with these, and `decoder`, where
the GF(2^8) side lives, imports them back under their old names.
"""

from __future__ import annotations

from seaweedfs_tpu.stats.metrics import default_registry

# The modes / typed fallback reasons / chain-restart reasons below ride into
# metric labels and are linted by tools/check_metric_names.py like the other
# reason sets. A "fallback" is a pipelined repair degrading to classic
# whole-shard pulls; a "restart" is the chain re-planned minus a dead hop
# (the retry ladder's cheaper rung — the repair stays pipelined).
REPAIR_MODES = ("classic", "pipelined")
REPAIR_FALLBACK_REASONS = (
    "too_few_holders",     # auto mode: a <=2-node chain spreads nothing
    "hop_failed",          # chain restarts exhausted the surviving holders
    "crc_mismatch",        # a partial arrived corrupt twice in a row
    "start_failed",        # the rebuilder refused the partial-write state
    "insufficient_shards", # survivors minus dead hops dropped below 10
    "stream_stall",        # a streaming hop's bounded window backed up past
                           # the stall budget twice (downstream wedged)
    "chunk_crc",           # a streamed chunk failed its per-chunk CRC twice
)
REPAIR_RESTART_REASONS = ("hop_failed", "crc_mismatch", "stream_stall",
                          "chunk_crc")

# per-chunk lifecycle states of the streaming session plane — the `state`
# label of SeaweedFS_volume_ec_repair_stream_chunks_total (linted like the
# reason sets): a chunk is `forwarded` by a mid-chain hop's forwarder
# thread, `written` by the terminal writer, `stalled` when the bounded
# in-flight window blocked past the stall budget, `crc_failed` when its
# CRC32C did not survive the hop transfer.
STREAM_CHUNK_STATES = ("forwarded", "written", "stalled", "crc_failed")

REPAIR_BYTES_ON_WIRE = "SeaweedFS_volume_ec_repair_bytes_on_wire_total"
REPAIR_SECONDS = "SeaweedFS_volume_ec_repair_seconds"
REPAIR_FALLBACKS = "SeaweedFS_volume_ec_repair_fallbacks_total"
REPAIR_RESTARTS = "SeaweedFS_volume_ec_repair_chain_restarts_total"
REPAIR_STREAM_CHUNKS = "SeaweedFS_volume_ec_repair_stream_chunks_total"
REPAIR_RESUMED_BYTES = "SeaweedFS_volume_ec_repair_resumed_bytes_total"

_repair_metrics_cache = None
_stream_metrics_cache = None


def repair_metrics():
    """Idempotently register the ec_repair families; returns the tuple
    (bytes_on_wire{mode}, seconds{mode,stage}, fallbacks{reason},
    chain_restarts{reason}). bytes_on_wire counts every repair payload
    once, at the node that RECEIVES it (chain hops, the rebuilder's
    partial writes, classic shard pulls) or serves a ranged partial —
    so `rate(...{mode="classic"}) / rate(...{mode="pipelined"})` is the
    bandwidth cut, straight off /metrics."""
    global _repair_metrics_cache
    if _repair_metrics_cache is None:
        reg = default_registry()
        _repair_metrics_cache = (
            reg.counter(
                REPAIR_BYTES_ON_WIRE,
                "EC repair bytes moved over the network, by rebuild mode",
                ("mode",),
            ),
            reg.histogram(
                REPAIR_SECONDS,
                "EC repair wall time per stage and mode",
                ("mode", "stage"),
            ),
            reg.counter(
                REPAIR_FALLBACKS,
                "pipelined repairs degraded to classic, by typed reason",
                ("reason",),
            ),
            reg.counter(
                REPAIR_RESTARTS,
                "repair chains re-planned minus a dead hop, by reason",
                ("reason",),
            ),
        )
    return _repair_metrics_cache


def stream_metrics():
    """Idempotently register the streaming-session families; returns
    (stream_chunks{state}, resumed_bytes). `resumed_bytes` counts bytes a
    restarted chain did NOT re-send because the writer's committed
    frontier survived the failure — the wire savings of restarting from
    the first uncommitted chunk instead of byte 0."""
    global _stream_metrics_cache
    if _stream_metrics_cache is None:
        reg = default_registry()
        _stream_metrics_cache = (
            reg.counter(
                REPAIR_STREAM_CHUNKS,
                "streaming-rebuild chunks by per-chunk lifecycle state",
                ("state",),
            ),
            reg.counter(
                REPAIR_RESUMED_BYTES,
                "bytes not re-sent because a restarted chain resumed from"
                " the writer's committed frontier",
            ),
        )
    return _stream_metrics_cache
