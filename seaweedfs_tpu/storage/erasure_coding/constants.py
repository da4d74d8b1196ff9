"""The numbers of the RS(10,4) layout (`ec_encoder.go:17-23`): shard counts
and the two block sizes, defined once. A leaf: it imports nothing, so the
kernels (`ops.rs_kernel`), the striping math (`geometry`) and the admin
shell, which wants the integers and not the codec, can all take them from
here."""

DATA_SHARDS = 10
PARITY_SHARDS = 4
TOTAL_SHARDS = DATA_SHARDS + PARITY_SHARDS

LARGE_BLOCK_SIZE = 1024 * 1024 * 1024  # 1GB
SMALL_BLOCK_SIZE = 1024 * 1024  # 1MB
