"""seaweedfs_tpu — a TPU-native distributed object store / file system.

A ground-up rebuild of the capabilities of SeaweedFS (reference: kvaps/seaweedfs)
designed TPU-first:

  - the data-plane hot paths (Reed-Solomon(10,4) erasure coding, CRC32C / MD5
    content hashing, CDC dedup fingerprinting) run as JAX/XLA/Pallas kernels on
    TPU, batched onto the MXU/VPU, with C++ native CPU fallbacks (never pure
    Python) loaded via ctypes;
  - the control plane (master / volume server / filer) is asyncio + HTTP/JSON,
    mirroring the reference's own HTTP surface (/dir/assign, /dir/lookup,
    /<vid>,<fid>), with on-disk formats bit-compatible with the reference
    (needle v1/v2/v3, .idx, superblock, .ec00–.ec13, .ecx, .ecj, .vif) so the
    reference's golden fixtures validate this implementation directly.

Layout:
  storage/   volume engine: needle format, volumes, needle maps, erasure coding
  ops/       TPU kernels: GF(2^8) Reed-Solomon, CRC32C, MD5, CDC (JAX/Pallas)
  native/    C++ CPU kernels (Reed-Solomon, CRC32C, MD5) behind ctypes
  topology/  master-side cluster state: DC/rack/node tree, volume layout, growth
  server/    master / volume / filer HTTP servers
  filer/     namespace: entries, chunking, visible intervals, stores
  s3/        S3 gateway subset
  shell/     admin shell commands (ec.*, volume.*, fs.*)
  command/   CLI entrypoints (weed-tpu ...)
  utils/     config, http client, misc
"""

__version__ = "0.1.0"
