"""TPU compute kernels (JAX/XLA/Pallas) + numpy references.

The reference's hot paths run on CPU vector assembly (SURVEY.md §2.2); here
they are re-designed for the TPU's MXU/VPU:

  gf256          GF(2^8) field + matrix math (numpy; klauspost-compatible)
  rs_kernel      Reed-Solomon codec; the jax door of the bit-plane mod-2
                 matmul (width policy, transfer side) and its XLA body
  rs_pallas      the fused Pallas TPU body of the same transform
  crc32c_kernel  batched CRC32C as a GF(2) linear map (matmul over bits)
  md5_kernel     MD5 batched across independent blobs (VPU uint32 lanes)
  cdc            content-defined chunking rolling hash (gear, GF(2)-linear)
"""
