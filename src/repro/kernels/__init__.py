"""Pallas TPU kernels for ENEC hot spots: native on a TPU, interpret mode
elsewhere (checked bit-exact there; tests/test_tpu_compile.py compiles them
for a described v5e).

enec_encode / enec_decode : the block codec (paper §IV-B + §V)
idd_scan                  : prefix sum, MXU triangular-matmul adaptation (§V-D)
decompress_matmul         : fused decompress+GEMM (beyond paper, DESIGN.md §8)
"""
from . import ops, ref  # noqa: F401
from .decode_attention_kv import (compress_kv_prefix,
                                  decode_attention_kv_enec)
from .ops import (decode_blocks, decompress_matmul, encode_blocks, idd_scan,
                  tile_weights_for_fusion)

__all__ = ["ops", "ref", "decode_blocks", "decompress_matmul",
           "encode_blocks", "idd_scan", "tile_weights_for_fusion",
           "compress_kv_prefix", "decode_attention_kv_enec"]
