"""Pallas TPU kernels for the paper's compute hot-spots: the compression
path (blockwise top-k / scaled-sign, fused with error feedback), the fused
FedAMS server update and the one-pass sparse ingest. They compile on TPU
and run in interpret mode on the CPU platform, where tests validate them
against ref.py."""
from repro.kernels.bitpack import (pack_bits_ref, pack_uint_words,  # noqa: F401
                                   unpack_bits_ref, unpack_uint_words)
from repro.kernels.fedams_update import fedams_update  # noqa: F401
from repro.kernels.ops import KernelImpl  # noqa: F401
from repro.kernels.sign_ef import sign_ef  # noqa: F401
from repro.kernels.topk_ef import topk_ef, topk_ef_sparse  # noqa: F401
