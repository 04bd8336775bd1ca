"""Which device operations of a trace belong to which kernels, by the
HLO instruction names the TPU trace gives them: a Pallas kernel's custom
call is named after the ``pallas_call``'s kernel (``encode_fused.1``,
``qdq_fused``, ``decode_fused_mean.2``, ...)."""

EXCHANGE = ("encode_fused", "qdq_fused", "decode_fused_mean",
            "decode_fused_each", "encode_bingrad")


def _base(op: str) -> str:
    """``encode_fused.1`` -> ``encode_fused``."""
    head, _, tail = op.rpartition(".")
    return head if head and tail.isdigit() else op


def is_exchange_kernel(op: str) -> bool:
    """The gradient exchange's Pallas kernels: fused encode,
    quantize-dequantize and decode."""
    return _base(op) in EXCHANGE

