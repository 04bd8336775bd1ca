"""Device time per training step of the gradient exchange's Pallas
kernels (encode, quantize-dequantize, decode), averaged over the chips."""
from harness.kernels import is_exchange_kernel


def read(run):
    ns = run.trace.mean_op_ns(is_exchange_kernel)
    if ns <= 0 or not run.steps:
        return None
    return ns * 1e-6 / run.steps
