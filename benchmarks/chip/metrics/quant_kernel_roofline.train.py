"""The exchange kernels' share of their roofline: the HBM bytes that the
quantized Algorithm-2 exchange must move per step (``cost.exchange_bytes``
over the model's gradient count) at the HBM peak, over their measured
device time per step, in %."""
from harness import common, cost
from harness.kernels import is_exchange_kernel


def read(run):
    ns = run.trace.mean_op_ns(is_exchange_kernel)
    if ns <= 0 or not run.steps:
        return None
    t = run.traffic
    q = cost.quantizer_shape(t["quant"])
    n = common.reference_module(run.config).param_count(run.config["sizes"])
    need = sum(cost.exchange_bytes(n, t["bucket"], q["bits"], q["levels"],
                                   t["data"], t["error_feedback"]).values())
    return cost.roofline_share(need, 0.0, ns * 1e-9 / run.steps, run.peaks)
