"""Model FLOP/s utilisation of training: tokens/s of the traced window
times the configuration's forward and backward FLOPs per token (no
recompute; its reference module counts them), over chips times the bf16
peak, in %."""
from harness import common


def read(run):
    ref = common.reference_module(run.config)
    flops = ref.train_flops_per_token(run.config["sizes"])
    return 100.0 * run.tokens_per_s * flops / (
        run.chips * run.peaks["bf16_flops"])
