"""Device time per training step of the model's backward, the
rematerialized forward included: the ops under ``transpose(jvp(model))``,
outside the exchange; the union of their intervals, averaged over the
chips."""
from harness import scopes


def read(run):
    return scopes.run_ms(run, scopes.is_layer("model_bwd"))
