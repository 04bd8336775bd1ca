"""Device time per training step of the model's forward: the ops under
the program's ``model`` scope as autodiff's ``jvp(model)`` left it, outside
the backward and the exchange; the union of their intervals, averaged over
the chips."""
from harness import scopes


def read(run):
    return scopes.run_ms(run, scopes.is_layer("model_fwd"))
