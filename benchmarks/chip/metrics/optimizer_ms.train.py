"""Device time per training step of the optimizer's update and the
parameter write: the ops under the program's ``optimizer`` scope; the
union of their intervals, averaged over the chips."""
from harness import scopes


def read(run):
    return scopes.run_ms(run, scopes.is_layer("optimizer"))
