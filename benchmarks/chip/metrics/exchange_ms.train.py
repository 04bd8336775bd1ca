"""Device time per training step of the gradient exchange: the ops under
the program's ``exchange`` scope (buffer layout copies, error feedback,
level fits, encode, collectives, decode); the union of their intervals,
averaged over the chips."""
from harness import scopes


def read(run):
    return scopes.run_ms(run, scopes.is_layer("exchange"))
