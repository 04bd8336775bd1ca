"""Device time per training step of the exchange's level fits: the ops
under ``fit`` inside the ``exchange`` scope (the bucket sorts and the
midpoint solve); the union of their intervals, averaged over the chips."""
from harness import scopes


def read(run):
    return scopes.run_ms(run, scopes.is_level_fit)
