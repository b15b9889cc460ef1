"""Device: 1 - busy / window over the traced window of a slide cell."""
from ._common import idle_share


def read(run):
    return idle_share(run)
