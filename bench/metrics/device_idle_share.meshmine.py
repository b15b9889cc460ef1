"""Device: 1 - busy / window over the traced window of the mesh mine cell,
busy being each chip's union of operation intervals, averaged over the
chips."""
from ._common import idle_share


def read(run):
    return idle_share(run)
