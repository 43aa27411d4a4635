"""Median time of one circuit application in the window, host clock,
from the first user call to the return of the completion read."""

import numpy as np


def read(ctx):
    return np.percentile(np.asarray(ctx["circuit_seconds"]) * 1e3, 50)
