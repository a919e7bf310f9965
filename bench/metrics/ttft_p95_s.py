"""95th percentile, over every request sent in the window, of the time
from its send to its first token; a request still without a token when
the window closes enters at its wait so far."""

import numpy as np


def read(run):
    t = run.clients.ttfts(run.t1)
    return float(np.percentile(t, 95)) if t else None
