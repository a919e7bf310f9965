"""95th percentile of the scheduler's queue wait (enqueue to first
admission, ``Request.queue_wait_s``) over the requests sent in the window
and admitted by its close: the part of TTFT spent behind other requests'
admissions.  A program that does not record the wait leaves nothing to
read."""

import numpy as np


def read(run):
    waits = [tr.req.queue_wait_s for tr in run.clients.tracked.values()
             if tr.in_window
             and getattr(tr.req, "queue_wait_s", None) is not None]
    return float(np.percentile(waits, 95)) if waits else None
