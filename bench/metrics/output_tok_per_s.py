"""Output tokens delivered in the window over the window's seconds.  A
token counts when the ``step()`` that produced it returns."""


def read(run):
    return run.clients.window_tokens(run.t0, run.t1) / run.window_s
