"""Device time per decode forward: the device operations under the scope
``repro.lm.decode_step_paged`` in the traced window, over the number of
decode forwards the window ran."""

SCOPE = "repro.lm.decode_step_paged"


def scopes(run):
    return (SCOPE,)


def read(run):
    if run.trace is None or not run.decode_steps:
        return None
    return 1000.0 * run.trace["scope_s"][SCOPE] / run.decode_steps
