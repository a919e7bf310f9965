"""Device time per prefill chunk: the device operations under the scope
``repro.lm.prefill_chunk`` in the traced window, over the number of
chunks the window ran."""

SCOPE = "repro.lm.prefill_chunk"


def scopes(run):
    return (SCOPE,)


def read(run):
    chunks = run.prefill_tokens_run // run.chunk
    if run.trace is None or not chunks:
        return None
    return 1000.0 * run.trace["scope_s"][SCOPE] / chunks
