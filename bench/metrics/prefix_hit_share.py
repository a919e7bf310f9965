"""Prompt tokens the prefix cache let the window skip (the serve loop's
``prefill_tokens_saved`` over the window) over the real prompt tokens of
the requests admitted in the window."""


def read(run):
    total = run.clients.prompt_tokens_admitted(run.t0, run.t1)
    if not total:
        return None
    return 100.0 * run.prefill_tokens_saved / total
