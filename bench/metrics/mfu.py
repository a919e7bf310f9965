"""Model ops completed in the window over the window's seconds, over the
chip's int8 peak.

Every real prefill and decode token counts, padding does not: a token
counts 2 x the linear weights it passes through plus attention's
4·H·ctx·hd a layer; the head's 2·d·vocab counts once per row of logits
taken (each decode token, the last prompt token of each prefill).  Prompt
tokens the prefix cache skipped do not count; where several admissions
share a step, the skipped tokens are split evenly among them.  The weights
are 3-bit and the activations 3-bit codes, so an int8 matrix unit could
run every linear: the int8 peak is the highest rate any implementation
could use, and the parts the program runs in bfloat16 make this a lower
bound of the bf16-relative share."""

from bench import counting


def read(run):
    if run.peaks is None:
        return None
    d = run.dims
    ops = 0
    for rec in run.clients.decodes:
        if rec.in_window:
            for _, _, pos in rec.rows:
                ops += counting.token_ops(d, pos + 1) + counting.head_ops(d)
    admitted = run.clients.first_token_in(run.t0, run.t1)
    if admitted:
        skip = run.prefill_tokens_saved / len(admitted)
        for req in admitted:
            L = len(req.prompt)
            a = int(min(skip, L - 1))
            ops += (L - a) * counting.token_ops(d, 0)
            ops += (d["n_layers"] * 4 * d["n_heads"] * d["head_dim"]
                    * counting.span_ctx_sum(a, L))
            ops += counting.head_ops(d)
    if not ops:
        return None
    return 100.0 * ops / run.window_s / run.peaks["int8_ops_per_s"]
