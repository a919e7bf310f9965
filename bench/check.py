"""What decides ``correct``: the served path's logits and tokens against the
plain reference.

After the window, a sample of the finished requests is drawn from the seed,
with the request that served the most tokens and the one with the longest
sequence in it, until it holds ``sample_tokens`` served tokens.  The
reference runs once over each sampled prompt with its served tokens, in
float32 and again with its activations rounded to ``rounding``, the
precision the program computes in.  At every position where a decode step
of the window's own compiled program produced a token, two numbers are
read:

- ``decorrelation_excess``: how far the program departs from the float32
  reference beyond what rounding to the program's own precision costs on
  the same seed and positions: D(program) / D(rounded reference) - 1, where
  D is the mean over the positions of 1 - corr(logits, float32 logits),
  Pearson over the vocabulary.  The configurations quantise every
  linear's input to 3-bit codes, and a 3-bit quantiser turns a small
  relative error e at its input into about sqrt(e) at its output; so
  rounding alone decorrelates any bfloat16 computation from float32 by a
  few percent, and by how much depends on the seed's weights (one seed
  reads half as much again as another).  Divided by the seed's own D of
  rounding, a program that computes as the configuration states reads
  about 0, and a precision below it (the control, ``control``) reads
  well above.
- ``served_not_argmax``: how many served tokens are not the argmax of the
  logits their decode step returned.  Greedy serving makes it 0 exactly;
  a token altered where it is produced makes it positive.  The control
  serves its own argmax and reads 0 here: this number is the altered
  token's to catch.

The widest gap of a served token below the reference's best is printed
beside them, for the record, and compared with nothing.
"""

from __future__ import annotations

import numpy as np


def sample(finished, seed: int, target_tokens: int) -> list:
    """Finished requests, drawn from the seed, with the most-served and
    the longest in it, until ``target_tokens`` served tokens."""
    if not finished:
        return []
    most = max(finished, key=lambda r: (len(r.output), r.rid))
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.output),
                                          r.rid))
    picked = {most.rid: most, longest.rid: longest}
    order = np.random.default_rng([seed, 7]).permutation(len(finished))
    for i in order:
        if sum(len(r.output) for r in picked.values()) >= target_tokens:
            break
        r = finished[int(i)]
        picked.setdefault(r.rid, r)
    return sorted(picked.values(), key=lambda r: r.rid)


def program_rows(decodes, req) -> tuple:
    """(served-token indices j, the program's logits rows that produced
    them) for one request, from the recorded decode steps."""
    L = len(req.prompt)
    js, rows = [], []
    for rec in decodes:
        for slot, rid, pos in rec.rows:
            if rid == req.rid:
                js.append(pos - L + 1)
                rows.append((rec, slot))
    return js, rows


def decorrelation(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """1 - Pearson correlation of each row pair."""
    a = a - a.mean(-1, keepdims=True)
    b = b - b.mean(-1, keepdims=True)
    return 1.0 - (a * b).sum(-1) / np.sqrt((a * a).sum(-1) * (b * b).sum(-1))


def readings(prog: np.ndarray, ref: np.ndarray, rounded: np.ndarray,
             served: np.ndarray) -> dict:
    """The numbers over stacked positions: program, float32 reference and
    rounded reference logits ``[n, vocab]``, and the tokens served there
    ``[n]``."""
    idx = np.arange(len(served))
    d_prog = float(decorrelation(prog, ref).mean())
    d_round = float(decorrelation(rounded, ref).mean())
    return {
        "decorrelation_excess": d_prog / d_round - 1.0,
        "served_not_argmax": int(np.sum(prog.argmax(-1) != served)),
        "decorrelation": d_prog,
        "rounding_decorrelation": d_round,
        "widest_gap": float(np.max(ref.max(-1) - ref[idx, served])),
        "positions": int(len(served)),
    }


def verdict(numbers: dict, limits: dict) -> bool:
    """Correct when every compared number is at or under its limit."""
    return all(numbers[k] <= v for k, v in limits.items())
