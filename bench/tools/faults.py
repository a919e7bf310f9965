"""Faults planted in the program, for the tests and the readings tool.

Each takes ``patch(owner, name, value)`` (pytest's
``monkeypatch.setattr``, or the readings tool's own) and breaks the timed
path underneath the harness:

- ``state_unchanged``: the decode step returns its KV state unchanged, so
  no decoded token's K/V is kept and later tokens attend to stale pages;
- ``token_altered``: a token is altered where it is produced (every fifth
  decode token of the serve loop is replaced by its successor id).
"""

from __future__ import annotations


def state_unchanged(patch) -> None:
    from repro.models import lm

    inner = lm.decode_step_paged

    def broken(params, caches, tokens, positions, block_table, cfg):
        logits, _ = inner(params, caches, tokens, positions, block_table,
                          cfg)
        return logits, caches

    patch(lm, "decode_step_paged", broken)


def token_altered(patch) -> None:
    from repro.serve.paged import PagedServeLoop

    inner = PagedServeLoop._accept

    def broken(self, i, entry, tokens):
        if self.gen_tokens % 5 == 4:
            tokens = [(int(t) + 1) % self.cfg.vocab for t in tokens]
        return inner(self, i, entry, tokens)

    patch(PagedServeLoop, "_accept", broken)


FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered}
