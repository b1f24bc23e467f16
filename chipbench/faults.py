"""Faults planted under the engine, which the check has to catch.

Each is a lane runner broken in one way that a serving cell can have:

- ``stale_state``: a step that returns its state unchanged: decode does
  not write its new cache rows back;
- ``half_batch``: half of the batch left out: decode computes the first
  half of its live rows (rounded down) and hands the rest the token its
  first live row was fed;
- ``altered_token``: a token altered where it is produced: every fifth
  decode returns the next token id in each row.

A one-chip cell has no exchange between chips to leave out.
``chipbench/tests/test_faults.py`` runs each at a reduced width on the CPU;
``chipbench/control.py --fault <name>`` runs one at a cell's own size.
"""
import numpy as np

from chipbench import serve


class StaleState(serve.SpannedRunner):
    def decode(self, tokens, pos, tables):
        pool = self._r.pool
        out = super().decode(tokens, pos, tables)
        self._r.pool = pool
        return out


class HalfBatch(serve.SpannedRunner):
    def decode(self, tokens, pos, tables):
        tables = np.array(tables)
        live = np.flatnonzero(tables[:, 0] != self._r.n_pages)
        out_rows = live[len(live) // 2:]
        tables[out_rows] = self._r.n_pages
        out = np.array(super().decode(tokens, pos, tables))
        if len(live):
            out[out_rows] = tokens[live[0]]
        return out


class AlteredToken(serve.SpannedRunner):
    n = 0

    def decode(self, tokens, pos, tables):
        out = np.array(super().decode(tokens, pos, tables))
        self.n += 1
        if self.n % 5 == 0:
            out = (out + 1) % self._r.cfg.vocab
        return out


FAULTS = {"stale_state": StaleState, "half_batch": HalfBatch,
          "altered_token": AlteredToken}
