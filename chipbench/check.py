"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed, goes through the
configuration's plain float32 reference: one forward pass over each prompt
with its served tokens.  Two numbers are compared, each with its limit in
the configuration's file (the readings that set it are in ``PERF.md``):

  logit_gap  the widest gap by which a served token's reference logit lies
             below the reference's best logit at that position.  Greedy
             decoding serves the best token, so a sound program reads no
             more than the near-ties its rounding can flip; a token altered
             or decoded from a broken cache reads of order one.
  logit_err  the program's logits after its last decode step (the engine's
             ``ServeStats.logits``), against the reference's at that
             position: root-mean-square difference over the reference's
             standard deviation across the vocabulary.  It depends on the
             prompt and every served token through the cache, and reads
             the precision of the whole path.

The control puts the reference computed in float8 in the program's place:
its first-ranked token at each served position, and its last logits.
"""
from __future__ import annotations

import importlib
import zlib
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Check:
    name: str
    value: Optional[float]          # None: nothing could be compared
    limit: Optional[float]

    @property
    def ok(self) -> bool:
        return (self.value is not None and self.limit is not None
                and self.value <= self.limit)

    def line(self) -> str:
        return (f"check {self.name}: {self.value!r} limit {self.limit!r} "
                f"{'ok' if self.ok else 'FAIL'}")


def reference(model: dict):
    return importlib.import_module(f"chipbench.references.{model['reference']}")


def sample(requests: Sequence, seed: int, n: int) -> List:
    """Up to ``n`` finished requests drawn from the seed, the longest
    among them, in the order they were sent."""
    done = [r for r in requests if r.tokens is not None]
    if len(done) <= n:
        return done
    longest = max(range(len(done)), key=lambda i: len(done[i].tokens))
    rng = np.random.default_rng([seed, zlib.crc32(b"check")])
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng.choice(rest, size=n - 1, replace=False)
    return [done[i] for i in sorted([longest, *pick.tolist()])]


def token_gaps(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Best reference logit minus the reference logit of each token."""
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.std(want))


def readings(weights, model: dict, prompt: np.ndarray, tokens: np.ndarray,
             last_logits: Optional[np.ndarray], *,
             fp8: bool = False) -> Tuple[float, float]:
    """(logit_gap, logit_err) of one served request.  With ``fp8`` they are
    the control's: the float8 reference in the program's place."""
    ref = reference(model)
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq))
    want = ref.logits_at(weights, model, seq, rows)
    if fp8:
        low = ref.logits_at(weights, model, seq, rows, fp8=True)
        tokens, last_logits = low[:-1].argmax(-1), low[-1]
    gap = float(token_gaps(want[:-1], tokens).max())
    return gap, rel_err(np.asarray(last_logits, np.float32), want[-1])
