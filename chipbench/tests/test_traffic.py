"""Every seed gets the same amount of work, in another order."""
import numpy as np

import traffic
from tiny import DECODE


def test_closed_batch_contexts():
    a = traffic.generate(DECODE, 3, 97)
    b = traffic.generate(DECODE, 2**31 + 4, 97)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                       for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(r.max_new == DECODE["max_new_tokens"] for r in a)
    lo, hi = DECODE["context_tokens"]["min"], DECODE["context_tokens"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)


def test_closed_batch_deterministic():
    a = traffic.generate(DECODE, 7, 97)
    b = traffic.generate(DECODE, 7, 97)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
