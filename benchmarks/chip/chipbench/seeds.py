"""Seeds for each purpose, drawn from the run's ``--seed``.

``--seed`` may exceed 32 bits; each purpose gets its own 32-bit stream so
that the weights, the rows and the schedule of one seed never share one.
"""

from __future__ import annotations

import zlib

import numpy as np


def sub_seed(seed: int, purpose: str) -> int:
    """A 31-bit seed for ``purpose``, fixed by ``seed`` (any int >= 0)."""
    ss = np.random.SeedSequence([int(seed), zlib.crc32(purpose.encode())])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)
