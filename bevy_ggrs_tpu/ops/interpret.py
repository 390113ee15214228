"""Where this package's Pallas kernels run interpreted.

One rule for all five ``pallas_call`` sites (checksum, the two dense
pairwise kernels, the grid cell kernel, the in-place ring row write):
compiled by Mosaic on a TPU, interpreted on any other backend (the CPU
test mesh). Nothing else flips it — no argument, no environment variable
— so a kernel Mosaic refuses fails loudly on the chip instead of quietly
running interpreted, and ``chip_smoke.py`` can assert the one resolved
value.
"""

from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    return jax.default_backend() != "tpu"
