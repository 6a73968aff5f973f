"""Lane-dense 2-D views of a buffer for the elementwise/reduction kernels.

The TPU compiler tiles the last two dims of a VMEM block by (8, 128) for
f32 and (32, 128) for int8, and a reshape that changes the last dim of a
tiled array is a relayout copy in HBM, not a bitcast.  So a buffer whose
last dim is already a multiple of 128, and whose second-to-last a multiple
of 32, is viewed as (M, N) by merging only its leading dims — no copy.
Any other buffer is flattened and zero-padded to (m, LANES).  Blocks are
(rows, lanes) with rows a multiple of 32 (so int8 levels tile like f32)
and lanes a multiple of 128, both dividing the view exactly."""
from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax.numpy as jnp

LANES = 1024                   # lane width of the flattened view
ROW_ALIGN = 32                 # sublane tile of int8
BLOCK_BYTES = 2 << 20          # f32 bytes of one input block, all lead rows


class View(NamedTuple):
    x: jnp.ndarray                       # (*lead, M, N)
    rows: int
    lanes: int
    restore: Callable[[jnp.ndarray], jnp.ndarray]   # (M, N) -> inner shape

    @property
    def grid(self) -> Tuple[int, int]:
        M, N = self.x.shape[-2:]
        return M // self.rows, N // self.lanes


def _largest_divisor(n: int, unit: int, cap: int) -> int:
    """Largest multiple of ``unit`` that divides ``n`` and is <= cap (at
    least ``unit``; ``n`` is a multiple of ``unit``)."""
    best = unit
    for d in range(unit, min(n, cap) + 1, unit):
        if n % d == 0:
            best = d
    return best


def lane_view(x: jnp.ndarray, lead: int = 0) -> View:
    """View ``x`` as (*x.shape[:lead], M, N) for blocked kernels.  The
    ``lead`` dims ride whole in every block (the stacked replicas)."""
    lead_shape, inner = x.shape[:lead], x.shape[lead:]
    n = 1
    for d in inner:
        n *= d
    per_elem = 4
    for d in lead_shape:
        per_elem *= d
    budget = max(ROW_ALIGN * 128, BLOCK_BYTES // per_elem)   # elements
    if (len(inner) >= 2 and inner[-1] % 128 == 0
            and inner[-2] % ROW_ALIGN == 0):
        N = inner[-1]
        M = n // N
        lanes = N if ROW_ALIGN * N <= budget else _largest_divisor(
            N, 128, budget // ROW_ALIGN)
        rows = _largest_divisor(M, ROW_ALIGN, budget // lanes)
        return View(x.reshape(lead_shape + (M, N)), rows, lanes,
                    lambda y: y.reshape(inner))
    need = -(-n // LANES)
    rows = min(max(ROW_ALIGN, budget // LANES // ROW_ALIGN * ROW_ALIGN),
               -(-need // ROW_ALIGN) * ROW_ALIGN)
    flat = x.reshape(lead_shape + (n,))
    pad = (-n) % (rows * LANES)
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * lead + [(0, pad)])
    return View(flat.reshape(lead_shape + (-1, LANES)), rows, LANES,
                lambda y: y.reshape(-1)[:n].reshape(inner))
