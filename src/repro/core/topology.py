"""2D-Torus topology mapping (paper §2.2, Table 4).

The paper arranges N GPUs in an X (horizontal) x Y (vertical) logical grid
and decomposes the gradient all-reduce into
    reduce-scatter along X  ->  all-reduce along Y (1/X volume)  ->  all-gather along X.

On a JAX mesh the grid is expressed with *named axes*. Two situations:

1. The mesh already has >=2 data-parallel axes (e.g. ``("pod", "data")``):
   the torus maps directly -- X = the fast intra-pod axis, Y = the slow
   inter-pod axis, so the slow links carry 1/X of the bytes (the paper's
   core win, transplanted to TPU DCI).

2. A single data-parallel axis (e.g. ``data=16`` on one pod): we factorize
   it into an internal X*Y grid by *reshaping the mesh* before building the
   step function. ``factorize()`` picks X,Y the way the paper's Table 4
   does: as close to square as possible, with X >= Y (horizontal no smaller
   than vertical, matching e.g. 48x72, 64x64 in Table 4).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np


def factorize(n: int) -> tuple[int, int]:
    """Split n into (Y, X), X >= Y, as square as possible (paper Table 4)."""
    if n < 1:
        raise ValueError(f"need n >= 1, got {n}")
    y = int(math.isqrt(n))
    while n % y != 0:
        y -= 1
    x = n // y
    # paper lists grids as (vertical, horizontal) with horizontal >= vertical
    if y > x:
        x, y = y, x
    return y, x


@dataclasses.dataclass(frozen=True)
class TorusGrid:
    """Named-axis description of the logical 2D torus.

    ``h_axes``: mesh axes forming the horizontal rings (reduce-scatter /
    all-gather phases). ``v_axes``: mesh axes forming the vertical rings
    (the middle all-reduce phase, which carries 1/X of the data).
    """

    h_axes: tuple[str, ...]
    v_axes: tuple[str, ...]

    @property
    def axes(self) -> tuple[str, ...]:
        return self.v_axes + self.h_axes

    def sizes(self, mesh: jax.sharding.Mesh | jax.sharding.AbstractMesh) -> tuple[int, int]:
        """(X, Y) sizes of the torus on a concrete mesh."""
        x = int(np.prod([mesh.shape[a] for a in self.h_axes])) if self.h_axes else 1
        y = int(np.prod([mesh.shape[a] for a in self.v_axes])) if self.v_axes else 1
        return x, y

    def steps(self, mesh) -> int:
        """Ring GPU-to-GPU steps: 2(X-1) horizontal + (vertical AR steps).

        Paper counts 2(X-1) for the horizontal phases; the vertical ring
        all-reduce adds 2(Y-1) steps on 1/X volume.
        """
        x, y = self.sizes(mesh)
        return 2 * (x - 1) + 2 * (y - 1)


def select_grid(dp_axes: Sequence[str]) -> TorusGrid:
    """Choose the torus orientation given the data-parallel mesh axes.

    With multiple DP axes the *last* axis (fastest-varying / intra-pod) is
    horizontal and the leading axes are vertical: the slow inter-pod links
    then carry the 1/X-reduced middle phase.
    """
    dp_axes = tuple(dp_axes)
    if not dp_axes:
        raise ValueError("at least one data-parallel axis required")
    if len(dp_axes) == 1:
        # degenerate: no second axis to split over -- callers who want a true
        # 2D torus should give the mesh two data-parallel axes.
        return TorusGrid(h_axes=dp_axes, v_axes=())
    return TorusGrid(h_axes=(dp_axes[-1],), v_axes=tuple(dp_axes[:-1]))


def paper_table4_grid(n_gpus: int) -> tuple[int, int]:
    """The grid dimensions the paper used (Table 4), for the benchmark."""
    table = {1024: (32, 32), 2048: (32, 64), 2176: (34, 64), 3456: (48, 72), 4096: (64, 64)}
    if n_gpus in table:
        return table[n_gpus]
    return factorize(n_gpus)
