"""One device-to-host transfer per harvest (engine/bfs ``Engine.check``).

A harvest reads a level's rows to the host: parents, lanes, every state
leaf and, where the level broke an invariant, the invariant bits.  Read
leaf by leaf that is one dispatch and one blocking copy per leaf, about
18 round trips a level, and each costs a fixed latency whatever its
size.  Here one jitted program slices every leaf to the rows the
harvest needs, packs each into uint32 words (``bool`` as ``uint8``) and
concatenates them into one flat buffer; the host reads it once and
splits it back into arrays with the leaves' dtypes and sliced shapes,
batch-last as the leaves are.

Row counts round up to a few buckets (``row_bucket``), so a handful of
pack programs serve every level of every check at one capacity set.
"""

from __future__ import annotations

import functools
import operator
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def row_bucket(n: int, chunk: int, cap: int) -> int:
    """Rows a pack moves for ``n`` live rows: ``n`` rounded up to 1,
    1.25, 1.5 or 1.75 x 2^k chunks (whole chunks up to 4), so at most
    four pack programs per doubling and, past four chunks, at most 25%
    padding; never more than the buffer's ``cap`` rows."""
    c = max(1, -(-int(n) // chunk))
    if c > 4:
        step = 1 << (c.bit_length() - 3)
        c = -(-c // step) * step
    return min(c * chunk, int(cap))


def _words(x, start, rows: int, levels: Optional[int]):
    """A leaf cut to ``rows`` rows from row ``start`` (the last axis;
    with ``levels`` its first ``levels`` ring levels too), zero-padded
    to a multiple of 4 rows, as uint32 words.  A 32-bit leaf is its own
    words.  A narrower leaf's k = 4 / itemsize row blocks (contiguous
    slices of q = rows / k rows each) share words: word m holds rows
    m, q + m, ... in its k byte groups, low first (``unpack`` undoes
    it).  Neither a trailing byte axis, which the chip tiles to 128
    lanes (a 32x copy), nor strided slices, whose program keeps about
    5 MB of code on the chip per row bucket (TPU v5e), is used."""
    if levels is not None:
        x = x[..., :levels, :]
    x = lax.dynamic_slice_in_dim(x, start, rows, axis=x.ndim - 1)
    pad = -rows % 4
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    u = lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[size]
    ).astype(jnp.uint32)
    k = 4 // size
    if k > 1:
        q = u.shape[-1] // k
        u = functools.reduce(operator.or_, (
            u[..., j * q:(j + 1) * q] << (8 * size * j) for j in range(k)))
    return u.reshape(-1)


@functools.partial(jax.jit, static_argnames=("rows", "levels"))
def pack(leaves: Sequence, start, rows: int,
         levels: Optional[int] = None):
    """Every leaf cut to ``rows`` rows from row ``start`` (and to
    ``levels``), as one flat uint32 buffer: the leaves' bytes back to
    back, in their order.  ``start`` is traced: one program serves
    every block of a level."""
    return jnp.concatenate([_words(x, start, rows, levels)
                            for x in leaves])


@functools.lru_cache(maxsize=256)
def _layout(specs: Tuple, rows: int,
            levels: Optional[int]) -> Tuple[Tuple[int, np.dtype, tuple], ...]:
    padded = rows + -rows % 4
    out, off = [], 0
    for shape, dtype in specs:
        shape = (shape[:-1] + (padded,) if levels is None
                 else shape[:-2] + (levels, padded))
        dt = np.dtype(dtype)
        out.append((off, dt, shape))
        off += int(np.prod(shape)) * dt.itemsize
    return tuple(out)


def layout(leaves: Sequence, rows: int, levels: Optional[int] = None):
    """(byte offset, dtype, padded shape) of each leaf's part of
    ``pack``'s buffer, computed from the leaves' shapes and dtypes alone
    (once per capacity set and bucket).  Every part is a whole number
    of 4-row groups, so each offset is aligned to its dtype."""
    return _layout(tuple((tuple(x.shape), np.dtype(x.dtype).str)
                         for x in leaves), int(rows), levels)


def unpack(buf: np.ndarray, lay, rows: int) -> List[np.ndarray]:
    """``pack``'s buffer (read as uint32) split back into one host array
    per leaf, with the leaf's dtype and cut shape: a view for a 32-bit
    leaf, a copy with its row blocks back in order for a narrower one
    (``bool`` leaves read back from their bytes)."""
    b = buf.view(np.uint8)
    out = []
    for off, dt, shape in lay:
        x = b[off:off + int(np.prod(shape)) * dt.itemsize].view(dt)
        k = 4 // dt.itemsize
        if k > 1:
            x = np.ascontiguousarray(x.reshape(
                shape[:-1] + (shape[-1] // k, k)).swapaxes(-1, -2))
        out.append(x.reshape(shape)[..., :rows])
    return out
