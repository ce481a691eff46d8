"""IDD-Scan: intra-segment dependency-decoupled prefix sum (paper §V-D), TPU.

The paper's problem: Ascend AIV forbids SIMD ops between elements inside one
32-byte segment, so a flat prefix sum is "locked".  Their fix: transpose so
intra-row dependencies become inter-row ones, log-step scan, transpose back,
then propagate row offsets hierarchically.

TPU VPU has the same shape of constraint — cross-LANE shifts inside a vreg
are expensive, while full-register ops and the MXU are cheap.  The adaptation
(DESIGN.md §2): move the lane-axis dependency into the *matrix unit*:

  stage 1 (intra-row):  row_incl = M @ U, with U the (128,128) upper-
                        triangular ones matrix — a single MXU op replaces
                        log2(128) cross-lane shuffles.
  stage 2 (inter-row):  the exclusive row offsets as one strictly-lower-
                        triangular matmul over the row totals, then add
                        (the paper's log-step stage 2 lives on in the
                        codec kernels' group scan, ``lanes.group_count``).

Values are exact in f32 for sums < 2**24 — our masks sum to <= G <= 4096.

Kernel: ``idd_scan`` computes inclusive prefix sums along the flattened
(rows*128) axis for every batch row, ``lanes.ROWS`` batch rows per grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .lanes import step_rows

LANE = 128


def _upper_triangular(k: int, dtype=jnp.float32):
    r = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    return (r <= c).astype(dtype)


def _strict_lower(k: int, dtype=jnp.float32):
    r = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    return (r > c).astype(dtype)


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def scan_2d(mat):
    """Inclusive prefix sum of a (rows, LANE) f32 matrix flattened row-major.

    Pure jnp building block of the standalone kernel.  Both stages run on
    the MXU at HIGHEST precision, so integer sums stay exact up to 2**24.
    """
    rows, lane = mat.shape
    # stage 1: intra-row inclusive scan on the MXU
    row_incl = _dot(mat, _upper_triangular(lane, mat.dtype))
    # stage 2: inter-row propagation — every lane of row r gets the sum of
    # the totals of rows < r (one strictly-lower-triangular matmul)
    totals = jnp.broadcast_to(row_incl[:, lane - 1:], (rows, lane))
    return row_incl + _dot(_strict_lower(rows, mat.dtype), totals)


def exclusive_from_inclusive(incl, orig):
    return incl - orig


def _idd_scan_kernel(x_ref, o_ref, *, rows):
    for j in range(x_ref.shape[0]):
        mat = x_ref[j:j + 1].astype(jnp.float32).reshape(rows, LANE)
        o_ref[j:j + 1] = scan_2d(mat).reshape(1, rows * LANE).astype(
            o_ref.dtype)


def idd_scan(x, *, interpret=None):
    """Batched inclusive prefix sum: x (B, N) -> (B, N) int32, N % 128 == 0.

    ``lanes.ROWS`` batch rows per grid step (the last step may be partial); each
    row's (N/128, 128) working set lives in VMEM.  ``interpret=None``
    resolves like every other kernel entry: native on TPU, interpreter mode
    elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, n = x.shape
    assert n % LANE == 0, n
    rows = step_rows(b)
    fn = pl.pallas_call(
        functools.partial(_idd_scan_kernel, rows=n // LANE),
        grid=(pl.cdiv(b, rows),),
        in_specs=[pl.BlockSpec((rows, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=interpret,
        name="idd_scan",
    )
    return fn(x.astype(jnp.int32) if x.dtype == jnp.bool_ else x)
