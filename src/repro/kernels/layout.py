"""What the hashing kernels share: where a kernel runs, and word shifts.

* :func:`pallas_call` lowers a kernel through Mosaic when the launch
  targets a TPU and through the Pallas interpreter on any other backend
  (the CPU the tests run on).  ``jax.lax.platform_dependent`` makes the
  choice while lowering, from the platform of the device the launch is
  placed on, so no caller passes a flag and a TPU launch can never run
  interpreted.  :func:`interprets` states the same rule for a device.
* The stream kernels hold a word stream as rows of :data:`LANES` words,
  so one ``(SUBLANES, LANES)`` vreg tile is fully used.  A shift by
  ``k`` words along the flattened stream is then a lane roll plus a
  one-row carry (:func:`shift_words`) instead of a slice at an unaligned
  lane offset, which Mosaic cannot lower.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128            # minor (lane) dim of a vreg tile
SUBLANES = 8           # second-minor (sublane) dim of a 32-bit vreg tile


def interprets(platform: str) -> bool:
    """True where :func:`pallas_call` runs the Pallas interpreter."""
    return platform != "tpu"


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call`` whose lowering follows the target platform."""
    mosaic = pl.pallas_call(kernel, **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=mosaic,
                                          default=interpreted)
    return call


def shift_words(x: jax.Array, k: int) -> jax.Array:
    """``y[p] = x[p + k]`` over the row-major flattening of ``x`` [n, LANES].

    ``0 < |k| < LANES``.  The ``|k|`` positions whose source lies
    outside ``x`` (the last words for ``k > 0``, the first for
    ``k < 0``) hold wrapped-around garbage; callers give ``x`` one
    spare row of context on that side and never read those positions.
    """
    if k == 0:
        return x
    assert 0 < abs(k) < LANES, k
    n = x.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    a = pltpu.roll(x, (-k) % LANES, 1)       # a[s, l] = x[s, (l + k) % LANES]
    if k > 0:
        carry = pltpu.roll(a, n - 1, 0)      # carry[s] = a[s + 1]
        return jnp.where(lane < LANES - k, a, carry)
    carry = pltpu.roll(a, 1, 0)              # carry[s] = a[s - 1]
    return jnp.where(lane >= -k, a, carry)
