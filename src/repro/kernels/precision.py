"""Mixed-precision policy shared by every projector kernel pair.

One idiom, applied uniformly (see docs/KERNELS.md "Precision policy"):

* **Tiles** — the dominant HBM streams (volume lines for FP, sinogram
  stripes for BP) are cast to the *compute dtype* at the ``pallas_call``
  boundary, so VMEM blocks and DMA traffic shrink 2x at bf16.
* **Weights** — SF footprint weights are always *derived* in float32 from
  SMEM scalars (coordinates at bf16's 8-bit mantissa would corrupt the
  trapezoid geometry), then cast to the tile dtype right before the MXU
  contraction so both operands match (:func:`cast_like`).
* **Accumulation** — every contraction carries
  ``preferred_element_type=jnp.float32`` and every kernel output buffer is
  float32; partial sums never round through bf16.  The caller's dtype is
  restored only once, on the final result (:func:`store_tile` is the single
  point where an accumulator meets an output ref).

The policy is threaded as ``compute_dtype`` from ``Projector`` / ``get_ops``
through ``ops.py`` into each kernel entry point; ``None`` means "follow the
input's dtype" (f32 in -> f32 tiles, bf16 in -> bf16 tiles + f32 accum).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# bfloat16 has an 8-bit significand (incl. the hidden bit): one quantization
# step is 2^-8 relative.
BF16_EPS = 2.0 ** -8

# Documented relative error bound of a bf16-tile / f32-accumulate projection
# against the f32 oracle (max-abs error over max-abs reference).  Tile and
# weight quantization each contribute <= BF16_EPS relative per product and
# the SF weights are non-negative, so errors grow sublinearly under the f32
# accumulation; 12x covers the observed worst case with >2x margin.
BF16_FP_REL_BOUND = 12 * BF16_EPS            # ~= 0.047

# Matched-pair dot-test tolerance at bf16: the pair is still an exact
# transpose of the *quantized* operator, but the forward path quantizes the
# axially-convolved volume while the adjoint path quantizes the sinogram, so
# <Ax, y> and <x, A'y> differ by O(BF16_EPS) relative.  5x margin.
BF16_DOT_TOL = 5 * BF16_EPS                  # ~= 0.02

_SUPPORTED = ("float32", "bfloat16")
_ALIASES = {"f32": "float32", "fp32": "float32", "bf16": "bfloat16"}


def normalize(compute_dtype):
    """Canonicalize a compute-dtype policy value.

    ``None`` / ``"auto"`` -> ``None`` (follow the input dtype); otherwise the
    canonical jnp dtype name (``"float32"`` | ``"bfloat16"``).  Accepts
    strings, numpy/jnp dtypes and scalar types; raises ``ValueError`` for
    anything outside the supported policy set.  The returned name is stable
    and hashable — it is what goes into the op-cache key."""
    if compute_dtype is None or compute_dtype == "auto":
        return None
    if isinstance(compute_dtype, str):
        name = _ALIASES.get(compute_dtype, compute_dtype)
    else:
        try:
            name = jnp.dtype(compute_dtype).name
        except TypeError as e:
            raise ValueError(f"bad compute_dtype {compute_dtype!r}") from e
    if name not in _SUPPORTED:
        raise ValueError(
            f"unsupported compute_dtype {compute_dtype!r}; expected one of "
            f"{_SUPPORTED} (or None/'auto' to follow the input dtype)")
    return name


def resolve(compute_dtype, in_dtype):
    """The dtype kernel tiles are cast to at the VMEM boundary."""
    name = normalize(compute_dtype)
    return jnp.dtype(in_dtype) if name is None else jnp.dtype(name)


def cast_in(x, compute_dtype):
    """Cast a kernel input (the dominant HBM stream) to the compute dtype at
    the ``pallas_call`` boundary.  No-op on the f32 path."""
    dt = jnp.dtype(compute_dtype)
    return x if x.dtype == dt else x.astype(dt)


def cast_like(w, tile):
    """Cast on-the-fly f32 footprint weights to the streamed tile's dtype so
    the MXU contraction runs operand-matched (bf16 x bf16 with
    ``preferred_element_type=f32`` accumulation).  No-op on the f32 path."""
    return w.astype(tile.dtype)


def mxu_dot(a, b, contract=((1,), (0,))):
    """In-kernel MXU contraction of two same-dtype tiles with f32
    accumulation.  The precision is explicit: full f32 for f32 tiles (what
    the f32 oracles are matched against), native for bf16 ones.  Left
    implicit, an enclosing ``jax.default_matmul_precision("highest")``
    would ask Mosaic for an f32 contraction of bf16 operands, which it
    refuses."""
    prec = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def store_tile(out_ref, idx, acc):
    """Accumulate a float32 tile into the output ref *in the ref's dtype* —
    the single output-dtype policy point shared by all kernel pairs."""
    out_ref[idx] += acc.astype(out_ref.dtype)
