"""Pallas kernel: blocked exclusive suffix-sum — the SIC interference scan.

The NOMA SIC power engine (``repro.core.sic``) refreshes, once per Jacobi
sweep, the suffix interference every client sees from later-decoded clients
(paper Eq. 36 denominator):

    s[n] = Σ_{j>n} w[j],         w[j] = p_j · |h_j|²

i.e. an EXCLUSIVE suffix sum along the client axis.  Same fusion idea as
``ssd_scan``: the grid's last dimension walks the N axis in blocks using the
sequential-grid property ("arbitrary" dimension semantics), carrying the
running suffix total in a VMEM scratch accumulator — blocks are visited
right-to-left via a reversed ``index_map``, so the carry entering block b is
exactly the sum of all blocks after it.

Within a block the exclusive suffix sum is one MXU-shaped matmul against a
strictly-lower-triangular ones matrix ([L]·[L×L]: row k contributes to
column i iff k > i) — no flips or cumsums inside the kernel, so the same
body lowers on TPU and runs under interpret mode on CPU.

Layout: w [B, N] → s [B, N]; f32 accumulation.  Each grid step moves a
``[ROWS, block]`` tile (``ROWS`` = 8, the TPU sublane count), so B is
zero-padded up to a ``ROWS`` multiple and N up to a ``block``
multiple by the wrapper, and both pads are sliced off the result
(trailing zeros contribute nothing to any real element's suffix, and a
padded row is never read back).  The in-block product is a 2-D
``[ROWS, L] @ [L, L]`` MXU matmul at ``HIGHEST`` precision — the TPU's
default f32 dot is a single bf16 pass, which would cost ~3 significant
digits of the interference sum.  The carry scratch holds one running
total per row.

Masked-tail contract (ragged-N serving): the allocation service pads
variable-N requests with zero-gain clients, so w = p·|h|² carries an
all-zero tail BEFORE this wrapper adds its own block padding.  Both tails
compose: a zero element adds exactly 0.0 to the carry and to every
in-block matmul row, so s over the real prefix is bit-identical to the
kernel run on the truncated exact-N input — in f32 this is exact
(x + 0.0 == x), not approximate.  Asserted against ref and interpret
modes in tests/test_sic.py::TestPaddedTail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8    # rows per grid step: the TPU's f32 sublane count


def _suffix_kernel(w_ref, o_ref, carry_ref, *, block: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    w = w_ref[...].astype(jnp.float32)                    # [R, L]
    # strict[k, i] = 1 iff k > i : w @ strict == exclusive in-block suffix
    ks = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    is_ = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    strict = (ks > is_).astype(jnp.float32)
    carry = carry_ref[...]                                # [R, 1] later blocks
    s = jnp.dot(w, strict, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST) + carry
    carry_ref[...] = carry + jnp.sum(w, axis=1, keepdims=True)
    o_ref[...] = s.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sic_suffix_pallas(w, block: int = 128, interpret: bool = True):
    """w: [B, N] → exclusive suffix sums [B, N] (s[b, n] = Σ_{j>n} w[b, j]).

    ``interpret=True`` executes on CPU for validation; on TPU pass False
    (``block`` must then be a multiple of 128).
    """
    b, n = w.shape
    pad_b, pad_n = (-b) % ROWS, (-n) % block
    wp = jnp.pad(w, ((0, pad_b), (0, pad_n))) if pad_b or pad_n else w
    nb, nc = wp.shape[0] // ROWS, wp.shape[1] // block

    kern = functools.partial(_suffix_kernel, block=block)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    out = pl.pallas_call(
        kern,
        grid=(nb, nc),
        # blocks are visited right-to-left: grid step j touches block
        # nc-1-j, so the carry accumulates the suffix of later blocks
        in_specs=[pl.BlockSpec((ROWS, block), lambda i, j: (i, nc - 1 - j))],
        out_specs=pl.BlockSpec((ROWS, block), lambda i, j: (i, nc - 1 - j)),
        out_shape=jax.ShapeDtypeStruct(wp.shape, w.dtype),
        scratch_shapes=[pltpu.VMEM((ROWS, 1), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(wp)
    return out[:b, :n] if pad_b or pad_n else out
