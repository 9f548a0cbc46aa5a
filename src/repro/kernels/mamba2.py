"""Chunked SSD (Mamba-2 state-space duality) as a Pallas TPU kernel.

The SSD decomposition splits the linear recurrence into

  * intra-chunk terms  -- (Q x N)@(N x Q) score GEMMs and (Q x Q)@(Q x P)
    output GEMMs: dense matmuls that run on the MXU; *this* is the part the
    Gemmini technique covers (the paper's thesis: GEMM is the common kernel),
  * an inter-chunk recurrence -- a length-``n_chunks`` scan over the (N x P)
    state, attention-free and sequential; carried in a VMEM scratch across
    the sequential grid axis.

Schedule: grid = (B, H, nc) with the chunk axis innermost ("arbitrary").
The (N, P) running state is the resident accumulator (Gemmini
output-stationary residency applied to the SSM state); per chunk the kernel
performs only 2-D dots:

  scores  = C (Q,N) @ B^T (N,Q)               [MXU]
  y_diag  = (scores * L * dt) (Q,Q) @ X (Q,P) [MXU]
  y_off   = exp(seg) * (C (Q,N) @ state (N,P))[MXU]
  state   = decay * state + (w * B)^T (N,Q) @ X (Q,P)  [MXU]

B/C group mapping (G groups shared GQA-style across H heads) is resolved in
the BlockSpec index maps, so no repeat/gather materializes.

Fusion audit (ROADMAP, mirroring the PR 2 conv/attention audit): the whole
chunk-scan epilogue is fused in-kernel -- the (N, P) running state lives in
a VMEM scratch across the sequential chunk axis (never HBM), the per-chunk
output write already includes the carried-state term AND the ``d_skip``
residual add (previously a post-kernel XLA pass that round-tripped y
through HBM), and the final recurrent state is emitted as a second kernel
output on the last chunk step (previously recomputed by a separate XLA
pass over the full inputs). The only HBM traffic is the streamed inputs,
one y write per chunk, and one (N, P) state write per (batch, head).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.contracts import kernel_contract


def _ssd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, *rest,
                nc: int, chunk: int):
    # rest = (fs_ref, state_ref) when the caller wants the final state
    # emitted, else (state_ref,): the fs output buffer only exists when
    # requested (a pallas output cannot be dead-code-eliminated).
    fs_ref = rest[0] if len(rest) == 2 else None
    state_ref = rest[-1]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    hh = pl.program_id(1)
    a = a_ref[hh]                                  # scalar: -exp(a_log)
    d_skip = d_ref[hh]                             # scalar skip weight
    dt = dt_ref[0, 0, 0].astype(jnp.float32)       # (1, Q) row
    x = x_ref[0, 0, 0].astype(jnp.float32)         # (Q, P)
    b = b_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)
    c = c_ref[0, 0, 0].astype(jnp.float32)         # (Q, N)

    # Mosaic has no cumsum and no row->column relayout of a vector, so the
    # inclusive prefix sum and the column forms come from exact f32 GEMMs
    # against the lower-triangular ones and the identity:
    #   seg_r = dta @ tril^T (1, Q),  seg_c = tril @ dta^T (Q, 1).
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (ii >= jj).astype(jnp.float32)
    eye = (ii == jj).astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST

    def _mm(u, v):
        return jax.lax.dot_general(u, v, nt, precision=hi,
                                   preferred_element_type=jnp.float32)

    dta = dt * a                                   # (1, Q)
    seg_r = _mm(dta, tril)                         # (1, Q) inclusive cumsum
    seg_c = _mm(tril, dta)                         # (Q, 1) the same, column
    dt_c = _mm(eye, dt)                            # (Q, 1)

    # intra-chunk decay L[i, j] = exp(seg_i - seg_j) for i >= j else 0
    ldec = jnp.where(ii >= jj, jnp.exp(seg_c - seg_r), 0.0)

    # scores = C_i . B_j  (Q, Q): a GEMM on the engine schedule
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * ldec * dt, x,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # contribution of the carried-in state to every step of this chunk
    y_off = jax.lax.dot_general(c, state_ref[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + y_off * jnp.exp(seg_c)
    # fused epilogue: the d_skip residual rides the same f32 accumulator
    # (zero when the model has no skip weight -- an exact no-op)
    y_ref[0, 0, 0] = (y + d_skip * x).astype(y_ref.dtype)

    # state update: state = exp(seg_Q) * state + sum_j w_j B_j x_j^T
    seg_end = jnp.sum(dta)                         # scalar: seg at Q-1
    wb = b * (jnp.exp(seg_end - seg_c) * dt_c)     # (Q, N)
    ds = jax.lax.dot_general(wb, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = state_ref[...] * jnp.exp(seg_end) + ds

    if fs_ref is not None:
        @pl.when(ci == nc - 1)
        def _emit_state():
            # prefill->decode handoff: the carried VMEM state is the final
            # recurrent state (dt is zero on padded tail rows, so padding
            # neither decays nor feeds it) -- no XLA recompute pass.
            fs_ref[0, 0] = state_ref[...]


@kernel_contract("ssd")
def ssd(x: jnp.ndarray, dt: jnp.ndarray, a_log: jnp.ndarray, b: jnp.ndarray,
        c: jnp.ndarray, *, d_skip: Optional[jnp.ndarray] = None,
        chunk: int = 256, interpret: bool = False,
        return_final_state: bool = False):
    """x: (B,T,H,P), dt: (B,T,H) (softplus'd), a_log: (H,), b/c: (B,T,G,N).

    Returns y: (B,T,H,P) [and the final (B,H,N,P) state if requested].
    """
    bsz, t, h, p = x.shape
    _, _, g, n = b.shape
    hpg = h // g
    q = min(chunk, t)
    pad = (-t) % q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    tt = t + pad
    nc = tt // q

    # (B, H, nc, Q, ...) layouts so the last two dims are MXU tiles
    xt = jnp.moveaxis(x, 2, 1).reshape(bsz, h, nc, q, p)
    # (.., 1, Q): a (1, Q) last-two-dim block is a legal TPU tile
    dtt = jnp.moveaxis(dt, 2, 1).reshape(bsz, h, nc, 1, q)
    bt = jnp.moveaxis(b, 2, 1).reshape(bsz, g, nc, q, n)
    ct = jnp.moveaxis(c, 2, 1).reshape(bsz, g, nc, q, n)
    a = -jnp.exp(a_log.astype(jnp.float32))        # (H,)
    # d_skip rides SMEM like a_log; zeros when absent (exact no-op in the
    # fused f32 epilogue).
    d = jnp.zeros((h,), jnp.float32) if d_skip is None \
        else d_skip.astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, nc=nc, chunk=q)
    out_specs = [pl.BlockSpec((1, 1, 1, q, p),
                              lambda bb, hh, cc: (bb, hh, cc, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((bsz, h, nc, q, p), x.dtype)]
    if return_final_state:
        out_specs.append(pl.BlockSpec((1, 1, n, p),
                                      lambda bb, hh, cc: (bb, hh, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((bsz, h, n, p), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bb, hh, cc: (bb, hh, cc, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q),
                         lambda bb, hh, cc: (bb, hh, cc, 0, 0)),
            # whole (H,) vectors in SMEM, indexed by head in the body
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, q, n),
                         lambda bb, hh, cc: (bb, hh // hpg, cc, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, n),
                         lambda bb, hh, cc: (bb, hh // hpg, cc, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, a, d, bt, ct)

    y = out[0]
    y = jnp.moveaxis(y.reshape(bsz, h, tt, p), 1, 2)[:, :t]   # (B,T,H,P)
    if return_final_state:
        return y, out[1]
    return y
