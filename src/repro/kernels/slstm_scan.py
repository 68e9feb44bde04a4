"""Pallas TPU kernel: sLSTM recurrence with VMEM-resident weights.

The xlstm-1.3b train cell's dominant roofline term is the strictly
sequential sLSTM scan: in XLA-land each of the 4096 timesteps re-streams the
recurrent matrix R from HBM (measured: the memory term is ~10⁴ s/step for
the full train cell — EXPERIMENTS.md §Perf xlstm).  This kernel is the
designed fix: R is block-diagonal per head ((h, dh, 4·dh) ≈ 8 MB bf16 for
xlstm-1.3b), which FITS IN VMEM — so the kernel loads it once per grid
step and runs the whole time loop against the resident copy.  HBM traffic
collapses to the gates_x stream (read once) + hidden-state outputs.

This replays the paper's central lesson — "size the compute unit so the
memory system, not the schedule, is the limit" — on a layer the paper never
met: the FPGA keeps INT4 weights streaming from HBM at full rate; here we
keep recurrent weights OUT of HBM entirely.

Grid: (batch, L / Lc) with the time axis "arbitrary"; the (c, n, h, m)
state lives in VMEM scratch and persists across time chunks.  Numerics ==
``repro.models.xlstm._slstm_step`` scan (tested).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["slstm_scan_pallas"]


def _kernel(gx_ref, r_ref, b_ref, out_ref, c_ref, n_ref, h_ref, m_ref,
            *, lc: int, heads: int, dh: int):
    t_chunk = pl.program_id(1)

    @pl.when(t_chunk == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        h_ref[...] = jnp.zeros_like(h_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)

    r = r_ref[...].astype(jnp.float32)              # (h, dh, 4dh) — resident
    bias = b_ref[...].astype(jnp.float32)           # (h, 4dh)

    def step(t, _):
        gx = gx_ref[0, t].astype(jnp.float32)       # (h, 4dh)
        hid = h_ref[...]
        recur = jax.lax.dot_general(
            hid[:, None, :], r, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)[:, 0, :]   # (h, 4dh)
        gates = gx + recur + bias
        z_t = jnp.tanh(gates[:, :dh])
        i_t = gates[:, dh:2 * dh]
        f_t = gates[:, 2 * dh:3 * dh]
        o_t = jax.nn.sigmoid(gates[:, 3 * dh:])
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m_ref[...], i_t)
        i_act = jnp.exp(i_t - m_new)
        f_act = jnp.exp(logf + m_ref[...] - m_new)
        c_new = f_act * c_ref[...] + i_act * z_t
        n_new = jnp.maximum(f_act * n_ref[...] + i_act, jnp.exp(-m_new))
        h_new = o_t * c_new / n_new
        c_ref[...] = c_new
        n_ref[...] = n_new
        h_ref[...] = h_new
        m_ref[...] = m_new
        out_ref[0, t] = h_new.astype(out_ref.dtype)
        return 0

    jax.lax.fori_loop(0, lc, step, 0)


@functools.partial(jax.jit, static_argnames=("time_chunk", "interpret"))
def slstm_scan_pallas(
    gates_x: jax.Array,      # (b, L, h, 4*dh) — precomputed input gates
    r_gates: jax.Array,      # (h, dh, 4*dh) block-diagonal recurrent weights
    b_gates: jax.Array,      # (h, 4*dh)
    *,
    time_chunk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Returns hidden states (b, L, h, dh)."""
    b, L, heads, g4 = gates_x.shape
    dh = g4 // 4
    lc = min(time_chunk, L)
    if L % lc:
        raise ValueError(f"L={L} not a multiple of time_chunk={lc}")

    kernel = functools.partial(_kernel, lc=lc, heads=heads, dh=dh)
    out = pl.pallas_call(
        kernel,
        grid=(b, L // lc),
        in_specs=[
            pl.BlockSpec((1, lc, heads, g4), lambda i, t: (i, t, 0, 0)),
            pl.BlockSpec((heads, dh, g4), lambda i, t: (0, 0, 0)),
            pl.BlockSpec((heads, g4), lambda i, t: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, lc, heads, dh), lambda i, t: (i, t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, L, heads, dh), gates_x.dtype),
        scratch_shapes=[pltpu.VMEM((heads, dh), jnp.float32)] * 3
        + [pltpu.VMEM((heads, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="slstm_scan",
        interpret=interpret,
    )(gates_x, r_gates, b_gates)
    return out
