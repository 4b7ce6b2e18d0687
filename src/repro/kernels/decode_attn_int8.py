"""Pallas decode attention over the int8 KV cache (serving per-step hot loop).

``nn.attention.decode_attention_int8`` already runs the fully-integer math
(int8 QK^T, per-row K scales folded into the scores, softmax weights
requantized to int8 for the PV dot) but as unfused XLA einsums: the (B,T,
Hkv,D) score/probability intermediates round-trip HBM every decode step.
This kernel executes the identical computation per (batch, kv-head) pair in
one VMEM pass over that sequence's cache rows — the same quantization
definitions, in the same order, so the kernel and the XLA path agree to
float-rounding tolerance.

Grid: (B,), parallel; each step holds one sequence's whole cache slab
(T, Hkv, D) — T is bounded by the engine's ``max_len`` and the G = Hq/Hkv
query group stays whole, since decode caches are small slabs, unlike the
unbounded spatial maps that force tiling elsewhere.  Blocks keep their last
two dims whole (the TPU's (8, 128) tiling rule) and the kv heads are a
static loop inside the kernel; ``lengths`` rides in SMEM.  The wrapper may
zero-pad T; padded rows sit at positions >= ``lengths`` and are masked
exactly like unfilled cache rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30  # matches nn.attention's finite mask


def _kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, len_ref, o_ref, *,
            Hkv: int, T: int, scale: float, window: Optional[int]):
    length = len_ref[pl.program_id(0), 0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    valid = pos < length
    if window is not None:
        valid &= pos >= (length - window)
    ks = ks_ref[0].T                                          # (Hkv, T)
    vs = vs_ref[0].T                                          # (Hkv, T)
    for h in range(Hkv):
        qh = q_ref[0, h].astype(jnp.float32)                  # (G, D)
        # per-(b,h,g) on-the-fly q quantization — same expression as the
        # XLA path
        q_s = jnp.max(jnp.abs(qh), axis=-1, keepdims=True) / 127.0 + 1e-9
        q8 = jnp.clip(jnp.round(qh / q_s), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(q8, k_ref[0, :, h, :],
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.int32)  # (G, T)
        s = acc.astype(jnp.float32) * q_s * scale * ks[h:h + 1]
        s = jnp.where(valid, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # fold per-row V scales into p, requantize, int8 PV dot
        pv = p * vs[h:h + 1]
        p_s = jnp.max(jnp.abs(pv), axis=-1, keepdims=True) / 127.0 + 1e-12
        p8 = jnp.clip(jnp.round(pv / p_s), -127, 127).astype(jnp.int8)
        out = jax.lax.dot_general(p8, v_ref[0, :, h, :],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)  # (G, D)
        o_ref[0, h] = out.astype(jnp.float32) * p_s


def decode_attn_int8(q: jax.Array, k_q: jax.Array, v_q: jax.Array,
                     k_scale: jax.Array, v_scale: jax.Array,
                     lengths: jax.Array, *, scale: float,
                     window: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """q (B,Hkv,G,D) float; k_q/v_q (B,T,Hkv,D) int8; k_scale/v_scale
    (B,T,Hkv) f32 per-row; lengths (B,1) int32 -> out (B,Hkv,G,D) f32."""
    B, Hkv, G, D = q.shape
    T = k_q.shape[1]
    cache_spec = pl.BlockSpec((1, T, Hkv, D), lambda b: (b, 0, 0, 0))
    rows_spec = pl.BlockSpec((1, T, Hkv), lambda b: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, Hkv=Hkv, T=T, scale=scale, window=window),
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b: (b, 0, 0, 0)),
            cache_spec,
            cache_spec,
            rows_spec,
            rows_spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, Hkv, G, D), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="decode_attn_int8",
    )(q, k_q, v_q, k_scale, v_scale, lengths)
