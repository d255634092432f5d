"""Pallas TPU kernel: blocked online-softmax (flash) attention with GQA.

The LM substrate's compute hot-spot.  TPU adaptation: (Bq×D)·(D×Bk)
MXU tiles with the online-softmax recurrence carried in VMEM scratch
across the sequential kv grid dimension; causal blocks above the
diagonal band are skipped with `pl.when` (no work issued).  KV heads
are indexed through the BlockSpec index_map (no HBM materialization of
the GQA repeat — each q head streams its kv group's tiles directly).

The causal diagonal is aligned to the END of the kv axis, so the same
kernel serves training (Sq == Skv) and single-token / chunked decode
(Sq << Skv with a KV cache).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, sq: int, skv: int, skv_orig: int,
            bq: int, bk: int, n_kb: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Skip kv blocks strictly above the causal diagonal band.
    offset = skv - sq  # query i sits at absolute position i + offset
    q_last = qi * bq + (bq - 1) + offset
    live = (q_last >= ki * bk) if causal else True

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale          # (Bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (Bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Bq, Bk)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < skv_orig  # kv padding
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + offset
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), m_prev)
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(mask, p, 0.0)  # fully-masked rows stay at zero
        l_cur = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        vv = v_ref[0].astype(jnp.float32)                 # (Bk, D)
        pv = jax.lax.dot_general(p, vv, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(ki == n_kb - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _pow2_clip(n: int, lo: int, hi: int) -> int:
    p = 1 << max(0, (max(n, 1) - 1)).bit_length()
    return max(lo, min(hi, p))


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D), Hq % Hkv == 0.

    Returns (B, Hq, Sq, D) in q.dtype; accumulation in float32.
    """
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv
    scale = scale if scale is not None else float(D) ** -0.5

    bq = _pow2_clip(Sq, 8, block_q)
    bk = _pow2_clip(Skv, 128, block_kv)
    sq_pad = -Sq % bq
    skv_pad = -Skv % bk
    qq = jnp.pad(q.reshape(B * Hq, Sq, D), ((0, 0), (0, sq_pad), (0, 0)))
    kk = jnp.pad(k.reshape(B * Hkv, Skv, D), ((0, 0), (0, skv_pad), (0, 0)))
    vv = jnp.pad(v.reshape(B * Hkv, Skv, D), ((0, 0), (0, skv_pad), (0, 0)))
    sq_p, skv_p = Sq + sq_pad, Skv + skv_pad
    n_kb = skv_p // bk

    def kv_row(bh, _qi, _ki):
        return (bh // Hq) * Hkv + (bh % Hq) // group

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, sq=Sq,
                          skv=Skv, skv_orig=Skv, bq=bq, bk=bk, n_kb=n_kb),
        grid=(B * Hq, sq_p // bq, n_kb),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (kv_row(bh, qi, ki), ki, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki: (kv_row(bh, qi, ki), ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qq, kk, vv)
    return out[:, :Sq].reshape(B, Hq, Sq, D)
