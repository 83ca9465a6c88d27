"""Low-bit class-table representation for the head's hot path (DESIGN §12).

At paper scale the head is bandwidth-bound: every CE and proposal pass
streams rows of the [V, D] class table out of HBM. This module provides the
quantized twin of that table and everything the kernels/heads need to read
it:

  quantize_rows      per-row symmetric quantization to int8 / fp8-e4m3 with
                     fp32 scales — the same scale-sharing idiom as the
                     error-feedback int8 gradient collectives
                     (dist.collectives.psum_int8_ef). Zero rows quantize to
                     zero (the amax floor keeps the scale finite); outlier
                     rows only widen their own scale.
  QuantizedTable     (data [V, D] low-bit, scale [V, 1] fp32) pytree.
  dequant_rows       gather + dequantize with a straight-through estimator:
                     the forward reads ONLY the low-bit copy (the master
                     table argument is dead and XLA removes the read); the
                     backward scatters the cotangent onto the master table,
                     so the optimizer keeps updating master precision.
  ResidualCodes      PQ codes of the residual r_i = e_i - recon(k1, k2) with
                     per-subspace LUT (ADC) scoring — the proposal/rescore
                     pass reads n_sub bytes per candidate instead of 4·D
                     (paper §4.1's Theorem-1 split o_i = s1 + s2 + z·r_i,
                     with the residual term scored from codes).
  QuantHeadState     the head state that replaces the bare MultiIndex when
                     cfg.head.table_dtype != 'bf16': the index plus the
                     quantized table, quantized codebooks and residual
                     codes, re-quantized on refresh (quantize_on_refresh)
                     so the low-bit copy rides the IndexLifecycle double
                     buffer.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.index.build import MultiIndex
from repro.index.kmeans import kmeans
from repro.index.quantization import reconstruct

TABLE_DTYPES = ("bf16", "int8", "fp8")

# symmetric quantization range per format (fp8 = e4m3: max finite 448)
_QMAX = {"int8": 127.0, "fp8": 448.0}


def fp8_supported() -> bool:
    return hasattr(jnp, "float8_e4m3fn")


def resolve_table_dtype(table_dtype: str) -> str:
    """Validate cfg.head.table_dtype — raises at step-build time (the
    resolve_proposal convention), never silently falls back."""
    if table_dtype not in TABLE_DTYPES:
        raise ValueError(
            f"head.table_dtype must be one of {TABLE_DTYPES}, "
            f"got {table_dtype!r}")
    if table_dtype == "fp8" and not fp8_supported():
        raise ValueError(
            "head.table_dtype='fp8' needs jnp.float8_e4m3fn, which this "
            "jax build does not provide — use 'int8' or 'bf16'")
    return table_dtype


def storage_dtype(fmt: str):
    if fmt == "int8":
        return jnp.int8
    if fmt == "fp8":
        return jnp.float8_e4m3fn
    raise ValueError(f"no low-bit storage dtype for {fmt!r}")


def quantize_rows(x: jax.Array, fmt: str) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric quantization: [N, D] -> (q [N, D], scale [N, 1]).

    scale = amax/Qmax per row (amax floored so all-zero rows stay finite and
    quantize to exact zero); int8 rounds-to-nearest, fp8 relies on the cast's
    rounding. Dequantization is q.astype(f32) * scale.
    """
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    qmax = _QMAX[fmt]
    scale = jnp.maximum(amax, 1e-30) / qmax
    y = x / scale
    if fmt == "int8":
        q = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    else:
        q = jnp.clip(y, -qmax, qmax).astype(storage_dtype(fmt))
    return q, scale.astype(jnp.float32)


def dequantize(data: jax.Array, scale: jax.Array) -> jax.Array:
    """Full-table dequantization (tests / eval tooling — not the hot path)."""
    return data.astype(jnp.float32) * scale


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("data", "scale"), meta_fields=("fmt",))
@dataclasses.dataclass(frozen=True)
class QuantizedTable:
    fmt: str                  # 'int8' | 'fp8' (static metadata)
    data: jax.Array           # [V, D] int8 / float8_e4m3fn
    scale: jax.Array          # [V, 1] fp32 per-row scales

    @property
    def num_rows(self) -> int:
        return self.data.shape[0]

    def dequantize(self) -> jax.Array:
        return dequantize(self.data, self.scale)


def quantize_table(table: jax.Array, fmt: str) -> QuantizedTable:
    data, scale = quantize_rows(table, fmt)
    return QuantizedTable(fmt, data, scale)


# ---------------------------------------------------------------------------
# straight-through dequantizing gather
# ---------------------------------------------------------------------------

@jax.custom_vjp
def dequant_rows(master: jax.Array, data: jax.Array, scale: jax.Array,
                 ids: jax.Array) -> jax.Array:
    """rows = data[ids] * scale[ids] (fp32), with d(rows)/d(master) = gather.

    The master table is a *dead* primal in the forward — XLA never reads it
    — but the custom backward scatters the row cotangents onto it, so
    differentiating a loss built on the quantized rows updates the
    master-precision table (straight-through estimator: d dequant(quant(e))
    ≈ d e). `data`/`scale`/`ids` get no cotangent: the quantized copy is
    derived state, refreshed by quantize_on_refresh, never trained.
    """
    del master
    return data[ids].astype(jnp.float32) * scale[ids]


def _dequant_rows_fwd(master, data, scale, ids):
    out = data[ids].astype(jnp.float32) * scale[ids]
    # residuals must be real, non-empty arrays (shard_map/pjit moves them
    # across the fwd/bwd boundary, and a zero-size one loses its sharding):
    # a [1, D] slice keeps master's shape[1:]/dtype, the tiny [V, 1] scale
    # supplies the row count.
    dead = jax.lax.slice_in_dim(master, 0, 1, axis=0)
    return out, (dead, scale, ids)


def _dequant_rows_bwd(res, g):
    dead, scale, ids = res
    shape = (scale.shape[0],) + dead.shape[1:]
    dmaster = jnp.zeros(shape, jnp.float32).at[ids].add(
        g.astype(jnp.float32)).astype(dead.dtype)
    return dmaster, None, None, None


dequant_rows.defvjp(_dequant_rows_fwd, _dequant_rows_bwd)


def quantized_query_scores(kind: str, qcb1: jax.Array, sc1: jax.Array,
                           qcb2: jax.Array, sc2: jax.Array,
                           z: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantization.query_scores over the low-bit codebook copies.

    Scales apply AFTER the dot — z @ (q·s)ᵀ = (z @ qᵀ)·sᵀ — matching the
    midx_probs kernel's order of operations bit-for-bit, so jnp-path draws
    agree with fused-path draws."""
    zf = z.astype(jnp.float32)
    if kind == "pq":
        d = zf.shape[-1]
        z1, z2 = zf[..., : d // 2], zf[..., d // 2:]
    else:
        z1 = z2 = zf
    s1 = (z1 @ qcb1.T.astype(jnp.float32)) * sc1.astype(jnp.float32).reshape(1, -1)
    s2 = (z2 @ qcb2.T.astype(jnp.float32)) * sc2.astype(jnp.float32).reshape(1, -1)
    return s1, s2


# ---------------------------------------------------------------------------
# PQ codes of the residual term (proposal / rescore pass)
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("sub_codebooks", "codes"),
                   meta_fields=())
@dataclasses.dataclass(frozen=True)
class ResidualCodes:
    sub_codebooks: jax.Array  # [n_sub, ksub, D/n_sub] fp32
    codes: jax.Array          # [V, n_sub] int8 sub-codeword ids

    @property
    def n_sub(self) -> int:
        return self.sub_codebooks.shape[0]

    @property
    def ksub(self) -> int:
        return self.sub_codebooks.shape[1]


def resolve_n_sub(d: int, n_sub: int) -> int:
    """Largest divisor of D not exceeding the requested subspace count."""
    n = max(1, min(n_sub, d))
    while d % n:
        n -= 1
    return n


def fit_residual_codes(key: jax.Array, residual: jax.Array, *,
                       n_sub: int = 16, ksub: int = 16,
                       iters: int = 4) -> ResidualCodes:
    """PQ-code the residual table: split D into n_sub subspaces, k-means each
    with ksub centroids (codes fit in int8). O(V · ksub · D) per iteration —
    run at refresh cadence, never per step."""
    v, d = residual.shape
    n_sub = resolve_n_sub(d, n_sub)
    dsub = d // n_sub
    parts = residual.astype(jnp.float32).reshape(v, n_sub, dsub)
    cbs, codes = [], []
    for s in range(n_sub):
        r = kmeans(jax.random.fold_in(key, s), parts[:, s], ksub, iters)
        cbs.append(r.centroids)
        codes.append(r.assignments.astype(jnp.int8))
    return ResidualCodes(jnp.stack(cbs), jnp.stack(codes, axis=-1))


def residual_scores(rc: ResidualCodes, z: jax.Array,
                    ids: jax.Array) -> jax.Array:
    """ADC scoring of the coded residual term: z [..., D], ids [..., M] ->
    approximate z·r_i per candidate [..., M]. One [n_sub, ksub] LUT per
    query, then n_sub int8 code gathers per candidate — the candidate read
    is n_sub bytes instead of the 4·D-byte raw-embedding row."""
    n_sub, ksub, dsub = rc.sub_codebooks.shape
    zs = z.astype(jnp.float32).reshape(*z.shape[:-1], n_sub, dsub)
    lut = jnp.einsum("...sd,skd->...sk", zs, rc.sub_codebooks)  # [..., S, K]
    codes = rc.codes[ids].astype(jnp.int32)                     # [..., M, S]
    picked = jnp.take_along_axis(lut[..., None, :, :],
                                 codes[..., None], axis=-1)     # [..., M, S, 1]
    return jnp.sum(picked[..., 0], axis=-1)


def code_scores(index: MultiIndex, rc: ResidualCodes, z: jax.Array,
                ids: jax.Array, s1: jax.Array, s2: jax.Array) -> jax.Array:
    """Candidate scores from codes only (Theorem-1 split, paper §4.1):
    o_i ≈ s1[k1(i)] + s2[k2(i)] + ADC(z, codes_i). `s1`/`s2` are the
    [..., K] codeword score tables the two-stage draw already computed, so
    the rescore reads 2 int32 assignments + n_sub int8 codes per candidate
    — never the [V, D] table."""
    a1 = index.assign1[ids]
    a2 = index.assign2[ids]
    coarse = (jnp.take_along_axis(s1, a1, axis=-1) +
              jnp.take_along_axis(s2, a2, axis=-1))
    return coarse + residual_scores(rc, z, ids)


# ---------------------------------------------------------------------------
# the quantized head state
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("index", "qdata", "qscale", "qcb1",
                                "qcb1_scale", "qcb2", "qcb2_scale",
                                "sub_codebooks", "codes"),
                   meta_fields=("fmt",))
@dataclasses.dataclass(frozen=True)
class QuantHeadState:
    """MultiIndex + the low-bit twins the hot path reads (DESIGN §12).

    The lifecycle driver treats head state as an opaque pytree, so this
    container rides IndexLifecycle / checkpointing / validation unchanged;
    models.heads unwraps it to route the quantized kernel paths."""
    fmt: str                  # 'int8' | 'fp8'
    index: MultiIndex
    qdata: jax.Array          # [V, D] low-bit class table
    qscale: jax.Array         # [V, 1] fp32 per-row scales
    qcb1: jax.Array           # [K, Dc] low-bit stage-1 codebook
    qcb1_scale: jax.Array     # [K, 1] fp32 per-codeword scales
    qcb2: jax.Array           # [K, Dc] low-bit stage-2 codebook
    qcb2_scale: jax.Array     # [K, 1]
    sub_codebooks: jax.Array  # [n_sub, ksub, D/n_sub] fp32 residual PQ
    codes: jax.Array          # [V, n_sub] int8 residual codes

    @property
    def qtable(self) -> QuantizedTable:
        return QuantizedTable(self.fmt, self.qdata, self.qscale)

    @property
    def residual_codes(self) -> ResidualCodes:
        return ResidualCodes(self.sub_codebooks, self.codes)


def quantize_head_state(index: MultiIndex, table: jax.Array, fmt: str, *,
                        key: jax.Array, n_sub: int = 16, ksub: int = 16,
                        code_iters: int = 4) -> QuantHeadState:
    """Derive the full quantized head state from a (rebuilt) index + the
    current master table: quantize the table and both codebooks per row,
    PQ-code the reconstruction residual. Runs at init and on refresh."""
    t32 = table.astype(jnp.float32)
    qdata, qscale = quantize_rows(t32, fmt)
    qcb1, qcb1_s = quantize_rows(index.codebook1, fmt)
    qcb2, qcb2_s = quantize_rows(index.codebook2, fmt)
    resid = t32 - reconstruct(index.kind, index.codebook1, index.codebook2,
                              index.assign1, index.assign2)
    rc = fit_residual_codes(key, resid, n_sub=n_sub, ksub=ksub,
                            iters=code_iters)
    return QuantHeadState(fmt, index, qdata, qscale, qcb1, qcb1_s,
                          qcb2, qcb2_s, rc.sub_codebooks, rc.codes)


def unwrap_index(state):
    """The MultiIndex inside either head-state flavour."""
    return state.index if isinstance(state, QuantHeadState) else state
