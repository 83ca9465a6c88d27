"""Backend dispatch for the Pallas kernels (DESIGN §3).

One place answers "kernel or jnp oracle?" for every fused hot path, so the
decision is uniform across heads/steps/benchmarks:

  - TPU backend        -> compiled Pallas kernels (the production path).
  - anything else      -> jnp oracle (what the CPU dry-run and the tier-1
                          suite compile), unless the caller passes
                          `interpret=True`, in which case the *kernel
                          dataflow* runs under the Pallas interpreter
                          (parity tests, and compile-only dry-runs of the
                          fused graph).

Only the backend and the caller's arguments decide; nothing in the
environment can divert the TPU path to the interpreter or the oracle.

`core/` stays kernel-free: the samplers take a `tables_fn` hook, and this
module is where models/launch obtain one.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax

from repro.core.index import MultiIndex


def pallas_supported() -> bool:
    """Compiled (non-interpret) Pallas requires a TPU backend."""
    return jax.default_backend() == "tpu"


def fused_head_active(head_cfg, *, fused: Optional[bool] = None,
                      interpret: bool = False) -> bool:
    """Should `loss_midx` take the fused kernel path?

    Explicit `fused` wins; else `head_cfg.use_fused_head` gated on a
    backend that can run the kernels (TPU, or interpret mode). The fused
    kernels always mask collisions, so `mask_collisions=False` configs stay
    on the jnp path.
    """
    if not head_cfg.mask_collisions:
        return False
    if fused is not None:
        return fused
    return head_cfg.use_fused_head and (pallas_supported() or interpret)


def midx_tables_fn(*, use_kernel: Optional[bool] = None,
                   interpret: bool = False,
                   block_t: int = 256) -> Optional[Callable]:
    """A `tables_fn` hook for core.midx.sample / sample_twostage.

    Returns None when the jnp oracle (`twostage_tables`) should be used —
    the samplers treat None as "no hook". Otherwise returns a callable
    (index, z) -> (s1, s2, log_psi, lse) backed by the midx_probs kernel
    (differentiable; see kernels/midx_probs/ops.py).
    """
    from repro.kernels.midx_probs.ops import proposal_tables
    if use_kernel is None:
        use_kernel = pallas_supported() or interpret

    if not use_kernel:
        return None

    def tables_fn(index: MultiIndex, z: jax.Array):
        return proposal_tables(index, z, use_kernel=True, block_t=block_t,
                               interpret=interpret)

    return tables_fn


def midx_tables_fn_q(qcb1, sc1, qcb2, sc2, *,
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False,
                     block_t: int = 256) -> Callable:
    """Quantized-codebook `tables_fn` hook (DESIGN §12).

    Unlike midx_tables_fn this ALWAYS returns a callable: in quantized mode
    the proposal must score the low-bit codebooks on every backend so the
    draws match the serving head — the jnp fallback applies the same
    post-dot dequant as the kernel and agrees bit-for-bit.
    """
    from repro.kernels.midx_probs.ops import proposal_tables_q
    if use_kernel is None:
        use_kernel = pallas_supported() or interpret

    def tables_fn(index: MultiIndex, z: jax.Array):
        return proposal_tables_q(index, qcb1, sc1, qcb2, sc2, z,
                                 use_kernel=use_kernel, block_t=block_t,
                                 interpret=interpret)

    return tables_fn


def rff_sample_fn(*, use_kernel: Optional[bool] = None,
                  interpret: bool = False) -> Callable:
    """The fused RFF Gumbel-top-m sampler for proposals.rff ('rff-fused').

    Returns a callable (phi_z [T,R2], phi_c [N,R2], seed, m) -> (ids, log_q).
    TPU (or interpret mode) runs the Pallas kernel; every other backend runs
    the jnp oracle, which consumes the same counter-based hash noise, so the
    draws are bit-identical either way (kernels/rff_sample/ops.py).
    """
    from repro.kernels.rff_sample.ops import rff_gumbel_sample
    if use_kernel is None:
        use_kernel = pallas_supported() or interpret

    def sample_fn(phi_z: jax.Array, phi_c: jax.Array, seed, m: int):
        return rff_gumbel_sample(phi_z, phi_c, seed, m,
                                 use_kernel=use_kernel, interpret=interpret)

    return sample_fn
