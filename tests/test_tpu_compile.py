"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers one kernel at real widths for a v5e chip
described by `jax.experimental.topologies` and compiles it with the TPU
compiler, which refuses what the chip cannot run (unaligned blocks, scalar
reads from vector memory, too much VMEM or SMEM) — the faults that the
Pallas interpreter used by the parity tests never sees. Shapes are those
of the two training configurations: mamba2-370m's head (D=1024, M=1024
shared or per-token negatives, V=50304 padded, K=64) and the paper's LM
(D=200, M=20, V=10000, K=32).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.midx_probs.midx_probs import midx_probs
from repro.kernels.sampled_ce.per_token import (sampled_ce_pt,
                                                sampled_ce_pt_bwd)
from repro.kernels.sampled_ce.sampled_ce import sampled_ce, sampled_ce_bwd

WIDTHS = {
    "d1024": dict(t=4096, d=1024, m=1024, v=50304, k=64),
    "paper-lm": dict(t=4096, d=200, m=20, v=10000, k=32),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # compiles for a described chip cannot be read back from the
        # persistent cache without the chip: keep them out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _structs(one_chip, **shapes):
    return {name: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for name, (shape, dtype) in shapes.items()}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("kind", ["rq", "pq"])
def test_midx_probs_compiles(one_chip, width, kind):
    w = WIDTHS[width]
    dc = w["d"] // 2 if kind == "pq" else w["d"]
    s = _structs(one_chip, z=((w["t"], w["d"]), jnp.float32),
                 cb=((w["k"], dc), jnp.float32),
                 counts=((w["k"], w["k"]), jnp.float32))
    _compile(lambda z, cb, counts: midx_probs(z, cb, cb, counts,
                                              split=kind == "pq"),
             s["z"], s["cb"], s["counts"])


def _pt_structs(one_chip, w, table_dtype):
    return _structs(
        one_chip, h=((w["t"], w["d"]), jnp.float32),
        table=((w["v"], w["d"]), table_dtype),
        scale=((w["v"], 1), jnp.float32),
        log_q=((w["t"], w["m"]), jnp.float32),
        neg_ids=((w["t"], w["m"]), jnp.int32),
        pos_ids=((w["t"],), jnp.int32), g=((w["t"],), jnp.float32))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("table_dtype", ["bf16", "int8"])
def test_sampled_ce_pt_forward_compiles(one_chip, width, table_dtype):
    quantized = table_dtype == "int8"
    s = _pt_structs(one_chip, WIDTHS[width],
                    jnp.int8 if quantized else jnp.bfloat16)

    def fwd(h, table, scale, log_q, neg_ids, pos_ids):
        return sampled_ce_pt(h, table, log_q, neg_ids, pos_ids,
                             scale=scale if quantized else None)

    _compile(fwd, s["h"], s["table"], s["scale"], s["log_q"], s["neg_ids"],
             s["pos_ids"])


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("table_dtype", ["bf16", "int8"])
def test_sampled_ce_pt_backward_compiles(one_chip, width, table_dtype):
    quantized = table_dtype == "int8"
    s = _pt_structs(one_chip, WIDTHS[width],
                    jnp.int8 if quantized else jnp.bfloat16)

    def bwd(g, h, table, scale, log_q, neg_ids, pos_ids, lse):
        return sampled_ce_pt_bwd(g, h, table, log_q, neg_ids, pos_ids, lse,
                                 scale=scale if quantized else None)

    _compile(bwd, s["g"], s["h"], s["table"], s["scale"], s["log_q"],
             s["neg_ids"], s["pos_ids"], s["g"])


def _sce_structs(one_chip, w, table_dtype):
    return _structs(
        one_chip, h=((w["t"], w["d"]), jnp.float32),
        pos_emb=((w["t"], w["d"]), table_dtype),
        neg_emb=((w["m"], w["d"]), table_dtype),
        pos_scale=((w["t"], 1), jnp.float32),
        neg_scale=((w["m"], 1), jnp.float32),
        log_q=((w["m"],), jnp.float32), neg_ids=((w["m"],), jnp.int32),
        pos_ids=((w["t"],), jnp.int32), g=((w["t"],), jnp.float32))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("table_dtype", ["bf16", "int8"])
def test_sampled_ce_forward_compiles(one_chip, width, table_dtype):
    quantized = table_dtype == "int8"
    s = _sce_structs(one_chip, WIDTHS[width],
                     jnp.int8 if quantized else jnp.bfloat16)

    def fwd(h, pe, ne, ps, ns, log_q, neg_ids, pos_ids):
        if not quantized:
            ps = ns = None
        return sampled_ce(h, pe, ne, log_q, neg_ids, pos_ids, pos_scale=ps,
                          neg_scale=ns)

    _compile(fwd, *(s[n] for n in ("h", "pos_emb", "neg_emb", "pos_scale",
                                    "neg_scale", "log_q", "neg_ids",
                                    "pos_ids")))


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("table_dtype", ["bf16", "int8"])
def test_sampled_ce_backward_compiles(one_chip, width, table_dtype):
    quantized = table_dtype == "int8"
    s = _sce_structs(one_chip, WIDTHS[width],
                     jnp.int8 if quantized else jnp.bfloat16)

    def bwd(g, h, pe, ne, ps, ns, log_q, neg_ids, pos_ids, lse):
        if not quantized:
            ps = ns = None
        return sampled_ce_bwd(g, h, pe, ne, log_q, neg_ids, pos_ids, lse,
                              pos_scale=ps, neg_scale=ns)

    _compile(bwd, *(s[n] for n in ("g", "h", "pos_emb", "neg_emb",
                                    "pos_scale", "neg_scale", "log_q",
                                    "neg_ids", "pos_ids", "g")))


def test_kernels_keep_their_names_under_vmap_and_grad(one_chip):
    """Each kernel's custom call is named after its `pallas_call(name=)`
    whatever transformation wraps it, as the decode head's vmap and the
    training head's grad do: the benchmark finds kernels in the device
    trace by these names."""
    import re

    from repro.kernels.sampled_ce.ops import sampled_ce_op
    s = _structs(one_chip, z=((4, 128), jnp.float32),
                 cb=((16, 128), jnp.float32), counts=((16, 16), jnp.float32),
                 h=((2, 256, 128), jnp.float32),
                 pe=((2, 256, 128), jnp.float32),
                 ne=((256, 128), jnp.float32), lq=((2, 256), jnp.float32),
                 ni=((256,), jnp.int32), pi=((2, 256), jnp.int32))

    def tables(z, cb, counts):
        return jax.vmap(lambda r: midx_probs(
            jnp.repeat(r[None], 256, 0), cb, cb, counts, split=False))(z)

    def loss(h, pe, ne, lq, ni, pi):
        return jnp.sum(jax.vmap(
            lambda hb, pb, lb, ib: sampled_ce_op(hb, pb, ne, lb, ni, ib))(
                h, pe, lq, pi))

    texts = [jax.jit(tables).lower(s["z"], s["cb"], s["counts"])
             .compile().as_text(),
             jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
                 *(s[n] for n in ("h", "pe", "ne", "lq", "ni", "pi")))
             .compile().as_text()]
    calls = {m.group(1) for text in texts for m in re.finditer(
        r"^\s*%([a-z_]+)(?:\.\d+)? = .* custom-call\(", text, re.M)}
    assert calls == {"midx_probs", "sampled_ce", "sampled_ce_bwd_dh",
                     "sampled_ce_bwd_dne"}
