"""What the benchmark hands the program, made by the benchmark itself: the
program's configuration object from a configuration file, the MIDX index
over a seeded class table, and seeded language-model batches.

The index is fitted here by plain Lloyd iterations (residual quantisation:
a second codebook over the residuals of the first), so that the reference
can read the same codebooks and assignments without taking anything the
program made. The program's own k-means runs at each index refresh.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def program_config(spec: dict):
    """The program's ModelConfig for a configuration file: the named
    configuration with every field the file's `program` section sets."""
    from repro.configs import get_config
    prog = spec["program"]
    cfg = dataclasses.replace(get_config(prog["arch"]), **prog["model"])
    if prog.get("head"):
        cfg = cfg.with_head(**prog["head"])
    if prog.get("serve"):
        cfg = cfg.with_serve(**prog["serve"])
    return cfg


def check_layout(cfg, weights) -> None:
    """The benchmark's weights have the program's tree, shapes and dtypes
    for the leaves the program initialises (a changed layout fails here,
    not as a wrong answer later)."""
    from repro.models import init_params
    like = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    want = jax.tree_util.tree_structure(like)
    got = jax.tree_util.tree_structure(weights)
    if want != got:
        raise ValueError(f"weight tree differs from the program's:\n{got}\n"
                         f"vs\n{want}")
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(like),
                            jax.tree_util.tree_leaves(weights)):
        if a.shape != b.shape:
            raise ValueError(f"{jax.tree_util.keystr(path)}: shape "
                             f"{b.shape}, the program has {a.shape}")


# --------------------------------------------------------------- the index
def _kmeans(x, key, k: int, iters: int):
    """Plain Lloyd iterations from k distinct rows; an empty cluster keeps
    its centroid. Returns (centroids [k, D], assignment [N])."""
    n = x.shape[0]
    cent = x[jax.random.choice(key, n, (k,), replace=False)]
    x2 = jnp.sum(x * x, axis=1, keepdims=True)

    def assign(c):
        d = x2 - 2.0 * jnp.matmul(x, c.T, precision=HIGHEST) + \
            jnp.sum(c * c, axis=1)[None, :]
        return jnp.argmin(d, axis=1).astype(jnp.int32)

    def body(_, c):
        a = assign(c)
        sums = jnp.zeros_like(c).at[a].add(x)
        cnt = jnp.zeros((k,), x.dtype).at[a].add(1.0)
        return jnp.where(cnt[:, None] > 0, sums / jnp.maximum(cnt, 1.0)[:, None],
                         c)

    cent = jax.lax.fori_loop(0, iters, body, cent)
    return cent, assign(cent)


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def midx_index(table, key, *, k: int, iters: int) -> dict:
    """Residual-quantised inverted multi-index over `table` [N, D], with its
    CSR layout: class ids sorted (stably) by joint cluster k1*K + k2."""
    x = table.astype(jnp.float32)
    k1, k2 = jax.random.split(key)
    c1, a1 = _kmeans(x, k1, k, iters)
    c2, a2 = _kmeans(x - c1[a1], k2, k, iters)
    joint = a1 * k + a2
    sorted_ids = jnp.argsort(joint, stable=True).astype(jnp.int32)
    counts = jnp.zeros((k * k,), jnp.int32).at[joint].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)]).astype(jnp.int32)
    log_counts = jnp.where(counts > 0, jnp.log(jnp.maximum(counts, 1)
                                               .astype(jnp.float32)), -jnp.inf)
    return {"codebook1": c1, "codebook2": c2, "assign1": a1, "assign2": a2,
            "sorted_ids": sorted_ids, "offsets": offsets,
            "counts": counts.reshape(k, k),
            "log_counts": log_counts.reshape(k, k)}


def to_program_index(arrays: dict):
    """The program's MultiIndex around the benchmark's arrays (no residual
    table, as the program's fast sampler keeps none)."""
    from repro.index.build import MultiIndex
    d = arrays["codebook1"].shape[1]
    return MultiIndex("rq", residuals=jnp.zeros((0, d), jnp.float32),
                      **arrays)


# --------------------------------------------------------------- the data
@functools.partial(jax.jit, static_argnames=("n", "batch", "seq", "vocab"))
def lm_batches(key, *, n: int, batch: int, seq: int, vocab: int,
               zipf_s: float):
    """n batches of `batch` rows of seq+1 token ids, Zipf-distributed over a
    seeded permutation of the vocabulary. Returns (tokens, labels), each
    [n, batch, seq] int32: labels are the tokens shifted by one."""
    k_perm, k_draw = jax.random.split(key)
    rank = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    w = rank ** (-zipf_s)
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(k_draw, (n, batch, seq + 1))
    r = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
    ids = jax.random.permutation(k_perm, vocab)[r].astype(jnp.int32)
    return ids[..., :-1], ids[..., 1:]
