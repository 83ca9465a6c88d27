"""Token-choice top-k MoE with sort-based capacity dispatch (+ shared expert).

Dispatch is the scatter/gather formulation (not the [T,E,C] one-hot einsum,
which is O(T·E·C) memory): flatten the T·k (token, expert) selections, sort by
expert, compute the rank within each expert group, drop ranks ≥ capacity, and
scatter into an [E·C, D] buffer. Expert FFNs run batched over E with one
einsum. Combine gathers results back with the router weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def moe_init(key, d_model: int, d_ff: int, num_experts: int,
             shared_d_ff: int = 0, act: str = "silu"):
    ks = jax.random.split(key, 6)
    e, d, f = num_experts, d_model, d_ff
    p = {
        "router": dense_init(ks[0], d, e, scale=0.02),
        "w_gate": (d ** -0.5) * jax.random.normal(ks[1], (e, d, f), jnp.float32),
        "w_up": (d ** -0.5) * jax.random.normal(ks[2], (e, d, f), jnp.float32),
        "w_down": (f ** -0.5) * jax.random.normal(ks[3], (e, f, d), jnp.float32),
    }
    if shared_d_ff:
        p["shared"] = {
            "gate": dense_init(ks[4], d, shared_d_ff),
            "up": dense_init(jax.random.fold_in(ks[4], 1), d, shared_d_ff),
            "down": dense_init(jax.random.fold_in(ks[4], 2), shared_d_ff, d),
            "shared_gate": dense_init(ks[5], d, 1),
        }
    return p


def _capacity(t: int, e: int, k: int, factor: float) -> int:
    c = int(t * k * factor / e) + 1
    return max(4, ((c + 3) // 4) * 4)


# --------------------------------------------------------------------------
# explicit shard_map dispatch (production path; DESIGN §4 / §Perf iter 2)
#
# GSPMD mis-partitions the scatter/gather dispatch when left to sharding
# propagation: the [B, E·cap, D] buffers get all-gathered over the data axis
# (43 GB/step on granite-moe train_4k). Under shard_map every shard
# dispatches only its local tokens; the only collective left is the psum
# over the model axis for the TP-contracted expert down-projection.
# --------------------------------------------------------------------------
_SHARD_MODE: dict = {"mesh": None, "dp": ("data",), "tp": "model"}


def set_moe_mesh(mesh, dp_axes=("data",), tp_axis="model") -> None:
    """Enable the shard_map dispatch path (None disables -> local/vmap path)."""
    _SHARD_MODE["mesh"] = mesh
    _SHARD_MODE["dp"] = tuple(dp_axes)
    _SHARD_MODE["tp"] = tp_axis


def moe_shard_mode():
    return _SHARD_MODE["mesh"]


def apply_moe_sharded(p, x, *, top_k: int, capacity_factor: float = 1.25,
                      act: str = "silu", batch_sharded: bool = True):
    """x [B, S, D] with B data-sharded, expert d_ff model-sharded.

    Returns (y [B, S, D], aux scalar). Requires set_moe_mesh(...) first.
    """
    from jax.sharding import PartitionSpec as P

    mesh = _SHARD_MODE["mesh"]
    dp, tp = _SHARD_MODE["dp"], _SHARD_MODE["tp"]
    has_shared = "shared" in p

    def body(x_loc, router, w_gate, w_up, w_down, *shared_w):
        b_loc, s, d = x_loc.shape
        y_flat, aux = _local_moe(
            x_loc.reshape(b_loc * s, d), router, w_gate, w_up, w_down,
            shared_w, top_k=top_k, capacity_factor=capacity_factor, act=act,
            tp_axis=tp)
        aux = jax.lax.pmean(aux, dp) if batch_sharded else aux
        return y_flat.reshape(b_loc, s, d), aux

    bspec = P(dp, None, None) if batch_sharded else P(None, None, None)
    in_specs = [bspec, P(None, None),
                P(None, None, tp), P(None, None, tp), P(None, tp, None)]
    args = [x, p["router"], p["w_gate"], p["w_up"], p["w_down"]]
    if has_shared:
        sp = p["shared"]
        in_specs += [P(None, tp), P(None, tp), P(tp, None), P(None, None)]
        args += [sp["gate"], sp["up"], sp["down"], sp["shared_gate"]]
    return jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=(bspec, P()), check_vma=False)(*args)


def _local_moe(x, router, w_gate, w_up, w_down, shared_w, *, top_k: int,
               capacity_factor: float, act: str, tp_axis: str):
    """Per-shard dispatch + TP expert compute (+psum) + combine. x [T, D]."""
    t, d = x.shape
    e = router.shape[-1]
    dt = x.dtype

    router_logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(router_logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(tope[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    cap = _capacity(t, e, top_k, capacity_factor)
    flat_e = tope.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t), top_k)
    flat_w = topw.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * top_k, dtype=jnp.int32) - starts[sorted_e]
    keep = rank < cap
    dest = jnp.where(keep, sorted_e * cap + rank, e * cap)

    buf = jnp.zeros((e * cap + 1, d), dt).at[dest].set(x[sorted_tok])
    h = buf[: e * cap].reshape(e, cap, d)
    if act == "silu":
        inner = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, w_gate.astype(dt)))
                 * jnp.einsum("ecd,edf->ecf", h, w_up.astype(dt)))
    else:
        inner = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, w_up.astype(dt)))
    out = jnp.einsum("ecf,efd->ecd", inner, w_down.astype(dt))

    # combine FIRST (linear in `out`), psum the [T, D] token tensor AFTER:
    # combine(psum(out)) == psum(combine(out)), and T·D is top_k·cf x smaller
    # than the E·cap·D dispatch buffer (§Perf iteration 3: 10x less traffic).
    out_flat = jnp.concatenate([out.reshape(e * cap, d),
                                jnp.zeros((1, d), dt)], axis=0)
    gathered = out_flat[dest]
    w = jnp.where(keep, flat_w[order], 0.0).astype(jnp.float32)
    y = jnp.zeros((t, d), jnp.float32).at[sorted_tok].add(
        gathered.astype(jnp.float32) * w[:, None])
    if shared_w:
        sg_w, su_w, sd_w, sgate_w = shared_w
        sh = jax.nn.silu(x @ sg_w.astype(dt)) * (x @ su_w.astype(dt))
        sh = sh @ sd_w.astype(dt)                       # partial over F shard
        sgate = jax.nn.sigmoid(x.astype(jnp.float32) @ sgate_w)
        y = y + sh.astype(jnp.float32) * sgate          # still partial sums
    y = jax.lax.psum(y.astype(dt), tp_axis)             # one [T, D] psum
    return y, aux


def apply_moe(p, x, *, top_k: int, capacity_factor: float = 1.25,
              act: str = "silu"):
    """x [T, D] -> (y [T, D], aux_loss scalar). Flatten batch dims first."""
    t, d = x.shape
    e = p["router"].shape[-1]
    dt = x.dtype

    router_logits = (x.astype(jnp.float32) @ p["router"])        # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    topw, tope = jax.lax.top_k(probs, top_k)                     # [T, k]
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(tope[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)

    cap = _capacity(t, e, top_k, capacity_factor)

    flat_e = tope.reshape(-1)                                    # [T·k]
    flat_tok = jnp.repeat(jnp.arange(t), top_k)                  # [T·k]
    flat_w = topw.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    sorted_tok = flat_tok[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * top_k, dtype=jnp.int32) - starts[sorted_e]
    keep = rank < cap
    dest = jnp.where(keep, sorted_e * cap + rank, e * cap)       # overflow slot

    buf = jnp.zeros((e * cap + 1, d), dt).at[dest].set(x[sorted_tok])
    h = buf[: e * cap].reshape(e, cap, d)

    if act == "silu":
        inner = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["w_gate"].astype(dt)))
                 * jnp.einsum("ecd,edf->ecf", h, p["w_up"].astype(dt)))
    else:
        inner = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", h, p["w_up"].astype(dt)))
    out = jnp.einsum("ecf,efd->ecd", inner, p["w_down"].astype(dt))
    out_flat = jnp.concatenate([out.reshape(e * cap, d),
                                jnp.zeros((1, d), dt)], axis=0)

    gathered = out_flat[dest]                                    # [T·k, D]
    w = jnp.where(keep, flat_w[order], 0.0).astype(jnp.float32)
    y = jnp.zeros((t, d), jnp.float32).at[sorted_tok].add(
        gathered.astype(jnp.float32) * w[:, None])

    if "shared" in p:
        sp = p["shared"]
        sh = jax.nn.silu(x @ sp["gate"].astype(dt)) * (x @ sp["up"].astype(dt))
        sh = sh @ sp["down"].astype(dt)
        sg = jax.nn.sigmoid(x.astype(jnp.float32) @ sp["shared_gate"])
        y = y + sh.astype(jnp.float32) * sg
    return y.astype(dt), aux
