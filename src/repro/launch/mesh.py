"""Production mesh builders (functions, never module-level state).

Every mesh here has `Auto` axes: the train, refresh and decode paths place
their data with `shard_map` specs and leave the rest to the partitioner,
which is what `Auto` means. (`jax.make_mesh` would otherwise build
`Explicit` axes, under which those paths refuse to trace.)
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis `Auto` — the one mesh constructor of
    the program and its tests."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, vocab_parallel: int = 1):
    """16x16 chips per pod; 2 pods when multi_pod. 512 placeholder devices are
    provided by the dry-run's XLA_FLAGS (host-platform device count).

    vocab_parallel > 1 carves a `vocab` axis out of the model axis (the class
    table + MIDX index row-shard over it; dist.vocab_parallel): the 16-chip
    inner dim becomes (16 // vocab_parallel) model x vocab_parallel vocab.
    """
    if vocab_parallel > 1:
        inner = 16
        if inner % vocab_parallel:
            raise ValueError(f"vocab_parallel {vocab_parallel} must divide "
                             f"the {inner}-chip inner mesh dim")
        model = inner // vocab_parallel
        shape = ((2, 16, model, vocab_parallel) if multi_pod
                 else (16, model, vocab_parallel))
        axes = (("pod", "data", "model", "vocab") if multi_pod
                else ("data", "model", "vocab"))
        return make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — for tests."""
    return make_mesh((data, model), ("data", "model"))


def make_vocab_mesh(data: int = 1, vocab: int = 1):
    """(data, vocab) mesh for the vocab-parallel head — tests + small runs."""
    return make_mesh((data, vocab), ("data", "vocab"))


def mesh_dp_tp(mesh) -> tuple[int, int]:
    """(total data-parallel degree incl. pod axis, tensor-parallel degree)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    return dp, sizes.get("model", 1)


def mesh_vp(mesh) -> int:
    """Vocab-parallel degree (1 when the mesh has no vocab axis)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("vocab", 1)
