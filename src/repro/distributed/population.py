"""Device-sharded population evaluation for the closed-loop HERO search.

`BatchedQuantEnv` scores K policies with one `jax.vmap` call — fine on one
chip, but the population axis is embarrassingly parallel, so on a multi-
device host the K policies should split across the mesh. This module wraps
any *batched* pure function (leading axis = population on every non-
broadcast argument and every output leaf) in a `shard_map` over a 1-D
``("pop",)`` mesh of the local devices:

  - K is padded up to a multiple of the device count (rows repeat the
    last policy; the pad is sliced off after the call), so callers never
    think about divisibility;
  - broadcast arguments (e.g. the shared NGP weights for the PSNR proxy)
    are replicated via an empty PartitionSpec;
  - on a single-device mesh the wrapper degrades to the plain vmapped
    call — same numbers, no sharding machinery in the way.

Both halves of a population evaluation fit this contract as pure jax:
`policy_latency` (the fused NeuRex model, including the on-device grid-
cache sort) and the proxy-MSE render. Cache statistics are integer-exact
in both the host-memoized and on-device paths, so sharding does not move
the numbers (pinned by tests/test_closed_loop.py in a forced multi-device
subprocess). Frontier merging stays on the host: metrics come back as
(K,) numpy arrays and feed `repro.core.pareto`.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import make_mesh_compat

POP_AXIS = "pop"


def population_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the local devices; the single axis carries policies."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return make_mesh_compat((n,), (POP_AXIS,))


def pad_population(arr: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
    """Pad the leading axis up to `multiple` by repeating the last row.
    Returns (padded, original_k). Repeating (vs zero-fill) keeps every row
    a valid policy, so padded lanes can't trip asserts or NaNs."""
    k = arr.shape[0]
    pad = (-k) % multiple
    if pad == 0:
        return arr, k
    filler = np.repeat(arr[-1:], pad, axis=0)
    return np.concatenate([arr, filler], axis=0), k


def shard_population(
    fn: Callable,
    mesh: Optional[Mesh] = None,
    broadcast_argnums: Sequence[int] = (),
) -> Callable:
    """Shard a batched fn's population axis over the mesh.

    `fn` must be shard-agnostic: outputs for row i depend only on inputs
    of row i (a vmapped per-policy function qualifies). Positional args in
    `broadcast_argnums` are replicated; all others (and all output leaves)
    carry the population on axis 0.
    """
    mesh = population_mesh() if mesh is None else mesh
    n_shards = int(np.prod(mesh.devices.shape))
    bcast = frozenset(broadcast_argnums)

    if n_shards == 1:
        jitted = jax.jit(fn)

        def call_single(*args):
            return jax.tree_util.tree_map(np.asarray, jitted(*args))

        call_single.n_shards = 1
        return call_single

    def specs(args):
        return tuple(
            P() if i in bcast else P(POP_AXIS) for i in range(len(args))
        )

    sharded = {}  # arity -> compiled fn (arity is fixed per wrapper use)

    def call(*args):
        key = len(args)
        if key not in sharded:
            sharded[key] = jax.jit(
                jax.shard_map(
                    fn, mesh=mesh, in_specs=specs(args),
                    out_specs=P(POP_AXIS), check_vma=False,
                )
            )
        batched = [i for i in range(len(args)) if i not in bcast]
        k = np.shape(args[batched[0]])[0]
        padded = list(args)
        for i in batched:
            arr, _ = pad_population(np.asarray(args[i]), n_shards)
            padded[i] = jnp.asarray(arr)
        out = sharded[key](*padded)
        return jax.tree_util.tree_map(lambda x: np.asarray(x)[:k], out)

    call.n_shards = n_shards
    return call


def auto_shard(threshold_devices: int = 2) -> bool:
    """Default policy: shard when the host exposes >= 2 devices."""
    return len(jax.devices()) >= threshold_devices
