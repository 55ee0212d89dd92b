"""Multi-chip sharding for the verification batch dimension.

Reference analog (SURVEY.md §2.2): the blst pool fans signature chunks
out to N-1 CPU worker threads round-robin
(chain/bls/multithread/index.ts:183-199). The TPU design replaces the
worker fan-out with SPMD: every batch-shaped crypto kernel in this
package broadcasts over a leading axis, so distributing work across
chips is a matter of placing that axis on a `Mesh` axis and letting
XLA insert the collectives (the log-depth aggregate/product reduction
trees in ops/curve.jac_sum and ops/pairing._fq12_masked_product become
ICI all-reduces). There is no NCCL/MPI analog to port — the "comm
backend" is jax.sharding over ICI/DCN (SURVEY.md §5.8).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

BATCH_AXIS = "batch"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D device mesh over the verify batch axis."""
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (BATCH_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) axis; replicate limb axes."""
    return NamedSharding(mesh, P(BATCH_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, tree):
    """Place every array leaf of a batched pytree (JacPoint / Lv / Fq2
    tuples / bool masks) with its leading axis split over the mesh."""
    sh = batch_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def replicate(mesh: Mesh, tree):
    r = replicated(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, r), tree)


def whole_bucket_verify(mesh: Mesh, local_fn, n_args: int,
                        replicated_args: tuple = ()):
    """Whole-bucket SPMD verify wrapper (ISSUE 16).

    The auto-spmd mesh path shards the batch axis INSIDE one bucket's
    program, so XLA inserts ICI all-reduces into the aggregate and
    product reduction trees — several collectives per wave, each a
    latency wall. But the random-linear-combination batch-verify
    equation is SEPARABLE across disjoint subsets of sets: each chip
    can run the complete verify on the sub-bucket it owns and the
    batch verdict is just the AND of the per-chip verdicts. shard_map
    makes that explicit: `local_fn` (batch-shaped args -> () bool) is
    traced per shard with collective-free local shapes, and the ONLY
    collective in the whole program is one scalar `psum` of the
    per-chip bad counts at the final verdict.

    in_specs are pytree PREFIXES: P(batch) splits every array leaf's
    leading axis across the mesh; indices in `replicated_args` get P()
    (e.g. the shared same-message hash point). The caller places
    inputs with shard_batch/replicate to match.

    The last argument is the bucket's live-set mask. A shard past the
    last live set holds padding only: it has nothing to verify and
    counts as good, whatever `local_fn` makes of an empty batch (the
    BLS bodies pair its aggregate, the point at infinity, and fail).
    """
    import jax.numpy as jnp

    in_specs = tuple(
        P() if i in replicated_args else P(BATCH_AXIS)
        for i in range(n_args)
    )

    def spmd(*args):
        ok = jnp.logical_or(local_fn(*args), ~jnp.any(args[-1]))
        bad = jax.lax.psum(jnp.where(ok, 0, 1), BATCH_AXIS)
        return bad == 0

    # check_vma=False: the varying-axes checker mis-infers the carry
    # of lax.scan bodies (the ladders and masked products are
    # scan-based) and rejects the program; the body is collective-free
    # by construction and the one explicit psum above is the whole
    # cross-shard story, so the check adds nothing here.
    return jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(),
        check_vma=False,
    )
