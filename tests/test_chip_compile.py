"""Deviceless compiles of the BLS verifier's chip programs for a v5e.

The TPU compiler is installed here, and it compiles for a chip that is
described and not attached (`jax.experimental.topologies`). These cases
compile the Pallas kernels of the main path at the shapes of the
production 2048-set bucket and, slow-marked, the three fused programs
of a 2048-set wave, the host-path programs at the retry rungs and the
whole-bucket mesh program on four chips. Nothing runs, so they say
nothing about results or times: they catch the compiler's refusals
(tiling, VMEM, device memory) at no chip time.

Each case asserts that its program holds a Pallas kernel
(`tpu_custom_call`) and fits one chip's memory. The chip branches are
steered by monkeypatching `lodestar_tpu.utils.chip.on_tpu`, and every
program is traced through a fresh wrapper: jit's trace cache is keyed
by the Python function, so a function an earlier CPU test traced would
come back with its CPU branch and compile the XLA-scan program.
"""

from __future__ import annotations

import os
import time

import pytest

# one v5e chip: 16 GB of HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9
BUCKET = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_branch():
    """The chip's branches on, and the persistent compile cache off: a
    compile for a described chip is written to it but cannot be read
    back without one."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from lodestar_tpu.utils import chip

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(chip, "on_tpu", lambda: True)
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def points():
    """Host-built inputs of the 2048-set bucket, as the verifier builds
    them (CPU arrays; only their shapes and bounds are used)."""
    from lodestar_tpu.bls import kernels as K
    from lodestar_tpu.ops import curve as C

    pk, sig_x, sig_sign, u0, u1, bits, mask = K.ingest_batch_args(BUCKET)
    g2 = C.g2_batch_from_ints([K._pad_ints()["sig"]] * BUCKET)
    return {"g1": pk, "g2": g2, "bits": bits, "u0": u0, "u1": u1}


def _fresh(fn):
    return lambda *a: fn(*a)


def _specs(args, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        args,
    )


def _compile(name, fn, args, sharding):
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(_fresh(fn)).lower(*_specs(args, sharding)).compile()
    m = compiled.memory_analysis()
    used = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        + m.generated_code_size_in_bytes
        - m.alias_size_in_bytes
    )
    print(
        f"{name}: compiled in {time.perf_counter() - t0:.1f} s,"
        f" temp {m.temp_size_in_bytes} B, total {used} B"
    )
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{name}: no Pallas kernel in the program (CPU branch traced?)"
    )
    assert used < V5E_HBM_BYTES, f"{name}: {used} B exceeds one chip"
    return compiled


def _kernel(name, p):
    """(fn, args) of one Pallas kernel family at the bucket's shapes."""
    import jax
    import jax.numpy as jnp

    from lodestar_tpu.bls import kernels as K
    from lodestar_tpu.ops import curve as C
    from lodestar_tpu.ops import fq, ingest
    from lodestar_tpu.ops import pallas_ladder as PL
    from lodestar_tpu.ops import pallas_pairing as PP

    g1, g2 = p["g1"], p["g2"]
    if name == "chain":
        return lambda a: fq.pow_const(a, fq.P - 2), (g1.x,)
    if name == "g1_ladder":
        return PL.g1_scalar_mul, (g1.x, g1.y, p["bits"], g1.inf)
    if name == "g2_ladder":
        return PL.g2_scalar_mul, (g2.x, g2.y, p["bits"], g2.inf)
    if name == "g2_static_ladder":
        return (
            lambda x, y, i: PL.g2_scalar_mul_static(x, y, ingest.X_ABS, i),
            (g2.x, g2.y, g2.inf),
        )
    if name == "g2_sum":
        return PP.g2_sum, (g2,)
    if name == "sswu_iso":
        return ingest.sswu_iso_sum, (p["u0"], p["u1"])
    # the pairing kernels see n + 1 pairs: the sets and -G1
    pq = C.g1_batch_from_ints([K._pad_ints()["pk"]] * (BUCKET + 1))
    qq = C.g2_batch_from_ints([K._pad_ints()["h"]] * (BUCKET + 1))
    miller_args = (pq.x, pq.y, qq.x, qq.y)
    if name == "miller":
        return PP.miller_loop, miller_args
    f = jax.eval_shape(_fresh(PP.miller_loop), *miller_args)
    mask = jnp.ones((BUCKET + 1,), bool)
    if name == "product":
        return PP.fq12_masked_product, (f, mask)
    prod = jax.eval_shape(_fresh(PP.fq12_masked_product), f, mask)
    assert name == "final_exp"
    return PP.final_exponentiation, (prod,)


# kernel families chosen by measured deviceless compile time on an
# 8-core host: chain 2.2 s, g1 ladder 2.4 s, g2 ladder 8.3 s
@pytest.mark.parametrize("name", ["chain", "g1_ladder", "g2_ladder"])
def test_kernel_compiles_for_v5e(name, one_chip, chip_branch, points):
    fn, args = _kernel(name, points)
    _compile(name, fn, args, one_chip)


# tens of seconds to minutes each: miller 42 s, static ladder 42 s,
# g2 sum 47 s, product 80 s, sswu+iso 102 s, final exponentiation 166 s
@pytest.mark.slow
@pytest.mark.parametrize(
    "name",
    ["miller", "g2_static_ladder", "g2_sum", "product", "sswu_iso",
     "final_exp"],
)
def test_slow_kernel_compiles_for_v5e(name, one_chip, chip_branch, points):
    fn, args = _kernel(name, points)
    _compile(name, fn, args, one_chip)


@pytest.mark.slow
def test_fused_wave_programs_compile_for_v5e(one_chip, chip_branch):
    """prepare, pairing and final of a 2048-set ingest wave, as
    run_verify_batch_ingest_async dispatches them with fused stages."""
    import jax
    import jax.numpy as jnp

    from lodestar_tpu.bls import kernels as K

    args = K.ingest_batch_args(BUCKET)
    _compile("prepare", K._fused_ingest_batch_fn, args, one_chip)
    out = jax.eval_shape(_fresh(K._fused_ingest_batch_fn), *args)
    _compile("pairing", K._fused_pairing_fn, out[:5], one_chip)
    prod = jax.eval_shape(_fresh(K._fused_pairing_fn), *out[:5])
    _compile(
        "final",
        lambda p, v: jnp.logical_and(K._final_expo_impl(p), v),
        (prod, jnp.asarray(True)),
        one_chip,
    )


@pytest.mark.slow
@pytest.mark.parametrize("b", [128])
def test_host_path_programs_compile_for_v5e(b, one_chip, chip_branch):
    """The host-decompressed path at the rung a failed bulk bucket's
    per-job and per-set retries dispatch on the chip."""
    import jax

    from lodestar_tpu.bls import kernels as K

    pk, h, sig, bits, mask = K.host_batch_args(b)
    args = (pk, h[0], h[1], sig, bits, mask)
    _compile(f"prepare_batch@{b}", K._prepare_batch_impl, args, one_chip)
    out = jax.eval_shape(_fresh(K._prepare_batch_impl), *args)
    _compile(f"pairing@{b + 1}", K._fused_pairing_fn, out, one_chip)


@pytest.mark.slow
def test_mesh_program_compiles_for_four_v5e_chips(topo, chip_branch):
    """The whole-bucket mesh program over a 2x2 v5e mesh: one
    all-reduce, a Pallas kernel, and each chip's share fits."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from lodestar_tpu import parallel
    from lodestar_tpu.bls import kernels as K

    mesh = Mesh(np.array(topo.devices), (parallel.BATCH_AXIS,))
    program = parallel.whole_bucket_verify(
        mesh, _fresh(K._verify_ingest_local), 7
    )
    specs = _specs(
        K.ingest_batch_args(BUCKET),
        NamedSharding(mesh, P(parallel.BATCH_AXIS)),
    )
    lowered = jax.jit(program).lower(*specs)
    assert lowered.as_text().count("all_reduce") == 1
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes + m.argument_size_in_bytes < V5E_HBM_BYTES
