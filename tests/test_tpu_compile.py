"""Compile-only checks for a TPU v5e that is described, not attached.

The TPU's compiler (Mosaic for the Pallas kernels, XLA for the bucket
program) refuses things that interpret mode on the CPU accepts: block
shapes not divisible by (8, 128), vector shape casts of narrow integer
types, programs that do not fit.  Each test here compiles one kernel at
the widths the modeler's users run (qwen3-4b MLP: 256 x 2560 x 9728;
attention at S=4096, D=128), the bucket program at 1024 candidates, or
its row variant at one block of fleet shapes, for one chip of a
described ``v5e:2x2``.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compile cache stays off around these
compiles (an entry written for a described chip cannot be read back).
"""
import os

import numpy as np
import pytest

M, K, N = 256, 2560, 9728
FLASH = (1, 4096, 8, 128)          # (B, S, H, D)


@pytest.fixture(scope="module")
def topo():
    # the TPU compiler otherwise writes its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name):
    """(fn, [(shape, dtype)]) of one kernel call at real widths."""
    import jax.numpy as jnp
    from repro.kernels.block_mm.ops import gated_mm, skip_mm
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.nm_spmm.ops import nm_spmm
    from repro.sparsity.nm import offsets_bits
    bf = jnp.bfloat16
    if name.startswith("nm_spmm"):
        n, m = (2, 4) if "2:4" in name else (2, 8)
        packed = name.endswith("packed")
        kc = K // m * n
        idx = ((kc // (8 // offsets_bits(m)), N), jnp.uint8) if packed \
            else ((kc, N), jnp.int8)
        return (lambda a, v, i: nm_spmm(a, v, i, n=n, m=m, packed=packed,
                                        interpret=False),
                [((M, K), bf), ((kc, N), bf), idx])
    if name == "skip_mm":
        nnz = (K // 128) * (N // 128) // 4          # density 0.25
        return (lambda a, w, ki, ji: skip_mm(a, w, ki, ji, bm=128, bk=128,
                                             bn=128, interpret=False),
                [((M, K), bf), ((K, N), bf), ((nnz,), jnp.int32),
                 ((nnz,), jnp.int32)])
    if name == "gated_mm":
        return (lambda a, w, mk: gated_mm(a, w, mk, bm=128, bk=128, bn=128,
                                          interpret=False),
                [((M, K), bf), ((K, N), bf), ((K // 128, N // 128),
                                              jnp.int32)])
    assert name == "flash_attention"
    return (lambda q, k, v: flash_attention(q, k, v, interpret=False),
            [(FLASH, bf)] * 3)


@pytest.mark.parametrize("name", [
    "nm_spmm 2:4", "nm_spmm 2:8", "nm_spmm 2:4 packed",
    "nm_spmm 2:8 packed", "skip_mm", "gated_mm", "flash_attention"])
def test_kernel_compiles_for_v5e(one_chip, name):
    import jax
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bucket_program_compiles_for_v5e_at_1024(one_chip):
    """The engine's bucket program on Table 5 conv2_x at 1024 candidates.
    Its float64 gammaln is emulated on the TPU at seconds of compile per
    call site, so the program must hold exactly one."""
    import jax
    import jax.numpy as jnp
    from repro.core import matmul
    from repro.core.engine import Sparseloop
    from repro.core.mapper import MapspaceConstraints
    from repro.core.presets import scnn_like, three_level_arch
    from repro.search.encoding import MapspaceEncoding
    from repro.search.strategies import init_population

    wl = matmul(3136, 576, 64, densities={"A": ("uniform", 0.4),
                                          "B": ("uniform", 0.55)})
    design = scnn_like(three_level_arch())
    cons = MapspaceConstraints(budget=4096, seed=0, spatial={1: {"n": 8}})
    enc = MapspaceEncoding(wl, design.arch.num_levels, cons)
    pop = enc.repair(init_population(jax.random.PRNGKey(0), enc, 1024))
    bucket, bounds, ids = enc.decode_bucketed(pop)
    bm = Sparseloop(design).bucketed_model(wl, bucket)

    def sds(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, jnp.asarray(x).dtype,
                                    sharding=one_chip)

    with jax.enable_x64():
        storage, comp = bm._bind_arch(None, len(bounds))
        args = ((sds(np.asarray(bounds, np.float64)),
                 sds(np.asarray(ids, np.int64)), (sds(storage), sds(comp))),
                tuple(sds(x) for x in bm._bind_params(None)))
        lowered = bm._prog.fn.lower(*args)
        assert lowered.as_text().count("chlo.lgamma") == 1
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("option", ["nm-2:4", "dsa-skip"])
def test_row_program_compiles_for_v5e(one_chip, option):
    """The bucket program's row variant, as the fleet sweep calls it:
    one block of 2:4 shapes, or of DSA value products at cache lengths
    up to 163840 (the selection's branch compiled in), each row with its
    own workload params (the density kind's switch is then vmapped too),
    still one gammaln."""
    import jax
    from repro.core.advisor import tpu_mapping
    from repro.core.batched import (ROW_BLOCK, bucket_for, lower_nests,
                                    pack_workload_params,
                                    stack_workload_params, template_of)
    from repro.core.engine import Sparseloop
    from repro.core.workload import matmul
    from repro.fleet.sweep import dsa_option, nm_option

    if option == "nm-2:4":
        opt = nm_option(2, 4)
        shapes = [(8 * (i + 1), 512 * (1 + i % 4), 1024)
                  for i in range(ROW_BLOCK)]
        wls = [matmul(*s, densities=opt.densities) for s in shapes]
        design = opt.design
    else:
        ctx = [4096 * (1 + i % 40) for i in range(ROW_BLOCK)]
        shapes = [(8, L, 512) for L in ctx]
        wls = [matmul(*s, densities={"B": ("selected", {
            "rank": "k", "L": L, "k": 2048, "fibre": 512})})
            for s, L in zip(shapes, ctx)]
        design = dsa_option().design_for(wls[0].densities)
    nests = [tpu_mapping(*s) for s in shapes]
    bucket = bucket_for(template_of(nests[0]), tuple(wls[0].rank_bounds))
    bm = Sparseloop(design).bucketed_model(wls[0], bucket,
                                           check_capacity=False)
    assert bm.caps.select == (option == "dsa-skip")
    bounds, ids, _ = lower_nests(bucket, nests, range(ROW_BLOCK))
    wp = stack_workload_params(
        [pack_workload_params(wl, bm.caps) for wl in wls])

    def sds(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    with jax.enable_x64():
        storage, comp = bm._bind_arch(None, ROW_BLOCK)
        args = ((sds(np.asarray(bounds, np.float64)),
                 sds(np.asarray(ids, np.int64)), (sds(storage), sds(comp))),
                tuple(sds(x) for x in wp.leaves()))
        lowered = bm._prog.row_program().lower(*args)
        assert lowered.as_text().count("chlo.lgamma") == 1
        compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
