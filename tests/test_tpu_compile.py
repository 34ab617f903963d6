"""Ahead-of-time compiles of the hot-path Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) runs a kernel body as Python on
the CPU; it cannot see what the TPU compiler refuses: blocks whose last two
dims break the (8, 128) tiling, dynamic unaligned vector loads, VMEM
overruns.  These tests lower and compile each kernel of the serving path at
phi3.5-moe-42b widths (d_model 4096, GQA 32/8 x 128, 16 experts of width
6400) — and the MLA/deepseek shapes the registry also serves — for a
described ``v5e:2x2`` topology.  Nothing runs; a compile takes a second or
two and needs no chip.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels import flash_chunk as fc
from repro.kernels import moe_gemm as mg
from repro.kernels import permute as pm
from repro.kernels import topk_gate as tg

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _gg_blocks(args):
    x, w = args[0], args[1]
    blocks = autotune.select_blocks(
        "grouped_gemm", x.shape + (w.shape[-1], w.shape[-3]), x.dtype)
    assert not mg.w_pad(x.shape[1], w.shape[-1], blocks["bh"], blocks["bd"])
    return blocks


# name -> (kernel call, argument shapes/dtypes); block sizes as the
# autotune defaults pick them at these shapes, or the kernel's own
CASES = {
    "flash_chunk_paged_gqa": (
        lambda *a: fc.flash_chunk_paged(*a, bq=64),
        [((8, 64, 32, 128), BF16), ((512, 16, 8, 128), BF16),
         ((512, 16, 8, 128), BF16), ((8, 64), I32), ((8,), I32),
         ((8,), I32), ((8,), I32)]),
    "flash_chunk_paged_decode": (
        lambda *a: fc.flash_chunk_paged(*a, bq=1),
        [((8, 1, 32, 128), BF16), ((512, 16, 8, 128), BF16),
         ((512, 16, 8, 128), BF16), ((8, 64), I32), ((8,), I32),
         ((8,), I32), ((8,), I32)]),
    "flash_chunk_paged_mla": (       # absorbed MLA: latent 512 + rope 64
        lambda *a: fc.flash_chunk_paged(*a, bq=16),
        [((4, 16, 128, 576), BF16), ((256, 16, 1, 576), BF16),
         ((256, 16, 1, 512), BF16), ((4, 64), I32), ((4,), I32),
         ((4,), I32), ((4,), I32)]),
    "flash_chunk_s2048": (
        lambda *a: fc.flash_chunk(*a, bq=64, bs=512),
        [((8, 64, 32, 128), BF16), ((8, 2048, 8, 128), BF16),
         ((8, 2048, 8, 128), BF16), ((8,), I32), ((8,), I32),
         ((8,), I32)]),
    "grouped_gemm_4096x6400": (
        lambda *a: mg.grouped_gemm(*a, bn=256, bd=512, bh=512),
        [((1024, 4096), BF16), ((16, 4096, 6400), BF16), ((17,), I32)]),
    "grouped_gemm_6400x4096": (
        lambda *a: mg.grouped_gemm(*a, bn=256, bd=512, bh=512),
        [((1024, 6400), BF16), ((16, 6400, 4096), BF16), ((17,), I32)]),
    "grouped_gemm_5120x1536": (      # deepseek-v2 expert width
        lambda *a: mg.grouped_gemm(*a, bn=256, bd=512, bh=512),
        [((1024, 5120), BF16), ((20, 5120, 1536), BF16), ((21,), I32)]),
    # the layer stack read in place, at the autotune default tiles
    "grouped_gemm_stacked_phi35_4096x6400": (
        lambda *a: mg.grouped_gemm(*a, **_gg_blocks(a)),
        [((1024, 4096), BF16), ((4, 16, 4096, 6400), BF16), ((17,), I32),
         ((), I32)]),
    "grouped_gemm_stacked_phi35_6400x4096": (
        lambda *a: mg.grouped_gemm(*a, **_gg_blocks(a)),
        [((1024, 6400), BF16), ((4, 16, 6400, 4096), BF16), ((17,), I32),
         ((), I32)]),
    "grouped_gemm_stacked_lite_2048x1408": (
        lambda *a: mg.grouped_gemm(*a, **_gg_blocks(a)),
        [((3072, 2048), BF16), ((8, 64, 2048, 1408), BF16), ((65,), I32),
         ((), I32)]),
    "grouped_gemm_stacked_lite_1408x2048": (
        lambda *a: mg.grouped_gemm(*a, **_gg_blocks(a)),
        [((3072, 1408), BF16), ((8, 64, 1408, 2048), BF16), ((65,), I32),
         ((), I32)]),
    "permute_tokens": (
        lambda *a: pm.permute_tokens(*a, bn=64),
        [((512, 4096), BF16), ((512,), I32)]),
    "permute_tokens_ragged": (
        lambda *a: pm.permute_tokens_ragged(*a, seg_stride=128, bn=64),
        [((512, 4096), BF16), ((512,), I32), ((4,), I32)]),
    "unpermute_tokens": (
        lambda *a: pm.unpermute_tokens(*a, bn=64),
        [((1024, 4096), BF16), ((512, 2), I32), ((512, 2), F32)]),
    "topk_gate": (
        lambda *a: tg.topk_gate(*a, 2, bt=256),
        [((512, 16), F32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
