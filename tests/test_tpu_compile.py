"""Compile the decode path's device programs for a described TPU v5e.

Interpret mode cannot show what Mosaic refuses (tile-illegal blocks,
unsupported gathers, VMEM over the limit), so these tests compile the
executables ``chip_smoke.py`` runs, from shapes only and with
``interpret=False``, for a v5e that is described but not attached.  The
shapes are those of the 10 MB ``rand_50`` asset (W = 32, n = 11, packed
LUT) at the smoke's client capabilities, bucketed by the legacy ladder as
the executors bucket them.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""

import base64
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine.plan import LEGACY_POLICY
from repro.core.vectorized import _walk_batch_symbol_jit
from repro.kernels.rans_decode import ops
from repro.kernels.rans_decode.rans_decode import (LANES,
                                                   POINTER_KERNEL_REFUSAL,
                                                   check_vmem)
from repro.runtime.observability import WALK_GATHER

N_SYMBOLS = 10_000_000     # benchmarks.datasets.rand_exponential(50)
WAYS = 32
N_BITS = 11
ROWS_PER_BLOCK = 8
PACK = LANES // WAYS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _shapes(capability: int) -> dict:
    """Bucketed shapes of one request for the asset at ``capability``
    splits: a split walks ceil(N / capability / W) groups plus one."""
    per_split = -(-N_SYMBOLS // capability)
    rows = -(-capability // PACK)
    return dict(
        splits=LEGACY_POLICY.work(capability),
        rows=LEGACY_POLICY.work(-(-rows // ROWS_PER_BLOCK)) * ROWS_PER_BLOCK,
        steps=LEGACY_POLICY.work(-(-per_split // WAYS) + 1),
        slab=LEGACY_POLICY.mem(ROWS_PER_BLOCK * PACK * per_split + WAYS, 8),
        out=LEGACY_POLICY.mem(N_SYMBOLS),
        sym_bucket=LEGACY_POLICY.mem(N_SYMBOLS, 1024))


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pallas_args(sharding, sh: dict):
    rows = _sds(sharding, (sh["rows"], LANES))
    split = _sds(sharding, (sh["rows"] * PACK,))
    slabs = _sds(sharding, (sh["rows"] // ROWS_PER_BLOCK, sh["slab"]))
    lut = _sds(sharding, (1 << N_BITS,))
    # slabs, packed LUT (f/F tables None), 9 lane-packed tiles,
    # per-split g_hi and out_base
    return (slabs, lut, None, None, *[rows] * 9, split, split)


def _pallas_statics(sh: dict) -> dict:
    return dict(n_bits=N_BITS, ways=WAYS, n_steps=sh["steps"],
                rows_per_block=ROWS_PER_BLOCK, interpret=False, pack=PACK,
                n_symbols=sh["out"])


def _symbol_statics(sh: dict) -> dict:
    """The symbol-layout statics, with the T tile ``PallasExecutor.plan``
    derives for the packed LUT's 2048 words."""
    return dict(_pallas_statics(sh), tile_steps=check_vmem(
        sh["steps"], ROWS_PER_BLOCK, 1 << N_BITS))


def _kernel_grid(compiled) -> tuple[int, ...]:
    """The iteration bounds of the walk kernel's Mosaic module in a
    compiled executable: (row blocks, T tiles)."""
    from jax._src.lib.mlir import ir
    from jaxlib.mosaic.python import tpu
    (call,) = [line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    body = base64.b64decode(re.search(r'"body":"([^"]+)"', call).group(1))
    with ir.Context() as ctx:
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        text = str(ir.Module.parse(body))
    bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>", text)
    return tuple(int(b) for b in bounds.group(1).split(","))


@pytest.fixture(scope="module")
def fused_symbol_decode(one_chip):
    """The fused symbol-layout decode compiled for the v5e, once per
    capability for every test of this file."""
    compiled = {}

    def compile_for(capability: int):
        if capability not in compiled:
            sh = _shapes(capability)
            compiled[capability] = ops.decode_tiles_fused_symbol.lower(
                *_pallas_args(one_chip, sh), **_symbol_statics(sh)).compile()
        return compiled[capability]
    return compile_for


@pytest.mark.parametrize("capability", [2048, 256, 16])
def test_fused_symbol_decode_compiles_for_v5e(fused_symbol_decode,
                                              capability):
    """Up to 256 splits the walk is one T tile; a 16-split client's walk
    of 24,576 steps is over the VMEM limit as one block, so the compiled
    kernel walks it in several tiles."""
    compiled = fused_symbol_decode(capability)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    sh = _shapes(capability)
    blocks, tiles = _kernel_grid(compiled)
    assert blocks == sh["rows"] // ROWS_PER_BLOCK
    assert tiles == sh["steps"] // _symbol_statics(sh)["tile_steps"]
    assert (tiles > 1) == (capability == 16)


@pytest.mark.parametrize("capability", [2048, 256, 16])
def test_walk_gather_reads_windows_not_elements(fused_symbol_decode,
                                                capability):
    """The walk-order words are read as one window per split: no gather of
    the compiled ``recoil.walk_gather`` scope takes single elements."""
    hlo = fused_symbol_decode(capability).as_text()
    gathers = [line for line in hlo.splitlines()
               if re.search(r"\sgather\(", line) and WALK_GATHER in line]
    elementwise = [g for g in gathers
                   if set(re.search(r"slice_sizes=\{([\d,]*)\}", g)
                          .group(1).split(",")) == {"1"}]
    assert not elementwise, elementwise[0]


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason=POINTER_KERNEL_REFUSAL)
def test_fused_pointer_decode_compiles_for_v5e(one_chip):
    sh = _shapes(2048)
    ops.decode_tiles_fused.lower(
        *_pallas_args(one_chip, sh), **_pallas_statics(sh)).compile()


@pytest.mark.parametrize("capability", [2048, 64])
def test_jnp_symbol_walk_compiles_for_v5e(one_chip, capability):
    sh = _shapes(capability)
    splits = _sds(one_chip, (sh["splits"],))
    lanes = _sds(one_chip, (sh["splits"], WAYS))
    words = _sds(one_chip, (sh["splits"], WAYS), jnp.uint32)
    compiled = _walk_batch_symbol_jit.lower(
        _sds(one_chip, (sh["sym_bucket"],), jnp.uint32),
        _sds(one_chip, (1 << N_BITS,)), None, None,
        lanes, words, words, *[splits] * 7,
        n_bits=N_BITS, ways=WAYS, n_steps=sh["steps"],
        n_symbols=sh["out"], ctx_of_index=None).compile()
    assert compiled.memory_analysis() is not None
