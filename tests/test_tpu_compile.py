"""Ahead-of-time compiles of the spconv kernels for a described TPU v5e.

Nothing runs: each test lowers one conv site of the served MinkUNet at the
65,536-row bucket through the ops wrapper with `interpret=False` and
compiles it for one chip of a `v5e:2x2` topology, so the TPU compiler's
refusals (block alignment, scoped VMEM, lowering of in-kernel ops) show up
here instead of on the chip.  The topology is described inside a fixture,
never at import: only one process may load the TPU library, and every
pytest worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fusion as F
from repro.core.mapping import KernelMaps
from repro.core.sparseconv import Epilogue
from repro.kernels.spconv import ops as spops

ROWS = 65536          # the top bucket of serve.buckets.DEFAULT_LADDER

# (K, cin, cout): a level-0 residual-block conv, a wide decoder-block conv,
# and the stem-level stride-2 down conv of minkunet_init's defaults
SITES = {"subm27_32x32": (27, 32, 32),
         "subm27_128x128": (27, 128, 128),
         "down8_32x64": (8, 32, 64)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip, so keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _site_shapes(sharding, k, cin, cout):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    idx = sds((k, ROWS), jnp.int32)
    maps = KernelMaps(idx, idx, sds((k, ROWS), jnp.bool_),
                      sds((k, 3), jnp.int32), inv=idx, inv_t=idx)
    return (sds((ROWS, cin), jnp.float32), maps,
            sds((k, cin, cout), jnp.float32), sds((cout,), jnp.float32),
            sds((ROWS,), jnp.bool_), sds((ROWS, cout), jnp.float32))


def _fod(feats, maps, w, ln, mask, res):
    return spops.sparse_conv_fod(feats, maps, w, ROWS, interpret=False)


def _fused(feats, maps, w, ln, mask, res):
    k, cin, cout = w.shape
    plan = F.plan_conv_epilogue(ROWS, cin, cout, k, residual=True)
    epi = Epilogue(ln_scale=ln, ln_bias=ln, relu=True, mask=mask,
                   residual=res)
    return spops.sparse_conv_fused(feats, maps, w, ROWS, epilogue=epi,
                                   feat_tile=plan.feat_tile,
                                   out_tile=plan.out_tile, interpret=False)


@pytest.mark.parametrize("site", sorted(SITES))
@pytest.mark.parametrize("flow", ["pallas", "pallas_fused"])
def test_spconv_compiles_for_v5e(one_chip, flow, site):
    fn = {"pallas": _fod, "pallas_fused": _fused}[flow]
    args = _site_shapes(one_chip, *SITES[site])
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (ROWS, SITES[site][2])
    assert np.dtype(out.dtype) == np.float32
