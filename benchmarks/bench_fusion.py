"""Paper Fig. 20: temporal layer fusion — DRAM access reduction running
PointNet-family FC chains in Fusion Mode vs layer-by-layer.

Uses the paper's own compile-time planner (core.fusion.plan_fusion) on the
real MLP chains of our PointNet/PointNet++ models, plus wall-time of the
fused_mlp Pallas kernel vs per-layer execution (interpret mode).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro import nn
from repro.core import fusion as F
from repro.kernels.fused_mlp import ops as fm


CHAINS = {
    "pointnet_feat": [3, 64, 64, 64, 128, 1024],
    "pointnet_head": [1024, 512, 256, 40],
    "pnpp_sa1": [3, 64, 64, 128],
    "pnpp_sa2": [131, 128, 128, 256],
    "pnpp_fp": [384, 256, 128],
}


def run_chain(name, widths, n_points=8192,
              budget=F.VMEM_LIMIT_BYTES):
    groups = F.plan_fusion(widths, budget_bytes=budget)
    unfused = F.dram_bytes_unfused(n_points, widths)
    fused = F.dram_bytes_fused(n_points, widths, groups)
    emit(f"fusion/{name}_plan", float(len(groups)),
         f"reduction={unfused / fused:.2f}x;groups={len(groups)};"
         f"tiles={[g.tile_points for g in groups]}")
    return unfused / fused


def run_kernel_timing(n_points=2048):
    widths = [64, 256, 256, 64]
    rng = np.random.default_rng(0)
    p = nn.mlp_chain_init(jax.random.key(0), widths)
    x = jnp.asarray(rng.normal(size=(n_points, widths[0]))
                    .astype(np.float32))

    fused = jax.jit(lambda x: fm.fused_mlp_chain(x, p))
    layerwise = jax.jit(lambda x: nn.mlp_chain(p, x))
    emit("fusion/kernel_fused", timeit(fused, x), "interpret_mode=1")
    emit("fusion/xla_layerwise", timeit(layerwise, x), "")


def main():
    reductions = [run_chain(k, v) for k, v in CHAINS.items()]
    emit("fusion/mean_reduction", float(np.mean(reductions)),
         f"paper_range=1.33x-2.8x")
    run_kernel_timing()


if __name__ == "__main__":
    main()
