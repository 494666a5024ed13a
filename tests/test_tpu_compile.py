"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts: tiles the hardware
cannot hold, a kernel it cannot partition, a program that does not fit the
device's memory. These tests hand it the real shapes: every stage of the
fused aggregation kernel on an (m=17, d=2^22) stack, and the full-width
SmolLM-360M Mode B step with the Pallas aggregation on a 1x1 mesh. A
compile that passes is not a chip run; ``chip_smoke.py`` is.

The topology is described inside a fixture (only one process may load the
TPU library at a time, so nothing here touches it at import).
"""
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

M, D = 17, 1 << 22
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described device are written to the persistent cache
    # but cannot be read back without a chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fused_stages():
    from repro.kernels import fused

    w = jnp.full((M, M), 1.0 / M, jnp.float32)
    return {
        "cwmed": lambda x: fused.cwmed(x),
        "cwtm_static": lambda x: fused.cwtm(x, 4),
        "cwtm_traced": lambda x: fused.cwtm_masked(x, jnp.int32(4)),
        "pairwise": lambda x: fused.pairwise_sqdist(x),
        "combine": lambda x: fused.weighted_combine(x, w),
        "mix_reduce_pairwise_combine": lambda x: fused.fused_pass(
            x, w=w, reduce="tm", trim=4, pairwise=True, combine=True),
        "cross_sqdist": lambda x: fused.cross_sqdist(x, x[:1]),
    }


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("stage", sorted(_fused_stages()))
def test_fused_stage_compiles_for_v5e(one_chip, stage, dtype):
    x = jax.ShapeDtypeStruct((M, D), dtype, sharding=one_chip)
    compiled = jax.jit(_fused_stages()[stage]).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("level", [0, 2])
def test_smollm_360m_modeb_step_fits_one_v5e(topo, monkeypatch, level):
    """Full-width SmolLM-360M, bf16, seq 512, global batch 8 per level unit
    — the shapes ``chip_smoke.py`` trains — at level 0 and at MLMC level
    J=2 (4x the rows), with the aggregation on compiled Pallas kernels."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.mlmc import MLMCConfig
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import build_mlmc_train_step, build_train_step

    # off the chip the ops run in interpret mode; compile the real kernels
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    cfg = get_config("smollm-360m")
    shape = ShapeConfig("smoke", 512, 8, "train")
    kw = dict(aggregator="cwtm", attack="sign_flip", agg_backend="pallas",
              dtype=jnp.bfloat16)
    if level == 0:
        step = build_train_step(cfg, mesh, shape, **kw)
    else:
        mc = MLMCConfig(T=4, m=1, V=8.0, kappa=1.0, j_cap=3)
        step = build_mlmc_train_step(cfg, mesh, shape, mc, level, **kw)
    # lowered under the mesh, as ``launch.train`` calls it: the nested
    # aggregation region then lowers against the concrete mesh
    with jax.set_mesh(mesh):
        compiled = step.fn.lower(*step.inputs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert peak < HBM_BYTES, peak
