"""Satellite-1 regression (PR 7): both Mode-B train-step builders must derive
their batch specs / example inputs from the ONE shared builder
(``launch.sharding.batch_sds``) for EVERY config family — the old duplicated
spec code dropped the audio/vlm ``extra`` leaves from the MLMC path, so
``build_mlmc_train_step`` could not run the whisper / vision configs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config
from repro.configs.base import ShapeConfig
from repro.core.mlmc import MLMCConfig
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_mlmc_train_step, build_train_step

# one arch per family: dense, moe, hybrid, ssm, audio, vlm
FAMILY_ARCHS = [
    "smollm-360m",
    "qwen2-moe-a2.7b",
    "jamba-1.5-large-398b",
    "rwkv6-1.6b",
    "whisper-base",
    "llama-3.2-vision-90b",
]


@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_mlmc_batch_sds_matches_train_step(arch):
    cfg = get_reduced_config(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeConfig("t", 16, 4, "train")
    mc = MLMCConfig(T=8, m=1, V=1e9)
    bs = build_train_step(cfg, mesh, shape, dtype=jnp.float32)
    bm = build_mlmc_train_step(cfg, mesh, shape, mc, 1, dtype=jnp.float32)
    b1, b2 = bs.inputs[2], bm.inputs[2]
    # identical pytree structure — in particular the family 'extra' leaves
    assert jax.tree.structure(b1) == jax.tree.structure(b2)
    if cfg.family in ("audio", "vlm"):
        assert "extra" in b2, "MLMC step dropped the family extra leaves"
    # MLMC level J=1 scales only the batch dim (level_units = 2)
    for l1, l2 in zip(jax.tree.leaves(b1), jax.tree.leaves(b2)):
        assert l1.dtype == l2.dtype
        assert l2.shape[0] == 2 * l1.shape[0]
        assert l1.shape[1:] == l2.shape[1:]
