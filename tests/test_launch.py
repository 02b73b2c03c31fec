"""Launch-layer units: sharding resolution, roofline parser, cell registry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import all_cells, get_spec
from repro.distributed.sharding import ShardingRules
from repro.launch.steps import _safe_spec
from repro.roofline.analyze import collective_bytes, _shape_bytes


def _fake_mesh(shape=(4, 2), axes=("data", "model")):
    # AbstractMesh: axis sizes without devices (enough for _safe_spec)
    return jax.sharding.AbstractMesh(shape, axes)


RULES = ShardingRules()


def test_safe_spec_basic():
    mesh = _fake_mesh()
    assert _safe_spec(mesh, RULES, ("batch", None), (8, 16)) == P("data", None)
    assert _safe_spec(mesh, RULES, ("fsdp", "mlp"), (8, 16)) == P("data", "model")


def test_safe_spec_divisibility_drop():
    mesh = _fake_mesh()
    # 15 doesn't divide by 4 -> axis dropped
    assert _safe_spec(mesh, RULES, ("batch",), (15,)) == P(None)
    # experts=3 can't take model=2; 'model' must stay available for dim 2
    spec = _safe_spec(mesh, RULES, ("experts", "mlp"), (3, 8))
    assert spec == P(None, "model")


def test_safe_spec_no_double_use():
    mesh = _fake_mesh()
    spec = _safe_spec(mesh, RULES, ("heads", "mlp"), (8, 8))
    # both want 'model'; only the first gets it
    assert spec == P("model", None)


def test_safe_spec_multi_axis_dim():
    mesh = _fake_mesh()
    spec = _safe_spec(mesh, RULES.with_overrides(mlp=("model", "data")),
                      ("mlp",), (16,))
    assert spec == P(("model", "data"))


def test_shape_bytes():
    assert _shape_bytes("f32[4,2]") == 32
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("pred[8]") == 8
    assert _shape_bytes("s32[2,2] and f32[2]") == 24


def test_collective_bytes_parser():
    hlo = """
      %ag = bf16[128,256] all-gather(%x), replica_groups={}
      %ar = f32[64] all-reduce(%y), to_apply=%sum
      %p = f32[4] collective-permute(%z)
      %ig = s32[2] iota()
      %agd = bf16[128,256] all-gather-done(%ag)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"] == 128 * 256 * 2
    assert out["all-reduce"] == 64 * 4
    assert out["collective-permute"] == 16
    assert out["total"] == 128 * 256 * 2 + 256 + 16


def test_all_cells_matrix():
    cells = all_cells()
    # 10 assigned archs x 4 shapes = 40 cells
    assert len(cells) == 40
    skips = [c for c in cells if c[2] is not None]
    assert len(skips) == 3          # long_500k on the 3 dense full-attn LMs
    assert all(s == "long_500k" for _, s, _ in [c for c in skips])


def test_specs_expose_sources():
    for arch in ["mixtral-8x7b", "gat-cora", "bst"]:
        assert get_spec(arch).source


def test_checkpoint_roundtrip_under_train(tmp_path):
    """train -> save -> resume continues from the stored step."""
    from repro.checkpoint import CheckpointManager
    from repro.configs import get_spec
    from repro.launch.train import train_lm
    import dataclasses

    cfg = dataclasses.replace(get_spec("smollm-360m").smoke, vocab=64)
    d = str(tmp_path / "ck")
    ckpt = CheckpointManager(d, keep=2, async_save=False)
    train_lm(cfg, steps=55, batch=4, seq_len=16, ckpt=ckpt, resume=False,
             log_every=1000)
    from repro.checkpoint import latest_step
    assert latest_step(d) == 55
    # resume: runs steps 55.. without error and saves a later checkpoint
    train_lm(cfg, steps=60, batch=4, seq_len=16, ckpt=ckpt, resume=True,
             log_every=1000)
    assert latest_step(d) == 60
