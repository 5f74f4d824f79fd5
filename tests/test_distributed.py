"""Sharding rules (pure logic — no devices needed) + a subprocess-based
multi-device integration test (8 fake CPU devices)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (default_rules, logical_spec,
                                        param_names)


@pytest.fixture(scope="module")
def mesh():
    # a 1x1 named mesh is enough to unit-test spec RESOLUTION logic --
    # divisibility is checked against axis sizes, so use a fake spec of
    # the production mesh instead:
    return FakeMesh({"data": 16, "model": 16})


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


class TestLogicalSpec:
    def test_divisible_dims_shard(self, mesh):
        spec = logical_spec((256, 4096), ("batch", None), mesh)
        assert spec == P("data")

    def test_indivisible_dim_replicates(self, mesh):
        # 8 kv heads cannot split over 16-way model axis
        spec = logical_spec((8,), ("kv_heads",), mesh)
        assert spec == P()

    def test_fallback_candidate_used(self, mesh):
        # expert: model first, then data; 60 divides neither -> replicate
        assert logical_spec((60,), ("expert",), mesh) == P()
        # 32 divides both; model has priority
        assert logical_spec((32,), ("expert",), mesh) == P("model")

    def test_axis_consumed_once(self, mesh):
        # both vocab and heads want "model": first (by priority) wins
        spec = logical_spec((32000, 64), ("vocab", "heads"), mesh)
        assert spec == P("model")

    def test_ctx_yields_to_kv_heads(self, mesh):
        # kv_heads=16 divisible: ctx must NOT steal the model axis
        spec = logical_spec((4, 128, 32768, 16, 128),
                            ("layer", "batch", "ctx", "kv_heads",
                             "head_dim"), mesh)
        assert spec == P(None, "data", None, "model")

    def test_ctx_takes_model_when_kv_cannot(self, mesh):
        spec = logical_spec((4, 128, 32768, 8, 128),
                            ("layer", "batch", "ctx", "kv_heads",
                             "head_dim"), mesh)
        assert spec == P(None, "data", "model")

    def test_multi_pod_batch_tuple(self):
        mesh3 = FakeMesh({"pod": 2, "data": 16, "model": 16})
        spec = logical_spec((256, 4096), ("batch", None), mesh3)
        assert spec == P(("pod", "data"))
        # batch=1 cannot shard at all
        assert logical_spec((1,), ("batch",), mesh3) == P()


class TestEHSpecs:
    """Sharded-EH dims place via the same divisibility-aware rules."""

    def test_stacked_lookup_operands(self, mesh):
        # 16 shards over the data axis; directory/buckets over model;
        # the probed bucket row (eh_slots) must stay contiguous
        assert logical_spec((16, 1 << 14), ("eh_shard", "eh_dir"),
                            mesh) == P("data", "model")
        assert logical_spec((16, 4096, 64),
                            ("eh_shard", "eh_buckets", "eh_slots"),
                            mesh) == P("data", "model")

    def test_indivisible_shards_replicate(self, mesh):
        # 2 shards cannot split a 16-way data axis -> replicate the
        # shard dim, directory still lands on model
        assert logical_spec((2, 1 << 14), ("eh_shard", "eh_dir"),
                            mesh) == P(None, "model")

    def test_sharded_eh_specs_helper(self):
        # a real (1x1) mesh: every dim divides, so names resolve in
        # priority order — exercises the NamedSharding construction
        import numpy as np
        from jax.sharding import Mesh
        from repro.distributed.sharding import sharded_eh_specs
        real = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))

        class Shaped:
            def __init__(self, shape):
                self.shape = shape
        specs = sharded_eh_specs(
            {"keys": Shaped((16, 1024)),
             "directories": Shaped((16, 1 << 14)),
             "global_depths": Shaped((16,))}, real)
        assert specs["keys"].spec == P("data")
        assert specs["directories"].spec == P("data", "model")
        assert specs["global_depths"].spec == P()


class TestKVViewSpecs:
    """Stacked per-shard KV view arrays place via the same
    divisibility-aware rules (kv_shard ~ eh_shard)."""

    def test_stacked_view_names(self, mesh):
        # 16 shards over data; kv_heads over model; ctx/seqs replicate
        # once their candidate axes are consumed
        spec = logical_spec((16, 4, 64, 128, 16, 128),
                            ("kv_shard", "layer", "kv_seqs", "ctx",
                             "kv_heads", "head_dim"), mesh)
        assert spec == P("data", None, None, None, "model")

    def test_indivisible_shards_replicate(self, mesh):
        # 2 shards cannot split a 16-way data axis -> the shard dim
        # replicates and kv_seqs claims the freed data axis instead
        spec = logical_spec((2, 4, 64, 128, 16, 128),
                            ("kv_shard", "layer", "kv_seqs", "ctx",
                             "kv_heads", "head_dim"), mesh)
        assert spec == P(None, None, "data", None, "model")

    def test_sharded_kv_view_specs_helper(self):
        import numpy as np
        from jax.sharding import Mesh
        from repro.distributed.sharding import sharded_kv_view_specs
        real = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"))

        class Shaped:
            def __init__(self, shape):
                self.shape = shape
        shape = (8, 2, 4, 32, 2, 8)
        specs = sharded_kv_view_specs(
            {"view_k": Shaped(shape), "view_v": Shaped(shape)}, real)
        assert specs["view_k"].spec == P("data", None, None, None, "model")
        assert specs["view_v"].spec == specs["view_k"].spec


class TestParamNames:
    def test_names_cover_all_leaves(self):
        import jax.numpy as jnp
        from repro.configs import get
        from repro.models.model import init_params
        cfg = get("hymba_1_5b").reduced()   # attn + ssm + mlp
        params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        names = param_names(params)
        flat_p = jax.tree.leaves(params)
        flat_n = jax.tree.leaves(names, is_leaf=lambda x: isinstance(
            x, list))
        assert len(flat_p) == len(flat_n)
        for p, n in zip(flat_p, flat_n):
            assert len(n) == p.ndim


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.distributed import sharding as shd
    from repro.models.model import init_params
    from repro.optim.adamw import adamw_init
    from repro.optim.schedule import wsd_schedule
    from repro.runtime.train import make_train_step

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get("internlm2_1_8b").reduced()
    pipe = SyntheticLM(cfg, DataConfig(seq_len=32, global_batch=8))
    p_host = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    params = jax.device_put(p_host, shd.param_specs(p_host, mesh))
    opt = adamw_init(params)
    step = make_train_step(cfg, lr_fn=lambda s: wsd_schedule(
        s, peak_lr=1e-2, warmup_steps=2, total_steps=100),
        remat=False).fn
    with shd.activate_mesh(mesh):
        jitted = jax.jit(step)
        losses = []
        for i in range(8):
            b = pipe.batch(i)
            batch = jax.device_put(b, shd.batch_spec(b, mesh))
            params, opt, metrics = jitted(params, opt, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    # the params really are distributed
    w = jax.tree.leaves(params)[0]
    assert len(w.sharding.device_set) > 1
    print(json.dumps({"losses": losses}))
""")


def test_multi_device_train_step_subprocess():
    """End-to-end sharded training on an 8-device host mesh: loss is
    finite, decreasing, and the program actually partitions."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG], env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["losses"][-1] < result["losses"][0]
