"""Sharding rules: divisibility/duplicate drops + an 8-device SPMD subprocess."""
import os
import subprocess
import sys

import pytest

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, rules_for
from repro.launch.mesh import _auto
from repro.sharding import rules as shr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh11():
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


class TestSpecFor:
    def test_divisibility_drop(self):
        mesh = _mesh11()

        class FakeMesh:
            shape = {"data": 16, "model": 16}

        rules = {"heads": "model", "kv_heads": "model", "embed": None}
        # 32 heads shard; 8 kv heads don't divide 16 -> dropped
        s = shr.spec_for((4096, 32, 128), ("embed", "heads", "head_dim"),
                         rules, FakeMesh)
        assert s == P(None, "model", None)
        s = shr.spec_for((4096, 8, 128), ("embed", "kv_heads", "head_dim"),
                         rules, FakeMesh)
        assert s == P(None, None, None)

    def test_duplicate_axis_drop(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}

        rules = {"experts": "data", "embed": "data", "expert_mlp": "model"}
        s = shr.spec_for((16, 8192, 24576), ("experts", "embed", "expert_mlp"),
                         rules, FakeMesh)
        assert s == P("data", None, "model")  # embed's 'data' was taken

    def test_jamba_rules_fully_shard_experts(self):
        cfg = get_config("jamba_1_5_large")
        rules = rules_for(cfg)

        class FakeMesh:
            shape = {"pod": 2, "data": 16, "model": 16}

        s = shr.spec_for((36, 16, 8192, 24576),
                         ("layers", "experts", "embed", "expert_mlp"),
                         rules, FakeMesh)
        assert s == P(None, "data", None, "model")


class TestSpecForDrops:
    """spec_for's silent fallbacks become recorded entries (PR 7 satellite)."""

    def test_drops_recorded_with_reasons(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}

        rules = {"experts": "data", "embed": "data", "kv_heads": "model",
                 "seq": "pod"}
        drops = []
        s = shr.spec_for((16, 8192, 12, 100),
                         ("experts", "embed", "kv_heads", "seq"),
                         rules, FakeMesh, drops=drops)
        assert s == P("data", None, None, None)
        reasons = {d["dim"]: d["reason"] for d in drops}
        assert reasons == {1: "duplicate", 2: "indivisible",
                           3: "missing-axis"}
        kv = next(d for d in drops if d["dim"] == 2)
        assert kv["logical_axis"] == "kv_heads"
        assert kv["mesh_axis"] == "model"
        assert kv["dim_size"] == 12 and kv["mesh_axis_size"] == 16

    def test_intended_replication_is_not_a_drop(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}

        drops = []
        s = shr.spec_for((4096, 128), ("embed", "head_dim"),
                         {"embed": None}, FakeMesh, drops=drops)
        assert s == P(None, None)
        assert drops == []

    def test_param_fallbacks_names_gqa_kv_replication(self):
        """GQA kv_heads < model axis: the replicated KV tensors must show up
        as named entries with their byte sizes, not vanish."""
        class FakeMesh:
            shape = {"data": 32, "model": 32}

        cfg = get_config("llama3_8b")       # 8 kv heads < model=32
        entries = shr.param_fallbacks(cfg, FakeMesh)
        kv = [e for e in entries if e["reason"] == "indivisible"]
        assert kv, "expected indivisible drops on the 32-wide model axis"
        for e in kv:
            assert e["param"] and e["bytes"] > 0 and len(e["shape"]) >= 2
            assert e["mesh_axis_size"] == 32
            assert e["dim_size"] % 32 != 0


class TestBatchPartition:
    """data_sharding's all-or-nothing fallback is fixed: largest divisible
    prefix of ('pod','data') instead of replicating the whole batch."""

    class PodMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    def test_regression_batch16_pod2_data16(self):
        # The bug this PR fixes: batch=16 on pod=2 x data=16 used to fall
        # back to fully replicated because 16 % 32 != 0 — but the pod axis
        # alone divides 16, so the batch must shard over ('pod',).
        assert shr.batch_partition(self.PodMesh, 16) == ("pod",)
        assert shr.data_spec(self.PodMesh, 2, batch_size=16) == P("pod", None)

    def test_full_prefix_when_divisible(self):
        assert shr.batch_partition(self.PodMesh, 64) == ("pod", "data")
        assert shr.data_spec(self.PodMesh, 2, batch_size=64) == \
            P(("pod", "data"), None)

    def test_nothing_divides_replicates(self):
        assert shr.batch_partition(self.PodMesh, 7) == ()
        assert shr.data_spec(self.PodMesh, 2, batch_size=7) == P(None, None)

    def test_none_batch_uses_full_prefix(self):
        assert shr.batch_partition(self.PodMesh, None) == ("pod", "data")

    def test_single_pod_mesh(self):
        class M:
            shape = {"data": 16, "model": 16}

        assert shr.batch_partition(M, 48) == ("data",)
        assert shr.batch_partition(M, 10) == ()


class TestMakeHostMesh:
    """make_host_mesh raises ValueError (not a -O-stripped assert)."""

    def test_model_exceeds_device_count(self):
        from repro.launch.mesh import make_host_mesh

        n = jax.device_count()
        with pytest.raises(ValueError, match=f"exceeds the {n} available"):
            make_host_mesh(model=n + 1)

    def test_error_names_force_flag(self):
        from repro.launch.mesh import make_host_mesh

        with pytest.raises(ValueError,
                           match="xla_force_host_platform_device_count"):
            make_host_mesh(model=jax.device_count() + 1)

    def test_model_below_one(self):
        from repro.launch.mesh import make_host_mesh

        with pytest.raises(ValueError, match="must be >= 1"):
            make_host_mesh(model=0)

    def test_indivisible_names_device_count(self):
        from repro.launch.mesh import make_host_mesh

        n = jax.device_count()
        if n < 3:
            pytest.skip("needs >= 3 devices for an indivisible case")
        model = next(m for m in range(2, n) if n % m)
        with pytest.raises(ValueError, match=f"device count {n}"):
            make_host_mesh(model=model)

    def test_valid_mesh_still_builds(self):
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(model=1)
        assert mesh.shape["model"] == 1
        assert mesh.shape["data"] == jax.device_count()


def test_param_shardings_all_valid():
    """Every param's spec must divide its dims on the production mesh shape."""
    class FakeMesh:
        shape = {"data": 16, "model": 16}

    from repro.configs import ARCH_IDS
    from repro.models.params import model_specs, ParamSpec

    for arch in ARCH_IDS:
        cfg = get_config(arch)
        rules = rules_for(cfg)
        specs = model_specs(cfg)
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, ParamSpec))
        for p in leaves:
            s = shr.spec_for(p.shape, p.axes, rules, FakeMesh)
            for dim, part in zip(p.shape, s):
                if part is not None:
                    assert dim % FakeMesh.shape[part] == 0


SUBPROC_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import dataclasses
from repro.configs import get_smoke_config
from repro.models import init_params, abstract_params
from repro.sharding import rules as shr
from repro.optim import adamw
from repro.train import step as ts

cfg = dataclasses.replace(get_smoke_config("llama3_8b"))
from repro.launch.mesh import _auto
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=_auto(2))
params = init_params(cfg, jax.random.PRNGKey(0))
pshard = shr.param_shardings(cfg, mesh)
params = jax.device_put(params, pshard)
opt_cfg = adamw.AdamWConfig(division=cfg.division)
state = ts.init_state(cfg, params, opt_cfg)
batch = {"tokens": jnp.zeros((8, 32), jnp.int32),
         "labels": jnp.zeros((8, 32), jnp.int32)}
bshard = {k: shr.data_sharding(mesh, 2, batch_size=8) for k in batch}
batch = jax.device_put(batch, bshard)
with mesh:
    new_state, metrics = jax.jit(
        lambda s, b: ts.train_step(cfg, opt_cfg, s, b, n_micro=2))(state, batch)
loss = float(metrics["loss"])
assert loss > 0 and loss == loss, loss

# --- elastic resume: checkpoint under (4,2), restore under (2,4) ---
import tempfile, numpy as np
from repro.train import checkpoint as ck
with tempfile.TemporaryDirectory() as d:
    ck.save(d, 1, new_state)
    mesh2 = jax.make_mesh((2, 4), ("data", "model"), axis_types=_auto(2))
    pshard2 = shr.param_shardings(cfg, mesh2)
    state_shard2 = ts.TrainState(
        params=pshard2,
        opt=type(new_state.opt)(
            step=jax.sharding.NamedSharding(mesh2, P()),
            m=pshard2, v=pshard2),
        step=jax.sharding.NamedSharding(mesh2, P()))
    _, restored = ck.restore_latest(d, new_state, shardings=state_shard2)
    a = np.asarray(jax.device_get(jax.tree_util.tree_leaves(new_state.params)[0]))
    b = np.asarray(jax.device_get(jax.tree_util.tree_leaves(restored.params)[0]))
    assert np.array_equal(a, b), "elastic restore changed values"
    lf = jax.tree_util.tree_leaves(restored.params)[0]
    assert lf.sharding.mesh.shape["model"] == 4, "not resharded to new mesh"
print("SPMD8 OK", loss)
"""


def test_real_8device_spmd_training():
    """Real multi-device data+tensor parallel train step (subprocess: device
    count must be set before jax initializes)."""
    r = subprocess.run([sys.executable, "-c", SUBPROC_SNIPPET],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"},
                       cwd=ROOT)
    assert "SPMD8 OK" in r.stdout, r.stdout + r.stderr


COMPRESS_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compress

from repro.launch.mesh import _auto
mesh = jax.make_mesh((2, 4), ("pod", "data"), axis_types=_auto(2))
g = jnp.asarray(np.random.default_rng(0).normal(size=(2, 4, 64)), jnp.float32)
err = jnp.zeros_like(g)

def body(g_blk, e_blk):
    mean, new_err = compress.psum_compressed(g_blk, e_blk, "pod")
    return mean, new_err

f = jax.shard_map(body, mesh=mesh, in_specs=(P("pod", "data"), P("pod", "data")),
              out_specs=(P("pod", "data"), P("pod", "data")))
mean, new_err = jax.jit(f)(g, err)
# cross-pod mean: both pods see the same mean; check vs exact
exact = (g[0] + g[1]) / 2
got = np.asarray(mean)[0]
lsb = float(jnp.max(jnp.abs(g))) / 127
assert np.max(np.abs(got - np.asarray(exact))) <= lsb + 1e-6, "int8 mean off"
# pods agree
assert np.allclose(np.asarray(mean)[0], np.asarray(mean)[1])
print("COMPRESS8 OK")
"""


def test_int8_compressed_psum_on_pod_axis():
    """int8 error-feedback gradient compression across a real 'pod' axis."""
    r = subprocess.run([sys.executable, "-c", COMPRESS_SNIPPET],
                       capture_output=True, text=True, timeout=600,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       cwd=ROOT)
    assert "COMPRESS8 OK" in r.stdout, r.stdout + r.stderr
