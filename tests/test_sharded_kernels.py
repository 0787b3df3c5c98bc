"""Sharded-vs-single-device numerics pins for the mesh-aware division unit.

The PR-7 acceptance gates, each under a forced 8-device host platform
(subprocess: jax locks the device count at first init):

  * shard_map'd tiled divide/recip dispatch is bit-identical to the
    single-device kernels on ragged production shapes, and compiles with
    ZERO collectives — while the naive path (direct pallas_call under jit
    on sharded operands) demonstrably all-gathers;
  * sharded rsqrt dispatch is bit-identical to the single-device tiled
    rsqrt kernel on the same shard layout;
  * data-parallel K-Means at 10^6 points matches the unsharded run's
    assignments exactly and centroids to <= 1 int ulp, with the centroid
    divide consuming globally-reduced sums/counts (the psum/all-gather wire
    bytes in the HLO match launch/roofline.py's analytic models);
  * sharded batched Givens QR is bit-identical to the single-device batch.

Bit-identity note (docs/numerics.md): these pins hold at grid > 1 tile
geometries on both sides. Tiny grid-(1,1) mostly-masked launches can drift
1 ulp against other geometries (XLA CPU codegen variance at inlined small
shapes, same class as tests/test_jit_drift.py) — which is why the shapes
here are production-sized and ragged, not minimal.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENV8 = 'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"'


def _run(snippet: str, sentinel: str):
    r = subprocess.run([sys.executable, "-c", snippet],
                       capture_output=True, text=True, timeout=600,
                       env={**os.environ, "PYTHONPATH": "src",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=ROOT)
    assert sentinel in r.stdout, r.stdout + r.stderr


DIVIDE_SNIPPET = f"""
import os
{_ENV8}
import jax, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.launch import roofline as rl
from repro.sharding import rules as shr
from repro.kernels import ops, tsdiv as tsdiv_k

mesh = make_host_mesh()
for rows, cols in ((1992, 300), (2048, 384)):
    a = jax.random.uniform(jax.random.PRNGKey(0), (rows, cols), jnp.float32,
                           0.1, 10.0)
    b = jax.random.uniform(jax.random.PRNGKey(1), (rows, cols), jnp.float32,
                           0.1, 10.0)
    ref = ops.tsdiv_divide(a, b)                  # no mesh: plain launch
    sh = shr.data_sharding(mesh, 2, batch_size=rows)
    a_s, b_s = jax.device_put(a, sh), jax.device_put(b, sh)
    with shr.use_mesh(mesh):
        got = ops.tsdiv_divide(a_s, b_s)
    assert bool(jnp.all(got.view(jnp.int32) == ref.view(jnp.int32))), \\
        f"sharded divide not bit-identical at {{(rows, cols)}}"

# Compiled artifact checks at (2048, 384): the sharded dispatch must stay
# collective-free with per-shard-resident HBM traffic ...
rows, cols = 2048, 384
with shr.use_mesh(mesh):
    f_sh = jax.jit(lambda u, v: ops.tsdiv_divide(u, v))
    c_sh = f_sh.lower(a_s, b_s).compile()
hlo = c_sh.as_text()
colls = rl.parse_collectives(hlo, 8)
assert not colls["ops"], f"sharded dispatch compiled collectives: {{colls['ops']}}"
cost = c_sh.cost_analysis()
if isinstance(cost, (list, tuple)):
    cost = cost[0]
model = rl.elementwise_hbm_bytes(rows * cols, n_operands=2, n_results=1)
measured = float(cost.get("bytes accessed", 0.0))
assert 0.7 * model <= measured <= 1.5 * model, \\
    f"HBM traffic {{measured}} vs elementwise model {{model}}"

# ... while the naive path (direct tiled pallas_call under jit, no
# shard_map) silently all-gathers the sharded operands: the bug this PR
# fixes, pinned so it stays visible. Needs a grid > 1 shape — at grid
# (1, 1) interpret-pallas inlines to partitionable elementwise HLO.
a2 = jax.random.uniform(jax.random.PRNGKey(2), (2048, 512), jnp.float32,
                        0.1, 10.0)
a2_s = jax.device_put(a2, shr.data_sharding(mesh, 2, batch_size=2048))
f_naive = jax.jit(lambda u, v: tsdiv_k.tsdiv_divide_tiled_2d(u, v))
hlo_naive = f_naive.lower(a2_s, a2_s).compile().as_text()
assert "all-gather" in hlo_naive, "naive pallas jit no longer all-gathers?"
print("DIVIDE8 OK")
"""


def test_sharded_divide_bit_identity_and_no_collectives():
    """Tiled divide: sharded == single-device bitwise; zero collectives;
    HBM traffic matches the elementwise model; naive path all-gathers."""
    _run(DIVIDE_SNIPPET, "DIVIDE8 OK")


UNIFORM_SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from repro.core import division_modes as dm
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.launch import roofline as rl
from repro.sharding import rules as shr
from repro.workloads import kmeans as km

mesh = make_host_mesh()
assert mesh.devices.size == 4

# The plane over one number: rows shard, the divisor is replicated.
rows, cols = 1992, 300
a = jax.random.uniform(jax.random.PRNGKey(0), (rows, cols), jnp.float32,
                       0.1, 1e4)
b = jnp.float32(42.0)
ref = ops.tsdiv_divide_uniform(a, b)              # no mesh: plain launch
a_s = jax.device_put(a, shr.data_sharding(mesh, 2, batch_size=rows))
with shr.use_mesh(mesh):
    got = ops.tsdiv_divide_uniform(a_s, b)
    hlo = jax.jit(ops.tsdiv_divide_uniform).lower(a_s, b).compile().as_text()
assert bool(jnp.all(got.view(jnp.int32) == ref.view(jnp.int32))), \
    "sharded uniform divide not bit-identical"
assert not rl.parse_collectives(hlo, 4)["ops"], "uniform divide has collectives"

# Data-parallel K-Means through the fused kernels: the plane divide in the
# shard body takes the divisor unbroadcast, and the assignments are the
# unsharded run's.
cfg = dm.DivisionConfig(mode="taylor_pallas")
N, D, K, ITERS = 4 * 1024, 8, 16, 2
x = km.make_blobs(jax.random.PRNGKey(1), N, D, K)
init = jnp.take(x, jnp.arange(K) * (N // K), axis=0)
one = km.kmeans(x, cfg=cfg, n_iters=ITERS, init=init)
x_s = jax.device_put(x, shr.data_sharding(mesh, 2, batch_size=N))
with shr.use_mesh(mesh):
    f = jax.jit(lambda xx, ii: km.kmeans_sharded(
        xx, cfg=cfg, n_iters=ITERS, init=ii).assignments)
    four = f(x_s, init)
    text = f.lower(x_s, init).compile().as_text()
assert bool(jnp.all(one.assignments == four)), \
    "sharded K-Means assignments differ from the unsharded run"
assert dm.SCOPE_UNIFORM in text, "the shard body broadcast its divisor"
print("UNIFORM4 OK")
"""


def test_sharded_uniform_divisor():
    """A plane over one number on four devices: sharded == single-device
    bitwise with the divisor replicated and no collectives; ``kmeans_sharded``
    through the fused kernels assigns as the unsharded run does."""
    _run(UNIFORM_SNIPPET, "UNIFORM4 OK")


RECIP_RSQRT_SNIPPET = f"""
import os
{_ENV8}
import jax, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.launch import roofline as rl
from repro.sharding import rules as shr
from repro.kernels import ops, tsdiv as tsdiv_k

mesh = make_host_mesh()
rows, cols = 1992, 300
x = jax.random.uniform(jax.random.PRNGKey(0), (rows, cols), jnp.float32,
                       0.05, 50.0)
ref_recip = ops.tsdiv_recip(x)                    # no mesh: flatten path
ref_rsqrt = tsdiv_k.tsdiv_rsqrt_tiled_2d(x)       # single-device tiled kernel
x_s = jax.device_put(x, shr.data_sharding(mesh, 2, batch_size=rows))
with shr.use_mesh(mesh):
    got_recip = ops.tsdiv_recip(x_s)
    got_rsqrt = ops.tsdiv_rsqrt(x_s)
    f = jax.jit(lambda v: ops.tsdiv_rsqrt(v))
    hlo = f.lower(x_s).compile().as_text()
assert bool(jnp.all(got_recip.view(jnp.int32) == ref_recip.view(jnp.int32)))
assert bool(jnp.all(got_rsqrt.view(jnp.int32) == ref_rsqrt.view(jnp.int32)))
assert not rl.parse_collectives(hlo, 8)["ops"], "sharded rsqrt has collectives"
print("RECIPRSQRT8 OK")
"""


def test_sharded_recip_rsqrt_bit_identity():
    """recip/rsqrt dispatch: sharded == single-device bitwise, no
    collectives."""
    _run(RECIP_RSQRT_SNIPPET, "RECIPRSQRT8 OK")


KMEANS_SNIPPET = f"""
import os
{_ENV8}
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_host_mesh
from repro.launch import roofline as rl
from repro.sharding import rules as shr
from repro.core import division_modes as dm
from repro.eval.ulp import ulp_diff
from repro.workloads import kmeans as km

mesh = make_host_mesh()
N, D, K, ITERS = 1_000_000, 8, 8, 3
cfg = dm.DivisionConfig(mode="taylor")
x = km.make_blobs(jax.random.PRNGKey(0), N, D, K)
init = jnp.take(x, jnp.arange(K) * (N // K), axis=0)

ref = km.kmeans(x, cfg=cfg, n_iters=ITERS, init=init)
x_s = jax.device_put(x, shr.data_sharding(mesh, 2, batch_size=N))
with shr.use_mesh(mesh):
    got = km.kmeans_sharded(x_s, cfg=cfg, n_iters=ITERS, init=init)

assert bool(jnp.all(ref.assignments == got.assignments)), \\
    "sharded K-Means assignments differ from the unsharded run"
ud = ulp_diff(np.asarray(ref.centroids), np.asarray(got.centroids))
assert int(ud.max()) <= 1, f"centroids drifted {{int(ud.max())}} int ulp"

# The centroid divide must consume globally-reduced operands: the compiled
# HLO carries the group-8 reductions, with wire bytes matching the
# analytic models (counts: psum of K f32; sums: shard-ordered all-gather
# of the (K, D) block partials).
with shr.use_mesh(mesh):
    f = jax.jit(lambda xx, ii: km.kmeans_sharded(
        xx, cfg=cfg, n_iters=ITERS, init=ii).centroids)
    hlo = f.lower(x_s, init).compile().as_text()
ops_ = rl.parse_collectives(hlo, 8)["ops"]
ars = [o for o in ops_ if o["op"] == "all-reduce" and o["group"] == 8]
ags = [o for o in ops_ if o["op"] == "all-gather" and o["group"] == 8]
assert any(o["wire_bytes"] == rl.allreduce_wire_bytes(K, 8) for o in ars), \\
    f"no psum-of-counts matching the {{K}}-lane model: {{ops_}}"
assert any(o["bytes"] == 8 * K * D * 4 for o in ags), \\
    f"no all-gather of the (8, K, D) sum partials: {{ops_}}"
print("KMEANS8 OK")
"""


def test_sharded_kmeans_production_scale():
    """10^6-point data-parallel K-Means over 8 devices: assignments exact,
    centroids <= 1 int ulp, globally-reduced operands in the HLO."""
    _run(KMEANS_SNIPPET, "KMEANS8 OK")


QR_SNIPPET = f"""
import os
{_ENV8}
import jax, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.sharding import rules as shr
from repro.core import division_modes as dm
from repro.workloads import qr as qrw

mesh = make_host_mesh()
cfg = dm.DivisionConfig(mode="taylor")
a = jax.random.normal(jax.random.PRNGKey(3), (16, 12, 8), jnp.float32)
for via in ("div", "rsqrt"):
    q_ref, r_ref = qrw.qr_givens_batched(a, cfg, via=via)
    with shr.use_mesh(mesh):
        q_got, r_got = qrw.qr_givens_sharded(a, cfg, via=via)
    assert bool(jnp.all(q_ref.view(jnp.int32) == q_got.view(jnp.int32))), via
    assert bool(jnp.all(r_ref.view(jnp.int32) == r_got.view(jnp.int32))), via
print("QR8 OK")
"""


def test_sharded_qr_bit_identity():
    """Sharded batched Givens QR == single-device batch, bitwise, both
    rotation-coefficient formulations."""
    _run(QR_SNIPPET, "QR8 OK")


def test_kmeans_sharded_fallback_without_mesh():
    """No active mesh (or nothing divides): kmeans_sharded IS kmeans."""
    import jax
    import jax.numpy as jnp

    from repro.core import division_modes as dm
    from repro.workloads import kmeans as km

    cfg = dm.DivisionConfig(mode="taylor")
    x = km.make_blobs(jax.random.PRNGKey(0), 512, 4, 3)
    init = jnp.take(x, jnp.arange(3) * 100, axis=0)
    a = km.kmeans(x, cfg=cfg, n_iters=3, init=init)
    b = km.kmeans_sharded(x, cfg=cfg, n_iters=3, init=init)
    assert bool(jnp.all(a.assignments == b.assignments))
    assert bool(jnp.all(a.centroids == b.centroids))


def test_qr_batched_matches_loop():
    """qr_givens_batched == per-matrix qr_givens (vmap changes no numerics
    the residual tests rely on; allclose, not bitwise — vmap may reorder
    elementwise fusion)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import division_modes as dm
    from repro.workloads import qr as qrw

    cfg = dm.DivisionConfig(mode="taylor")
    a = jax.random.normal(jax.random.PRNGKey(5), (3, 10, 6), jnp.float32)
    qb, rb = qrw.qr_givens_batched(a, cfg)
    for i in range(a.shape[0]):
        qi, ri = qrw.qr_givens(a[i], cfg)
        np.testing.assert_allclose(np.asarray(qb[i]), np.asarray(qi),
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rb[i]), np.asarray(ri),
                                   rtol=0, atol=1e-6)
