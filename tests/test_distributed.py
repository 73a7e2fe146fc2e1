"""Multi-device integration tests (8 host devices via subprocess — the
XLA device-count flag must precede jax import, so these run out-of-process).

Covers: sharded train step under the rules system, GPipe pipeline
equivalence, ring collective-matmul, elastic restore onto a resized mesh.

The gpipe and ring tests drive ``jax.shard_map(check_vma=)`` and
``jax.lax.axis_size`` directly (the jax 0.9 APIs the parallel layer uses).
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str) -> str:
    code = "import os\nos.environ['XLA_FLAGS']=" \
           "'--xla_force_host_platform_device_count=8'\n" + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900,
                       env={**os.environ, "PYTHONPATH": SRC})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduce_config
    from repro.models import lm
    from repro.optim import adamw
    from repro.parallel import sharding as sh
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_step

    cfg = reduce_config(get_config("qwen1.5-0.5b")).replace(dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)}
    # single-device reference
    _, _, m_ref = make_train_step(cfg)(params, opt, batch)
    mesh = make_mesh((2, 4), ("data", "model"))
    psh = sh.param_shardings(params, mesh, cfg)
    osh = sh.opt_state_shardings(psh, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    bsh = {"tokens": NamedSharding(mesh, P("data", None)),
           "labels": NamedSharding(mesh, P("data", None))}
    with mesh, sh.act_rules(sh.default_act_rules(mesh, "train", cfg)):
        step = jax.jit(make_train_step(cfg), in_shardings=(psh, osh, bsh))
        p2, o2, m2 = step(jax.device_put(params, psh),
                          jax.device_put(opt, osh),
                          jax.device_put(batch, bsh))
    np.testing.assert_allclose(float(m2["loss"]), float(m_ref["loss"]), rtol=1e-4)
    print("SHARDED_OK", float(m2["loss"]))
    """)
    assert "SHARDED_OK" in out


def test_gpipe_matches_sequential():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduce_config
    from repro.models import lm
    from repro.parallel.pipeline import gpipe_loss
    from repro.launch.mesh import make_mesh
    cfg = reduce_config(get_config("qwen1.5-0.5b")).replace(
        dtype="float32", layers=4, tie_embeddings=False)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh((2, 4), ("pod", "data"))
    B, S = 8, 16
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B,S), 0, cfg.vocab),
             "labels": jax.random.randint(jax.random.PRNGKey(2), (B,S), 0, cfg.vocab)}
    ref = float(lm.loss_fn(params, batch, cfg, remat=False))
    with mesh:
        pp = float(jax.jit(lambda p, b: gpipe_loss(p, b, cfg, mesh=mesh, n_micro=4))(params, batch))
    np.testing.assert_allclose(pp, ref, rtol=2e-4)
    g = jax.grad(lambda p: gpipe_loss(p, batch, cfg, mesh=mesh, n_micro=4))(params)
    gn = float(jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(g))))
    assert np.isfinite(gn) and gn > 0
    print("GPIPE_OK", pp, gn)
    """)
    assert "GPIPE_OK" in out


def test_ring_ag_matmul_matches_dense():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.parallel.overlap import ring_ag_matmul_ws
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("model",))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    y_ref = x @ w

    def f(xs, wf):
        return ring_ag_matmul_ws(xs, wf, "model")

    fsm = jax.shard_map(f, mesh=mesh, in_specs=(P(None, "model"), P()),
                        out_specs=P(), check_vma=False)
    # each shard holds a k-slice of x; ring accumulates the full product
    y = fsm(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
    print("RING_OK")
    """)
    assert "RING_OK" in out


def test_elastic_restore_onto_resized_mesh():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.configs import get_config, reduce_config
    from repro.models import lm
    from repro.checkpoint import checkpointing as ckpt
    from repro.runtime.elastic import plan_for_devices, resume_elastic
    from repro.parallel import sharding as sh
    from repro.launch.mesh import make_mesh

    cfg = reduce_config(get_config("qwen1.5-0.5b")).replace(dtype="float32")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    d = tempfile.mkdtemp()
    # save from an 8-device (2,4) mesh
    mesh8 = make_mesh((2, 4), ("data", "model"))
    p8 = jax.device_put(params, sh.param_shardings(params, mesh8, cfg))
    ckpt.save(d, 42, p8)
    # resume on 4 devices (1,4): scale-down event
    plan = plan_for_devices(4, model_parallel=4, old_data=2)
    assert plan.microbatch_scale == 2
    step, p4, mesh4 = resume_elastic(d, params, plan, cfg)
    assert step == 42 and mesh4.devices.size == 4
    a = jax.tree.leaves(params)[0]
    b = jax.tree.leaves(p4)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
