"""Multi-device semantics (8 host CPU devices, run in a subprocess so the
XLA device-count flag never leaks into other tests): shard_map MoE vs local
oracle, sharded train step vs single-device, pipeline parallelism vs
sequential, snapshot pipelines sharded vs local, elastic checkpoint
restore across mesh shapes."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import sys
    sys.path.insert(0, "src")

    from repro.configs import get_config
    from repro.data.specs import reduced_config, reduced_shape, materialize_train_batch
    from repro import models
    from repro.launch.mesh import make_mesh
    from repro.training.steps import make_train_step, make_train_shardings, loss_fn
    from repro.training.optimizer import AdamWConfig, init_opt_state

    mesh = make_mesh((2, 4), ("data", "model"))

    # ---- 1. shard_map MoE == local oracle --------------------------------
    from repro.models.moe import apply_moe_local, apply_moe_sharded
    import dataclasses
    cfg = reduced_config(get_config("deepseek-moe-16b"))
    # capacity_factor high enough that neither layout drops tokens —
    # local and sharded dispatch then agree exactly
    cfg = cfg.replace(moe=dataclasses.replace(
        cfg.moe, num_experts=8, top_k=2, capacity_factor=8.0))
    from repro.models.moe import moe_desc
    from repro.models.layers import init_params as init_leaf
    desc = moe_desc(cfg)
    prm = init_leaf(desc, jax.random.PRNGKey(0), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
    y_local, aux_local = apply_moe_local(cfg, prm, x)
    y_sh, aux_sh = jax.jit(lambda p, x: apply_moe_sharded(
        cfg, p, x, mesh, ("data",), "model"))(prm, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_sh),
                               rtol=2e-3, atol=2e-3)
    # aux is a per-shard balance estimator under DP (intentional: EP wants
    # per-device balance) — agreement is approximate, outputs are exact
    np.testing.assert_allclose(float(aux_local), float(aux_sh), rtol=0.15)
    print("OK moe shard_map == local")

    # ---- 2. sharded train step == single-device --------------------------
    cfg2 = reduced_config(get_config("qwen2-1.5b")).replace(microbatches=2)
    params = models.init_params(cfg2, jax.random.PRNGKey(0))
    batch = materialize_train_batch(cfg2, reduced_shape("train"))
    opt = init_opt_state(params)
    oc = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    # single device
    p1, o1, m1 = jax.jit(make_train_step(cfg2, oc))(params, opt, batch)
    # sharded
    psh, osh, bsh = make_train_shardings(cfg2, mesh)
    params_s = jax.device_put(params, psh)
    opt_s = jax.device_put(opt, osh)
    batch_s = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    step = jax.jit(make_train_step(cfg2, oc, mesh), in_shardings=(psh, osh, bsh),
                   out_shardings=(psh, osh, None))
    p2, o2, m2 = step(params_s, opt_s, batch_s)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=2e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=3e-3)
    print("OK sharded train step == single device")

    # ---- 3. pipeline parallel == sequential (fwd + grad) -----------------
    from repro.distributed.pipeline import pipeline_apply, sequential_apply
    S = 4
    d = 16
    key = jax.random.PRNGKey(2)
    stack = {"w": jax.random.normal(key, (S, d, d)) * 0.3,
             "b": jnp.zeros((S, d))}
    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])
    x = jax.random.normal(jax.random.PRNGKey(3), (8, d))
    y_seq = sequential_apply(stage_fn, stack, x)
    y_pp = pipeline_apply(stage_fn, stack, x, mesh, axis="model", n_micro=4)
    np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_pp),
                               rtol=1e-5, atol=1e-5)
    g_seq = jax.grad(lambda s: jnp.sum(sequential_apply(stage_fn, s, x) ** 2))(stack)
    g_pp = jax.grad(lambda s: jnp.sum(pipeline_apply(
        stage_fn, s, x, mesh, axis="model", n_micro=4) ** 2))(stack)
    for a, b in zip(jax.tree.leaves(g_seq), jax.tree.leaves(g_pp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)
    print("OK pipeline parallel == sequential (fwd+grad)")

    # ---- 4. snapshot pipelines sharded == local ---------------------------
    from repro.core.metadata import synth_filesystem
    from repro.core import snapshot as snap
    table = synth_filesystem(2000, n_users=16, n_groups=8, seed=5)
    pcfg = snap.PipelineConfig(n_users=16, n_groups=8, n_dirs=40,
                               sketch=snap.dds.DDSketchConfig(n_buckets=512))
    rows_np, valid_np = snap.pad_rows(snap.preprocess(table, pcfg), 8)
    rows = {k: jnp.asarray(v) for k, v in rows_np.items()}
    valid = jnp.asarray(valid_np)
    c_local = snap.counting_local(pcfg, rows, valid)
    c_step = jax.jit(snap.make_counting_step(pcfg, mesh))
    c_sh = c_step(rows, valid)
    np.testing.assert_allclose(np.asarray(c_local), np.asarray(c_sh))
    a_local = snap.aggregate_local(pcfg, rows, valid)
    a_step = jax.jit(snap.make_aggregate_step(pcfg, mesh))
    a_sh = a_step(rows, valid)
    np.testing.assert_allclose(np.asarray(a_local["counts"]),
                               np.asarray(a_sh["counts"]), atol=1e-3)
    np.testing.assert_allclose(np.asarray(a_local["count"]),
                               np.asarray(a_sh["count"]), atol=1e-3)
    print("OK snapshot pipelines sharded == local")

    # ---- 5. elastic checkpoint across mesh shapes -------------------------
    import tempfile
    from repro.checkpoint import save_checkpoint, load_checkpoint
    tmp = tempfile.mkdtemp()
    w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("data", "model")))
    save_checkpoint(tmp, 1, {"w": w})
    mesh2 = make_mesh((8, 1), ("data", "model"))
    sh2 = {"w": NamedSharding(mesh2, P("data", None))}
    restored, _ = load_checkpoint(
        tmp, {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}, shardings=sh2)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0).reshape(8, 8))
    assert restored["w"].sharding.is_equivalent_to(sh2["w"], 2)
    print("OK elastic restore across meshes")
    print("ALL_DISTRIBUTED_OK")
""")


@pytest.mark.slow
def test_distributed_suite():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    # host devices only: a child must never reach for a chip this
    # process (or another test worker) may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), env=env,
        capture_output=True, text=True, timeout=1500)
    assert "ALL_DISTRIBUTED_OK" in r.stdout, (
        r.stdout[-3000:] + "\n---STDERR---\n" + r.stderr[-3000:])
