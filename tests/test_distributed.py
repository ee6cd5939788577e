"""Multi-device semantics of the snapshot pipelines (8 host CPU devices,
run in a subprocess so the XLA device-count flag never leaks into other
tests): the mesh counting and aggregate steps equal their local forms."""
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
    import sys
    sys.path.insert(0, "src")

    from repro.core.metadata import synth_filesystem
    from repro.core import snapshot as snap
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
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
