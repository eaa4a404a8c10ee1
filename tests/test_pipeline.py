"""Pipeline-parallelism test: GPipe over a 2-stage axis must equal the
sequential composition of the stages (subprocess with 2 simulated devices).

Triage note (PR 2): the long-standing failure here was NOT a numerical
tolerance issue — the subprocess died on ``jax.sharding.AxisType``
(missing on the container jax) and on the then-missing
``repro.dist.pipeline`` module.  With ``AxisType`` meshes and the
GPipe implementation in place, the pipeline matches the sequential
reference within the original 1e-5 tolerances; nothing numerical changed.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.dist.pipeline import gpipe, bubble_fraction

    mesh = jax.make_mesh((2,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    D = 8
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (2, D, D)) / jnp.sqrt(D)   # one W per stage

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    params = {"w": w}
    x_mb = jax.random.normal(jax.random.fold_in(key, 1), (4, 3, D))

    y = jax.jit(lambda p, x: gpipe(stage_fn, p, x, mesh))(params, x_mb)
    # sequential reference
    ref = x_mb
    for s in range(2):
        ref = jnp.tanh(ref @ w[s])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert abs(bubble_fraction(4, 2) - 1/5) < 1e-9
    print("gpipe OK")
""")


def test_gpipe_matches_sequential():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "gpipe OK" in r.stdout
