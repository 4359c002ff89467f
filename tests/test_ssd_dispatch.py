"""The SSD op picks its path from what it observes: the fused kernel on one
TPU device, the chunked jnp path on the CPU and under a mesh. Each trace
counts its path in ``ssd_path{path=...}``.

Nothing here lowers for a TPU: the kernel path is only traced, with
``jax.default_backend`` saying "tpu" as it does on the chip. The mesh case
runs in a subprocess on forced host devices (the main pytest process keeps
its single CPU device).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core.telemetry import tracer
from repro.models import Model

ROOT = Path(__file__).resolve().parent.parent


def _counts():
    return {p: tracer().value("ssd_path", path=p)
            for p in ("kernel", "chunked")}


def _traced_loss(model):
    """The jaxpr text of the model's loss and gradient on a small batch."""
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    grad = jax.grad(lambda p, t: model.loss_fn(p, {"tokens": t})[0])
    return str(jax.make_jaxpr(grad)(params, tokens))


@pytest.mark.parametrize("platform,path", [("tpu", "kernel"),
                                           ("cpu", "chunked")])
def test_ssd_path_follows_the_platform(monkeypatch, platform, path):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    model = Model(configs.get_reduced("mamba2-370m"))
    before = _counts()
    text = _traced_loss(model)
    after = _counts()
    other = "chunked" if path == "kernel" else "kernel"
    assert after[path] > before[path]
    assert after[other] == before[other]
    assert ("pallas_call" in text) == (path == "kernel")


def test_ssd_path_is_chunked_under_a_mesh():
    """On a (2, 2) data x model mesh the chunked path is traced even where
    the platform says TPU: a custom call would gather the heads that the
    mesh shards on 'model'."""
    code = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.core.telemetry import tracer
    from repro.launch.mesh import make_mesh
    from repro.models import Model

    jax.default_backend = lambda: "tpu"
    mesh = make_mesh((2, 2), ("data", "model"))
    model = Model(configs.get_reduced("hymba-1.5b"), mesh)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((4, 33), jnp.int32)
    grad = jax.grad(lambda p, t: model.loss_fn(p, {"tokens": t})[0])
    with jax.set_mesh(mesh):
        text = str(jax.make_jaxpr(grad)(params, tokens))
    print(json.dumps({"pallas": "pallas_call" in text,
                      "kernel": tracer().value("ssd_path", path="kernel"),
                      "chunked": tracer().value("ssd_path",
                                                path="chunked")}))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["chunked"] > 0 and got["kernel"] == 0, got
    assert not got["pallas"], got
