"""Real sharded execution on forced host devices (subprocess so the main
pytest process keeps its single CPU device):

  * train step of a reduced arch on a (2,2) data×model mesh, params/opt
    sharded, numerics finite;
  * elastic re-mesh: checkpoint saved under (2,2) restores onto (4,1) and
    (1,4) meshes and continues training (mesh-agnostic checkpoints);
  * reduced-config dry-run lower+compile on the tiny mesh (exercises the
    dryrun machinery inside the test suite).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def run_sub(code: str, timeout=420) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
             "JAX_PLATFORMS": "cpu"})


def test_sharded_train_step_and_elastic_remesh(tmp_path):
    code = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import Model, param_spec_tree
    from repro.optim import OptConfig, adamw_init
    from repro.runtime import make_train_step, opt_spec_tree, shard_batch
    from repro.checkpoint import CheckpointManager, to_device

    cfg = configs.get_reduced("qwen3-8b")
    mesh = make_mesh((2, 2), ("data", "model"))
    model = Model(cfg, mesh)
    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        specs = param_spec_tree(cfg)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, specs)
        opt = adamw_init(params)
        step = make_train_step(model, OptConfig(), num_microbatches=2)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                                    cfg.vocab_size, jnp.int32)
        batch = shard_batch({{"tokens": np.asarray(tokens)}}, mesh)
        params, opt, metrics = step(params, opt, batch, jnp.zeros((), jnp.int32))
        loss1 = float(metrics["loss"])
        assert np.isfinite(loss1), loss1
        mgr = CheckpointManager(r"{tmp_path}", async_save=False)
        mgr.save(1, {{"params": params, "opt": opt}}, meta={{}})

    # elastic restore onto different meshes
    for shape in ((4, 1), (1, 4)):
        mesh2 = make_mesh(shape, ("data", "model"))
        model2 = Model(cfg, mesh2)
        with jax.set_mesh(mesh2):
            _, trees, _ = mgr.restore()
            p2 = to_device(trees["params"], param_spec_tree(cfg), mesh2)
            o2 = to_device(trees["opt"], opt_spec_tree(model2, mesh2), mesh2)
            o2["count"] = jnp.asarray(o2["count"], jnp.int32)
            step2 = make_train_step(model2, OptConfig(), num_microbatches=1)
            b2 = shard_batch({{"tokens": np.asarray(tokens)}}, mesh2)
            p2, o2, m2 = step2(p2, o2, b2, jnp.ones((), jnp.int32))
            assert np.isfinite(float(m2["loss"]))
            print("REMESH_OK", shape, float(m2["loss"]))
    print("ALL_OK", loss1)
    """)
    r = run_sub(code)
    assert "ALL_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    assert r.stdout.count("REMESH_OK") == 2


def test_dryrun_machinery_on_tiny_mesh():
    code = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, dataclasses
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.launch.hlo_analysis import collective_bytes, memory_stats
    from repro.launch.jaxpr_cost import traced_cost, loop_trip_table
    from repro.models import Model
    from repro.models.common import ShapeConfig
    from repro.configs.shapes import input_specs

    cfg = configs.get_reduced("tinyllama-1.1b")
    mesh = make_mesh((2, 4), ("data", "model"))
    # widen the reduced cfg so dims divide the 4-way model axis
    cfg = dataclasses.replace(cfg, d_model=128, n_heads=8, n_kv_heads=4,
                              d_ff=256, vocab_size=512)
    model = Model(cfg, mesh)
    shape = ShapeConfig("tiny_prefill", "prefill", 64, 4)
    inputs = input_specs(cfg, shape, mesh)
    fn = jax.jit(lambda p, b: model.prefill(p, b))
    with jax.set_mesh(mesh):
        lowered = fn.lower(model.abstract_params(), inputs)
    compiled = lowered.compile()
    mem = memory_stats(compiled)
    assert mem["total_hbm_bytes"] > 0
    coll = collective_bytes(compiled.as_text(), 8,
                            loop_trip_table("prefill", num_layers=cfg.num_layers))
    cost = traced_cost(fn, model.abstract_params(), inputs)
    assert cost.flops > 0
    print("DRYRUN_OK", mem["total_hbm_bytes"], int(coll["total_bytes"]),
          cost.flops)
    """)
    r = run_sub(code)
    assert "DRYRUN_OK" in r.stdout, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"


def test_trainer_init_state_is_sharded_on_mesh(tmp_path):
    """On a mesh, Trainer.init_state creates every parameter and optimizer
    leaf with its spec's sharding, holding the values of the unsharded
    init (the four-chip smoke compares against that init on one chip)."""
    code = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import Model, param_spec_tree
    from repro.optim import OptConfig
    from repro.runtime import Trainer, TrainerConfig, opt_spec_tree

    cfg = configs.get_reduced("hymba-1.5b")
    mesh = make_mesh((2, 2), ("data", "model"))
    model = Model(cfg, mesh)
    tr = Trainer(model, None, OptConfig(),
                 TrainerConfig(ckpt_dir=r"{tmp_path}"), mesh=mesh)
    tr.init_state()
    is_spec = lambda x: isinstance(x, P)
    for tree, specs in ((tr.params, param_spec_tree(cfg, mesh)),
                        (tr.opt_state, opt_spec_tree(model, mesh))):
        leaves = jax.tree.leaves(tree)
        spec_leaves = jax.tree.leaves(specs, is_leaf=is_spec)
        assert len(leaves) == len(spec_leaves)
        for x, sp in zip(leaves, spec_leaves):
            assert x.sharding.is_equivalent_to(NamedSharding(mesh, sp),
                                               x.ndim), (x.sharding, sp)
    sharded = [s for x in jax.tree.leaves(tr.params)
               for s in [x.sharding.spec] if any(a is not None for a in s)]
    assert sharded, "no parameter is sharded"
    whole = Model(cfg).init(jax.random.PRNGKey(0))
    for a, b in zip(jax.tree.leaves(tr.params), jax.tree.leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("INIT_SHARDED_OK", len(sharded))
    """)
    r = run_sub(code)
    assert "INIT_SHARDED_OK" in r.stdout, \
        f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
