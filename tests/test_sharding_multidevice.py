"""Multi-device sharding: runs a reduced train step on an 8-fake-device mesh
in a subprocess (device count is locked at first jax init, so the main test
process stays single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import init_params, loss_fn
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import param_specs, opt_specs, make_run_policy
    from repro.launch.steps import _named
    from repro.train import TrainerConfig, make_train_state, make_train_step

    arch = sys_arch = "%ARCH%"
    cfg = get_config(arch).reduced()
    mesh = make_host_mesh(data=2, model=4)
    tp = 4
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32, tp=tp)
    pspec = param_specs(params, mesh)
    params = jax.device_put(params, _named(mesh, pspec))
    state = make_train_state(cfg, params)
    tc = TrainerConfig(grad_accum=2, total_steps=10, warmup_steps=1, tp=tp)
    pol = make_run_policy(mesh, remat=True)
    step = jax.jit(make_train_step(cfg, pol, tc))
    key = jax.random.PRNGKey(1)
    B, S = 4, 32
    if cfg.input_kind == "embeddings":
        toks = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    else:
        toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": jax.random.randint(key, (B,S), 0, cfg.vocab_size)}
    bspec = {"tokens": P("data"), "labels": P("data")}
    batch = jax.device_put(batch, _named(mesh, bspec))
    with mesh:
        state, metrics = step(state, batch)
        state, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), loss
    # every param the specs split comes out of the step split the same way
    # (replicated ones the partitioner may place as it likes)
    leaves = jax.tree.leaves(state["params"])
    specs = jax.tree.leaves(pspec, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(specs)
    split = [(leaf, spec) for leaf, spec in zip(leaves, specs)
             if any(ax is not None for ax in spec)]
    assert split, "no parameter is sharded"
    for leaf, spec in split:
        assert leaf.sharding.is_equivalent_to(NamedSharding(mesh, spec),
                                              leaf.ndim), (leaf.sharding, spec)
        assert leaf.sharding.shard_shape(leaf.shape) != leaf.shape
    print("SHARDED_OK", loss)
""")


@pytest.mark.parametrize("arch", ["yi-6b", "olmoe-1b-7b", "rwkv6-1.6b",
                                  "recurrentgemma-2b"])
def test_sharded_train_step(arch):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = _SCRIPT.replace("%ARCH%", arch)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout
