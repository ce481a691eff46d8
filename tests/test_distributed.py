"""Multi-device tests. Each spawns a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax locks the device
count at first init, so the main pytest process must stay single-device)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("REPRO_DRYRUN", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_param_sharding_rules_8dev():
    _run("""
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.runtime import sharding
    mesh = make_mesh((4, 2), ("data", "model"))
    params = {
        "embed": jnp.zeros((512, 64)),
        "period": [{"attn": {"wq": jnp.zeros((2, 64, 64)),
                             "wo": jnp.zeros((2, 64, 64))},
                    "mlp": {"w_gate": jnp.zeros((2, 64, 128)),
                            "w_down": jnp.zeros((2, 128, 64))},
                    "moe": {"e_gate": jnp.zeros((2, 4, 64, 128))},
                    "pre_norm": jnp.zeros((2, 64))}],
        "head": jnp.zeros((64, 512)),
    }
    specs = sharding.param_pspecs(params, mesh, mode="train")
    pos = specs["period"][0]
    assert specs["embed"] == P("model", "data"), specs["embed"]
    assert pos["attn"]["wq"] == P(None, "data", "model")
    assert pos["attn"]["wo"] == P(None, "model", "data")
    assert pos["moe"]["e_gate"] == P(None, "model", None, "data")
    assert pos["pre_norm"] == P(None, None)
    serve = sharding.param_pspecs(params, mesh, mode="serve")
    assert serve["period"][0]["attn"]["wq"] == P(None, None, "model")
    assert serve["period"][0]["moe"]["e_gate"] == P(None, "model", None, "data")
    print("rules ok")
    """)


def test_pjit_train_step_runs_8dev():
    _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.optim import adamw
    from repro.runtime import sharding
    from repro.runtime.steps import build_train_step
    from repro.launch.mesh import make_mesh
    from repro.data.pipeline import DataConfig, batch_at

    cfg = dataclasses.replace(get_smoke_config("llama3_2_1b"),
                              scan_layers=True, remat=True)
    mesh = make_mesh((4, 2), ("data", "model"))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    opt = adamw.init(params)
    pspecs = sharding.param_pspecs(params, mesh, mode="train")
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, named(pspecs))
    opt_specs = adamw.AdamWState(step=P(), m=pspecs, v=pspecs)
    opt = jax.device_put(opt, named(opt_specs))
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=8)
    step_fn = jax.jit(build_train_step(model, adamw.AdamWConfig(lr=1e-3)),
                      donate_argnums=(0, 1))
    losses = []
    for step in range(4):
        batch = {k: jnp.asarray(v) for k, v in batch_at(dc, step).items()}
        bspec = named(sharding.batch_pspecs(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}, mesh, 8))
        batch = jax.device_put(batch, bspec)
        params, opt, m = step_fn(params, opt, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses  # learning happens
    print("pjit train ok", losses)
    """)


def test_compressed_allreduce_bit_identical_2pods():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    from repro.optim.grad_compress import compressed_allreduce
    from repro.core import search_for_array, BF16

    mesh = make_mesh((2, 4), ("pod", "data"))
    rng = np.random.default_rng(0)
    g = rng.standard_normal((2, 4096)).astype("float32") * 1e-3
    grads = jnp.asarray(g).astype(jnp.bfloat16)
    p = search_for_array(np.asarray(grads), BF16, block_elems=4096)

    @partial(shard_map, mesh=mesh, in_specs=P("pod", None),
             out_specs=P("pod", None))
    def sync_enec(x):
        return compressed_allreduce(x[0], "pod", p,
                                    block_elems=4096)[None]

    @partial(shard_map, mesh=mesh, in_specs=P("pod", None),
             out_specs=P("pod", None))
    def sync_plain(x):
        return jax.lax.psum(x, "pod")

    a = np.asarray(sync_enec(grads)).astype(np.float32)
    b = np.asarray(sync_plain(grads)).astype(np.float32)
    np.testing.assert_array_equal(a, b)   # lossless => bit-identical sums
    print("compressed allreduce ok")
    """)


def test_elastic_reshard_4_to_8():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.runtime import elastic, sharding
    cfg = get_smoke_config("llama3_2_1b")
    m4 = elastic.best_mesh_for(cfg, n_devices=4, max_model=4)
    m8 = elastic.best_mesh_for(cfg, n_devices=8, max_model=4)
    assert np.prod(list(m4.shape.values())) == 4
    assert np.prod(list(m8.shape.values())) == 8
    x = {"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)}
    specs = {"w": jax.sharding.PartitionSpec(None, "model")
             if "model" in m8.shape else jax.sharding.PartitionSpec()}
    moved = elastic.reshard(x, m8, specs)
    np.testing.assert_array_equal(np.asarray(moved["w"]), np.asarray(x["w"]))
    print("elastic ok")
    """)
