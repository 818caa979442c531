"""Multi-device tests on 8 fake CPU devices (subprocess isolation so the
XLA device-count flag never leaks into other tests)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import partition

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_multidevice(body: str):
    """Run `body` in a fresh python with 8 fake devices."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


# -- pure-python spec logic (no devices needed) ------------------------------


def test_fit_spec_divisibility():
    sizes = {"data": 16, "model": 16}
    assert partition.fit_spec(P("model", "data"), (49155, 1024), sizes) \
        == P(None, "data")
    assert partition.fit_spec(P("model", "data"), (49152, 1024), sizes) \
        == P("model", "data")
    assert partition.fit_spec(P(("pod", "data"), None), (64, 7),
                              {"pod": 2, "data": 16, "model": 16}) \
        == P(("pod", "data"), None)
    assert partition.fit_spec(P(("pod", "data"),), (31,),
                              {"pod": 2, "data": 16}) == P(None)


def test_param_specs_cover_every_leaf():
    import jax
    from repro import configs
    from repro.models import transformer as tfm
    from repro.models.common import ShardRules
    for arch in ("qwen2.5-32b", "kimi-k2-1t-a32b", "mamba2-2.7b",
                 "whisper-medium", "hymba-1.5b"):
        cfg = configs.get(arch).make_config()
        sds = jax.eval_shape(lambda c=cfg: tfm.init_params(
            c, jax.random.PRNGKey(0)))
        specs = partition.param_specs(cfg, sds, ShardRules())
        flat_sds = jax.tree.leaves(sds)
        flat_sp = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_sds) == len(flat_sp)
        # every big matrix is sharded on at least one axis
        for sd, sp in zip(flat_sds, flat_sp):
            if np.prod(sd.shape) > 4e6:
                assert any(a is not None for a in sp), (arch, sd.shape, sp)


# -- 8-device shard_map behaviours -------------------------------------------


def test_compressed_allreduce_mean_and_feedback():
    run_multidevice("""
        from jax.sharding import AxisType
        from repro.distributed import compression
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(AxisType.Auto,))
        ar = compression.make_compressed_allreduce(mesh, "data", block=64)
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(8, 32, 16), jnp.float32)  # per-shard grads
        e = jnp.zeros_like(g)
        grads = {"w": g}
        errs = {"w": e}
        mean1, errs = ar(grads, errs)
        true_mean = np.asarray(g).mean(0)
        err1 = np.abs(np.asarray(mean1["w"]) - true_mean).max()
        assert err1 < 0.05, err1              # int8 quantization error bound
        # error feedback: repeating the SAME grads, the running average of
        # the compressed means converges to the true mean (unbiasedness)
        acc = np.zeros_like(true_mean)
        for i in range(20):
            m, errs = ar(grads, errs)
            acc += np.asarray(m["w"])
        err20 = np.abs(acc / 20 - true_mean).max()
        assert err20 < err1 / 2, (err20, err1)
        print("OK", err1, err20)
    """)


def test_pipeline_matches_sequential():
    run_multidevice("""
        from jax.sharding import AxisType
        from repro.distributed.pipeline import pipeline_forward
        mesh = jax.make_mesh((8,), ("stage",), axis_types=(AxisType.Auto,))
        rng = np.random.RandomState(0)
        n_stages, n_micro, mb, d = 8, 4, 2, 16
        ws = jnp.asarray(rng.randn(n_stages, d, d) * 0.3, jnp.float32)
        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"])
        params = {"w": ws}
        mbs = jnp.asarray(rng.randn(n_micro, mb, d), jnp.float32)
        f = pipeline_forward(mesh, stage_fn, "stage")
        got = f(params, mbs)
        # sequential reference
        want = mbs
        for s in range(n_stages):
            want = jnp.tanh(want @ ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        print("OK")
    """)


def test_elastic_reshard_roundtrip():
    run_multidevice("""
        from jax.sharding import AxisType, NamedSharding
        from repro.distributed import elastic
        m1 = jax.make_mesh((4, 2), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
        m2 = jax.make_mesh((2, 4), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
        rng = np.random.RandomState(0)
        tree = {"w": jnp.asarray(rng.randn(16, 8), jnp.float32),
                "b": jnp.asarray(rng.randn(8), jnp.float32)}
        specs = {"w": P("data", "model"), "b": P(None)}
        on1 = elastic.reshard(tree, specs, m1)
        on2 = elastic.rescale_checkpoint(
            jax.tree.map(np.asarray, on1), specs, m2)
        np.testing.assert_allclose(np.asarray(on2["w"]),
                                   np.asarray(tree["w"]))
        assert on2["w"].sharding.mesh.shape["data"] == 2
        print("OK")
    """)


def test_small_mesh_train_step_shards():
    """A reduced model train step under a (2, 4) mesh with real
    in_shardings — the miniature of the production dry-run."""
    run_multidevice("""
        from jax.sharding import AxisType, NamedSharding
        from repro import configs
        from repro.distributed import partition
        from repro.models.common import ShardRules
        from repro.training import optimizer as opt_mod, step as step_mod
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = configs.get("granite-moe-1b-a400m").reduced()
        import dataclasses
        cfg = dataclasses.replace(cfg, d_model=64, d_ff=32, vocab_size=512,
                                  n_heads=4, n_kv_heads=2)
        rules = ShardRules()
        oc = opt_mod.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        state = step_mod.init_train_state(cfg, oc, jax.random.PRNGKey(0))
        sds = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           state)
        sizes = {"data": 2, "model": 4}
        p_specs = partition.fit_tree(
            partition.param_specs(cfg, sds["params"], rules),
            sds["params"], sizes)
        st_specs = {"params": p_specs,
                    "opt": partition.fit_tree(
                        partition.opt_specs(cfg, p_specs, sds["opt"], rules),
                        sds["opt"], sizes),
                    "step": P()}
        rngn = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rngn.randint(0, 512, (8, 16))),
                 "labels": jnp.asarray(rngn.randint(0, 512, (8, 16)))}
        b_specs = {"tokens": P(("data",), None), "labels": P(("data",), None)}
        ts = step_mod.make_train_step(cfg, rules, oc)
        sh = lambda t: jax.tree.map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        with jax.set_mesh(mesh):
            state = jax.device_put(state, sh(st_specs))
            batch = jax.device_put(batch, sh(b_specs))
            f = jax.jit(ts, in_shardings=(sh(st_specs), sh(b_specs)))
            state, m = f(state, batch)
            state, m = f(state, batch)
        assert np.isfinite(float(m["loss"]))
        print("OK loss", float(m["loss"]))
    """)
