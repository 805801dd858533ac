"""§Perf optimization flags must be NUMERICALLY TRANSPARENT: sp_residual /
cache_seq_on_model change shardings and collective schedules, never math.

Runs in a subprocess with 8 forced host devices so the flags act on a real
(data=2, model=4) mesh (the main pytest process keeps 1 device)."""

import subprocess
import sys
import textwrap

from _subproc import repro_env

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_reduced
    from repro.models import transformer as tf
    from repro.train.train_step import TrainConfig, make_train_step
    from repro.train import optimizer as opt_mod
    from repro.serve.engine import ServeConfig, make_serve_step
    from repro.models import init_cache, init_params

    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((2, 4), ("data", "model"))

    # --- train: sp_residual transparency --------------------------------
    cfg = get_reduced("gemma3_4b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = opt_mod.init_state(params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 64)), jnp.int32)}
    losses = {}
    for flag in (False, True):
        tcfg = TrainConfig(n_microbatches=1, sp_residual=flag,
                           compute_dtype="float32")
        with mesh:
            step = jax.jit(make_train_step(cfg, tcfg, mesh))
            _, _, m = step(params, opt, batch)
        losses[flag] = float(m["loss"])
    assert abs(losses[True] - losses[False]) < 1e-4, losses
    print("sp_residual transparent:", losses)

    # --- decode: cache_seq_on_model transparency -------------------------
    cfg2 = get_reduced("llama3_8b")
    params2 = init_params(jax.random.PRNGKey(1), cfg2)
    tok = jnp.asarray(rng.integers(0, cfg2.vocab, (2, 1)), jnp.int32)
    outs = {}
    for flag in (False, True):
        scfg = ServeConfig(batch=2, max_seq=32, compute_dtype="float32",
                           cache_seq_on_model=flag)
        cache = init_cache(cfg2, 2, 32)
        with mesh:
            step = jax.jit(make_serve_step(cfg2, scfg, mesh))
            nxt, cache = step(params2, cache, tok)
            nxt2, _ = step(params2, cache, nxt)
        outs[flag] = (np.asarray(nxt), np.asarray(nxt2))
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][1], outs[True][1])
    print("cache_seq_on_model transparent")
""")


def test_perf_flags_numerically_transparent():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600, env=repro_env())
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "sp_residual transparent" in r.stdout
    assert "cache_seq_on_model transparent" in r.stdout
