"""Distribution tests that need multiple devices: run in subprocesses with
--xla_force_host_platform_device_count (NOT set globally — see dryrun.py).

Covers: sharded train step == single-device train step, MoE EP on a real
model axis, sharding rules divisibility fallback, dry-run cell lowering."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # every snippet builds its meshes with Auto axes, as the program does
    code = ("from repro.launch.mesh import make_mesh\n"
            + textwrap.dedent(code))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if "SKIP:" in out.stdout:
        pytest.skip(out.stdout.split("SKIP:", 1)[1].strip().splitlines()[0])
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_arch
        from repro.configs.base import RunConfig
        from repro.models.model import Model
        from repro.optim import AdamWConfig
        from repro.train.step import init_train_state, make_train_step
        from repro.sharding.rules import param_specs, opt_state_specs, named
        from repro.train.step import TrainState

        cfg = get_arch('deepseek-7b').reduced(d_model=64, n_layers=2,
                                              vocab_size=256)
        run = RunConfig(attn_impl='full', remat='nothing',
                        compute_dtype='float32')
        model = Model(cfg, run)
        acfg = AdamWConfig(lr=1e-2)
        state = init_train_state(model, jax.random.PRNGKey(0), acfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
        batch = {'tokens': toks, 'labels': toks}

        # single device
        s1, m1 = jax.jit(make_train_step(model, acfg, None))(state, batch)

        # sharded over (2 data, 4 model)
        mesh = make_mesh((2, 4), ('data', 'model'))
        p_specs = param_specs(state.params, mesh, run)
        o_specs = opt_state_specs(state.opt, p_specs, state.params, mesh, run)
        sh = TrainState(
            jax.tree.map(lambda s: named(mesh, s), p_specs),
            jax.tree.map(lambda s: named(mesh, s), o_specs), None)
        step = jax.jit(make_train_step(model, acfg, mesh), in_shardings=(sh, None))
        s2, m2 = step(state, batch)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(s1.params),
                                  jax.tree.leaves(s2.params)))
        print('LOSSDIFF', abs(float(m1['loss']) - float(m2['loss'])))
        print('PARAMDIFF', err)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-4
        assert err < 1e-4
        print('OK')
    """)
    assert "OK" in out


def test_moe_ep_sharded_matches_dense():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_arch
        from repro.configs.base import RunConfig
        from repro.models import moe as M

        cfg = get_arch('olmoe-1b-7b').reduced()
        run = RunConfig(compute_dtype='float32')
        params = M.init_moe(jax.random.PRNGKey(0), cfg)
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
        dense, aux_d, _ = M.moe_dense(params, x, cfg)
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg_hi = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
        ep, aux_e, _ = jax.jit(lambda p, x: M.moe_ep(p, x, cfg_hi, run, mesh))(
            params, x)
        err = float(jnp.max(jnp.abs(dense - ep)))
        print('ERR', err)
        assert err < 1e-4
        print('OK')
    """)
    assert "OK" in out


def test_moe_ep_a2a_matches_dense():
    """DeepSeek-style a2a EP (experts over model x data) == dropless dense
    at ample capacity, and is differentiable."""
    out = run_sub("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_arch
        from repro.configs.base import RunConfig
        from repro.models import moe as M
        cfg = get_arch('olmoe-1b-7b').reduced()   # 8 experts
        run = RunConfig(compute_dtype='float32')
        params = M.init_moe(jax.random.PRNGKey(0), cfg)
        x = 0.5 * jax.random.normal(jax.random.PRNGKey(1),
                                    (4, 16, cfg.d_model))
        dense, _, _ = M.moe_dense(params, x, cfg)
        mesh = make_mesh((2, 4), ('data', 'model'))
        cfg_hi = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.n_experts * 4),
            impl='ep_a2a'))
        ep, _, _ = jax.jit(lambda p, x: M.moe_ep_a2a(p, x, cfg_hi, run, mesh))(
            params, x)
        err = float(jnp.max(jnp.abs(dense - ep)))
        assert err < 1e-4, err
        g = jax.grad(lambda p: M.moe_ep_a2a(p, x, cfg_hi, run, mesh)[0]
                     .sum())(params)
        assert float(jnp.abs(g['w_gate']).sum()) > 0
        print('OK', err)
    """)
    assert "OK" in out


def test_dryrun_cell_multipod_small():
    """A multi-pod (2,2,2) mesh lowers+compiles a small arch cell and the
    record carries all roofline fields."""
    out = run_sub("""
        import jax, json
        from repro.configs import get_arch, SHAPES
        from repro.launch.dryrun import run_cell
        mesh = make_mesh((2, 2, 2), ('pod', 'data', 'model'))
        rec = run_cell(get_arch('whisper-small'), SHAPES['train_4k'], mesh)
        assert rec['roofline']['dominant'] in ('compute', 'memory',
                                               'collective')
        assert rec['memory']['per_device_bytes'] > 0
        assert rec['hlo_costs']['dot_flops_per_dev'] > 0
        print('OK', rec['roofline']['dominant'])
    """, timeout=1200)
    assert "OK" in out


def test_sharding_rules_divisibility_fallback():
    out = run_sub("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_arch
        from repro.configs.base import RunConfig
        from repro.sharding.rules import param_specs
        from repro.models.model import Model
        mesh = make_mesh((2, 4), ('data', 'model'))
        run = RunConfig()
        # whisper: 12 heads not divisible by 4? 12 % 4 == 0 -> sharded;
        # chatglm kv heads = 2 not divisible by 4 -> replicated
        cfg = get_arch('chatglm3-6b')
        model = Model(cfg, run)
        p_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = param_specs(p_abs, mesh, run)
        wq = specs['layers']['attn']['wq']
        wk = specs['layers']['attn']['wk']
        assert wq == P(None, None, 'model', None), wq  # 32 q heads sharded
        assert wk == P(None, None, None, None) or wk == P(), wk  # 2 kv heads
        print('OK')
    """)
    assert "OK" in out


def test_pipeline_parallelism_fwd_and_grad():
    """GPipe pipeline over a 4-stage 'pipe' axis == sequential layer stack,
    forward and backward."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax import lax
        from repro.sharding.pipeline import pipeline_apply
        mesh = make_mesh((4,), ('pipe',))
        L, d = 8, 16
        W = 0.3 * jax.random.normal(jax.random.PRNGKey(0), (L, d, d))
        def stage_fn(stage_w, x):
            def body(c, w):
                return jnp.tanh(c @ w), None
            return lax.scan(body, x, stage_w)[0]
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, d))
        ref = stage_fn(W, x)
        got = jax.jit(lambda w, x: pipeline_apply(
            stage_fn, w, x, mesh, n_micro=4))(W, x)
        assert float(jnp.max(jnp.abs(ref - got))) < 1e-5
        g1 = jax.grad(lambda w: stage_fn(w, x).sum())(W)
        g2 = jax.jit(jax.grad(lambda w: pipeline_apply(
            stage_fn, w, x, mesh, n_micro=4).sum()))(W)
        assert float(jnp.max(jnp.abs(g1 - g2))) < 1e-4
        print('OK')
    """, devices=4)
    assert "OK" in out


def test_elastic_checkpoint_restore_across_meshes():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint

        mesh_a = make_mesh((4, 2), ('data', 'model'))
        x = jnp.arange(64.0).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh_a, P('data', 'model')))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, {'x': xs})
        # restore onto a *different* mesh layout
        mesh_b = make_mesh((2, 4), ('data', 'model'))
        like = {'x': jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        shard = {'x': NamedSharding(mesh_b, P('model', 'data'))}
        got, _ = restore_checkpoint(d, 1, like, shardings=shard)
        np.testing.assert_array_equal(np.asarray(got['x']), np.asarray(x))
        assert got['x'].sharding.spec == P('model', 'data')
        print('OK')
    """)
    assert "OK" in out
