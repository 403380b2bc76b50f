"""Multi-device distribution tests.

These run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the parent process has locked jax to 1 device).  Each scenario script
executes sharded train/serve/pipeline steps on a real 8-device mesh and
asserts numerics against the single-device reference.
"""
import pytest

pytestmark = pytest.mark.slow  # minutes-long end-to-end tier (see pytest.ini)
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(script: str, n: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import common
        from repro.parallel import sharding as shd
        from repro.train import optimizer as opt, step as step_mod
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = common.reduced(configs.get("smollm-360m"), vocab=128,
                             n_layers=2, dtype="float32")
        tcfg = step_mod.TrainConfig(adamw=opt.AdamWConfig(lr=1e-3,
                                                          warmup_steps=0))
        data = SyntheticLM(DataConfig(vocab=128, global_batch=8, seq_len=32))
        batch = data.batch_at(0)
        state = step_mod.init_state(jax.random.PRNGKey(0), cfg, tcfg)

        # single device reference
        ref_state, ref_metrics = jax.jit(
            lambda s, b: step_mod.train_step(s, b, cfg, tcfg))(state, batch)

        # 4x2 mesh sharded
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        shd.set_mesh_axes(mesh.axis_names)
        with mesh:
            fn = step_mod.make_jitted_train_step(mesh, cfg, tcfg)
            sh_state, sh_metrics = fn(state, batch)
        np.testing.assert_allclose(float(sh_metrics["loss"]),
                                   float(ref_metrics["loss"]), rtol=1e-4)
        for a, b in zip(jax.tree.leaves(ref_state["params"]),
                        jax.tree.leaves(sh_state["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-4)
        print("SHARDED_MATCH")
    """)
    assert "SHARDED_MATCH" in out


def test_sharded_decode_matches_single_device():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import common, lm
        from repro.parallel import sharding as shd
        from repro.serve import engine

        cfg = common.reduced(configs.get("gemma2-27b"), vocab=128,
                             n_layers=2, dtype="float32")
        params = lm.init(jax.random.PRNGKey(0), cfg)
        tok = jnp.asarray([[3],[5],[7],[9]], jnp.int32)
        states = lm.decode_state_init(cfg, 4, 16)
        ref_logits, _ = lm.decode_step(params, tok, states, jnp.int32(0),
                                       cfg)

        mesh = jax.make_mesh((2, 4), ("data", "model"))
        shd.set_mesh_axes(mesh.axis_names)
        with mesh:
            fn = engine.make_jitted_serve_step(mesh, cfg)
            sh_logits, new_states = fn(params, tok,
                                       lm.decode_state_init(cfg, 4, 16),
                                       jnp.int32(0))
        np.testing.assert_allclose(np.asarray(sh_logits),
                                   np.asarray(ref_logits),
                                   rtol=2e-3, atol=2e-3)
        print("DECODE_MATCH")
    """)
    assert "DECODE_MATCH" in out


def test_pipeline_parallel_matches_sequential():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel import pipeline as pp

        n_stages, n_micro, mb, d = 4, 8, 2, 16
        mesh = jax.make_mesh((n_stages,), ("stage",))
        rng = np.random.default_rng(0)
        # 4 stages each with a weight matrix
        w = jnp.asarray(rng.normal(size=(n_stages, d, d)) / np.sqrt(d),
                        jnp.float32)
        x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

        def stage_fn(wi, h):
            return jnp.tanh(h @ wi)

        piped = pp.pipelined_apply(stage_fn, mesh, "stage")
        y = jax.jit(piped)(w, x)

        # sequential reference
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ w[s])
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("PIPELINE_MATCH bubble=%.3f" % pp.bubble_fraction(n_stages,
                                                                n_micro))
    """)
    assert "PIPELINE_MATCH" in out


def test_compressed_pod_allreduce_multidevice():
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.parallel import compression

        mesh = jax.make_mesh((8,), ("pod",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 4096)), jnp.float32)
        err = jnp.zeros_like(g)

        f = jax.shard_map(lambda gg, ee: compression.compress_psum(
                              gg[0], ee[0], "pod"),
                          mesh=mesh, in_specs=(P("pod"), P("pod")),
                          out_specs=(P(), P("pod")), check_vma=False)
        avg, _ = jax.jit(f)(g, err)
        expect = np.asarray(g).mean(0)
        rel = np.linalg.norm(np.asarray(avg) - expect) / \
            np.linalg.norm(expect)
        assert rel < 0.05, rel
        print("COMPRESS_MATCH", rel)
    """)
    assert "COMPRESS_MATCH" in out


def test_elastic_restore_across_topologies(tmp_path):
    """Checkpoint on a 4x2 mesh, restore onto 2x4 - elastic scaling."""
    out = run_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro import configs
        from repro.models import common
        from repro.parallel import sharding as shd
        from repro.train import optimizer as opt, step as step_mod
        from repro.checkpoint.manager import CheckpointManager

        cfg = common.reduced(configs.get("smollm-360m"), vocab=128,
                             n_layers=2)
        tcfg = step_mod.TrainConfig()
        state = step_mod.init_state(jax.random.PRNGKey(0), cfg, tcfg)
        mgr = CheckpointManager({str(tmp_path)!r})

        mesh1 = jax.make_mesh((4, 2), ("data", "model"))
        shd.set_mesh_axes(mesh1.axis_names)
        sspecs = shd.tree_specs(step_mod.state_specs(cfg, tcfg))
        sh1 = shd.shardings_pruned(mesh1, sspecs, state)
        state1 = jax.device_put(state, sh1)
        mgr.save(3, state1)

        mesh2 = jax.make_mesh((2, 4), ("data", "model"))
        shd.set_mesh_axes(mesh2.axis_names)
        sh2 = shd.shardings_pruned(mesh2, sspecs, state)
        restored, step = mgr.restore(state, shardings=sh2)
        assert step == 3
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        print("ELASTIC_MATCH")
    """)
    assert "ELASTIC_MATCH" in out
