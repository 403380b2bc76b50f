"""Substrate tests: sharding rules, optimizer, compression, checkpointing,
data determinism, training loop with restart/straggler handling."""
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.parallel import compression, sharding as shd
from repro.train import optimizer as opt


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_spec_for_dedups_mesh_axes():
    shd.set_mesh_axes(("pod", "data", "model"))
    s = shd.spec_for(("batch", "seq", "embed"),
                     rules={"embed": ("data",)})
    # batch takes (pod, data); embed must not reuse data
    assert s == P(("pod", "data"), None, None)


def test_spec_for_drops_missing_mesh_axes():
    shd.set_mesh_axes(("data", "model"))
    s = shd.spec_for(("batch", "seq"))
    assert s == P("data", None)
    shd.set_mesh_axes(("pod", "data", "model"))


def test_prune_spec_divisibility():
    mesh_shape = {"data": 16, "model": 16}
    # 8 experts can't shard over 16 -> replicated on that dim
    s = shd._prune_spec(P("data", None, "model"), (8, 4096, 14336),
                        mesh_shape)
    assert s == P(None, None, "model")
    # partial tuple shrink: drop trailing axes until divisible
    s2 = shd._prune_spec(P(("data", "model")), (32,), mesh_shape)
    assert s2 == P("data")   # 32 % 256 != 0 -> drop model -> 32 % 16 == 0
    s3 = shd._prune_spec(P(("data", "model")), (7,), mesh_shape)
    assert s3 == P(None)


def test_fsdp_rules_shard_embed_over_data():
    rules = shd.ShardingConfig(fsdp=True).resolved()
    shd.set_mesh_axes(("data", "model"))
    s = shd.spec_for(("embed", "mlp"), rules)
    assert s == P("data", "model")
    shd.set_mesh_axes(("pod", "data", "model"))


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    cfg = opt.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                          total_steps=200)
    params = {"w": jnp.array([5.0, -3.0, 2.0])}
    state = opt.init_state(params, cfg)
    for step in range(150):
        g = {"w": 2 * params["w"]}          # d/dw w^2
        params, state = opt.apply_updates(params, g, state,
                                          jnp.int32(step), cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_int8_second_moment_roundtrip():
    """Log-domain int8: ~0.16 octave resolution over 40 octaves."""
    rng = np.random.default_rng(0)
    # second moments span many orders of magnitude - that's the point
    v = jnp.asarray(rng.gamma(1.0, 1.0, (3, 1000))
                    * 10.0 ** rng.uniform(-9, 0, (3, 1000)), jnp.float32)
    q, s = opt._q8_encode(v)
    assert q.shape == v.shape and q.dtype == jnp.int8
    back = np.asarray(opt._q8_decode(q, s, v.shape))
    rel = np.abs(back - np.asarray(v)) / (np.asarray(v) + 1e-30)
    assert float(np.median(rel)) < 0.06
    # tiny values clamp *up* to the span floor (never to zero): the Adam
    # update m/sqrt(v) can only shrink, which is the safe direction
    tiny = opt._q8_decode(*opt._q8_encode(jnp.full((1, 256), 1e-30,
                                                   jnp.float32)),
                          (1, 256))
    assert float(jnp.min(tiny)) >= 0.0


def test_int8_adamw_tracks_fp32_adamw():
    """Log-quantized v: the int8 trajectory stays close to fp32's."""
    rng = np.random.default_rng(1)
    w0 = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
    cfgs = [opt.AdamWConfig(lr=0.01, weight_decay=0.0, warmup_steps=0,
                            int8_second_moment=b) for b in (False, True)]
    outs = []
    for cfg in cfgs:
        p = {"w": w0}
        s = opt.init_state(p, cfg)
        for step in range(20):
            g = {"w": p["w"] * 0.5 + 0.1}
            p, s = opt.apply_updates(p, g, s, jnp.int32(step), cfg)
        outs.append(p["w"])
    # both moved substantially and in the same direction
    move = float(jnp.linalg.norm(outs[0] - w0))
    diff = float(jnp.linalg.norm(outs[0] - outs[1]))
    assert move > 0.1
    assert diff / move < 0.1, (diff, move)


def test_chunked_update_matches_unchunked():
    """lax.map-chunked big-leaf path == direct path."""
    rng = np.random.default_rng(2)
    cfg = opt.AdamWConfig(lr=0.01, warmup_steps=0)
    p3 = {"w": jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)}
    p2 = {"w": p3["w"].reshape(4 * 8, 16)}
    g3 = {"w": jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)}
    g2 = {"w": g3["w"].reshape(4 * 8, 16)}
    s3, s2 = opt.init_state(p3, cfg), opt.init_state(p2, cfg)
    n3, _ = opt.apply_updates(p3, g3, s3, jnp.int32(0), cfg)
    n2, _ = opt.apply_updates(p2, g2, s2, jnp.int32(0), cfg)
    np.testing.assert_allclose(np.asarray(n3["w"]).reshape(32, 16),
                               np.asarray(n2["w"]), rtol=1e-6)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_compression_error_feedback_converges():
    """EF-int8 mean over an axis: residual shrinks the bias to ~0."""
    mesh = jax.make_mesh((1,), ("pod",))

    g = jnp.asarray(np.random.default_rng(0).normal(size=(4096,)),
                    jnp.float32)
    err = jnp.zeros_like(g)

    @jax.jit
    def step(g, err):
        f = jax.shard_map(
            lambda gg, ee: compression.compress_psum(gg, ee, "pod"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)
        return f(g, err)

    avg, err1 = step(g, err)
    # single participant: avg must be the (quantized) identity; EF makes
    # repeated application exact on average
    rel = float(jnp.linalg.norm(avg - g) / jnp.linalg.norm(g))
    assert rel < 0.02
    avg2, _ = step(g, err1)
    total = np.asarray(avg) + np.asarray(avg2)
    rel2 = float(np.linalg.norm(total - 2 * np.asarray(g))
                 / np.linalg.norm(2 * np.asarray(g)))
    assert rel2 < rel     # error feedback cancels quantization bias


def test_compression_wire_bytes():
    tree = {"a": jnp.zeros((2048,)), "b": jnp.zeros((100,))}
    full = compression.wire_bytes(tree, compressed=False)
    comp = compression.wire_bytes(tree, compressed=True)
    assert full == 4 * 2148
    assert comp < full / 3.5


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"params": {"w": jnp.asarray(rng.normal(size=(8, 8)),
                                        jnp.float32)},
            "step": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    tree = _tree()
    mgr.save(7, tree)
    restored, step = mgr.restore(tree)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(tree["params"]["w"]))


def test_checkpoint_keeps_last_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_last=5)
    mgr.save(1, _tree(1))
    mgr.save(2, _tree(2))
    # corrupt the newest
    d = mgr._step_dir(2)
    shard = [f for f in os.listdir(d) if f.startswith("shard")][0]
    with open(os.path.join(d, shard), "r+b") as f:
        f.seek(-4, 2)
        f.write(b"\x00\x00\x00\x01")
    restored, step = mgr.restore(_tree())
    assert step == 1                     # fell back to the older valid one


def test_checkpoint_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _tree(5), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_step_keyed():
    cfg = DataConfig(vocab=128, global_batch=4, seq_len=32, seed=9)
    d1, d2 = SyntheticLM(cfg), SyntheticLM(cfg)
    b1, b2 = d1.batch_at(5), d2.batch_at(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    b3 = d1.batch_at(6)
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))


def test_data_labels_are_shifted_tokens():
    cfg = DataConfig(vocab=64, global_batch=2, seq_len=16)
    b = SyntheticLM(cfg).batch_at(0)
    np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                  np.asarray(b["tokens"][:, 1:]))
    assert int(b["labels"][0, -1]) == -1          # masked final position


def test_data_has_learnable_structure():
    """A bigram predictor must beat uniform - the stream is not noise."""
    cfg = DataConfig(vocab=32, global_batch=8, seq_len=256, seed=3)
    data = SyntheticLM(cfg)
    toks = np.asarray(data.batch_at(0)["tokens"]).reshape(-1)
    counts = np.ones((32, 32))
    for a, b in zip(toks[:-1], toks[1:]):
        counts[a, b] += 1
    probs = counts / counts.sum(1, keepdims=True)
    toks2 = np.asarray(data.batch_at(1)["tokens"]).reshape(-1)
    ll = np.mean(np.log([probs[a, b] for a, b in zip(toks2[:-1],
                                                     toks2[1:])]))
    assert ll > np.log(1 / 32) + 0.1
