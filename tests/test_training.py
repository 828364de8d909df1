"""Training substrate: loss decreases, grad accumulation equivalence,
compression, checkpoint/restart + elastic resharding."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from repro.configs import get_reduced_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models.model import build_model
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import TrainConfig, make_train_step, train


def test_loss_decreases(tmp_path):
    cfg = get_reduced_config("tinyllama-1.1b")
    out = train(cfg,
                TrainConfig(steps=30, ckpt_dir=str(tmp_path / "ck"),
                            ckpt_every=10,
                            opt=AdamWConfig(lr=3e-3, weight_decay=0.0)),
                DataConfig(vocab_size=cfg.vocab_size, global_batch=8,
                           seq_len=32))
    first = np.mean(out["losses"][:5])
    last = np.mean(out["losses"][-5:])
    assert last < first - 0.3, (first, last)


def test_resume_from_checkpoint(tmp_path):
    cfg = get_reduced_config("tinyllama-1.1b")
    dc = DataConfig(vocab_size=cfg.vocab_size, global_batch=4, seq_len=16)
    tc = TrainConfig(steps=6, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3)
    out1 = train(cfg, tc, dc)
    # restart "after failure": resumes at step 6 checkpoint, runs 4 more
    tc2 = TrainConfig(steps=10, ckpt_dir=str(tmp_path / "ck"), ckpt_every=5)
    out2 = train(cfg, tc2, dc, resume=True)
    assert int(out2["opt_state"]["step"]) == 10
    assert len(out2["losses"]) == 4  # only the resumed steps ran


def test_grad_accumulation_matches_full_batch():
    cfg = get_reduced_config("tinyllama-1.1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.training.optimizer import adamw_init

    opt = adamw_init(params)
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    global_batch=8, seq_len=16))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    s1 = jax.jit(make_train_step(model, TrainConfig(microbatches=1)))
    s4 = jax.jit(make_train_step(model, TrainConfig(microbatches=4)))
    p1, _, m1 = s1(params, opt, batch)
    p4, _, m4 = s4(params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=5e-2)
    d = max(float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    assert d < 5e-2  # update direction preserved (microbatch CE re-weighting)


def test_grad_compression_runs_and_stays_close():
    cfg = get_reduced_config("tinyllama-1.1b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.training.optimizer import adamw_init

    opt = adamw_init(params)
    pipe = TokenPipeline(DataConfig(vocab_size=cfg.vocab_size,
                                    global_batch=4, seq_len=16))
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    outs = {}
    for how in ("none", "bf16", "int8"):
        fn = jax.jit(make_train_step(model, TrainConfig(grad_compress=how)))
        p, _, m = fn(params, opt, batch)
        outs[how] = (p, float(m["loss"]))
    # compressed updates deviate but stay bounded
    for how in ("bf16", "int8"):
        d = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree.leaves(outs["none"][0]), jax.tree.leaves(outs[how][0])))
        assert d < 1e-2, how


def test_checkpoint_elastic_reshard(tmp_path):
    """Save on one 'mesh', restore with different shardings (elastic)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import Checkpointer

    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            "b": jnp.ones((8,), jnp.float32)}
    ck = Checkpointer(str(tmp_path / "el"))
    ck.save(1, tree, blocking=True)
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    shardings = {"w": NamedSharding(mesh, P("model", None)),
                 "b": NamedSharding(mesh, P(None))}
    restored = ck.restore(tree, shardings=shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


def test_pipeline_determinism_and_sharding():
    dc = DataConfig(vocab_size=100, global_batch=8, seq_len=16, seed=3)
    a = TokenPipeline(dc).batch_at(7)
    b = TokenPipeline(dc).batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # host-sharded feeding covers the global batch disjointly
    h0 = TokenPipeline(dc, host_index=0, host_count=2).batch_at(7)
    h1 = TokenPipeline(dc, host_index=1, host_count=2).batch_at(7)
    assert h0["tokens"].shape == (4, 16)
    assert not np.array_equal(h0["tokens"], h1["tokens"])
