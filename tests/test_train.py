"""Training substrate: optimizers, compression, checkpointing, loop envelope."""

import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.tokens import TokenPipeline
from repro.train import (
    Checkpointer,
    LoopConfig,
    TrainHParams,
    init_state,
    make_train_step,
    run_loop,
)
from repro.train import compression as comp
from repro.train import optim
from repro.train.checkpoint import latest_step, restore, save

KEY = jax.random.key(0)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def quad_loss(p):
    return jnp.sum((p["w"] - 3.0) ** 2) + jnp.sum((p["b"] + 1.0) ** 2)


@pytest.mark.parametrize("name", ["sgd", "adamw", "adafactor"])
def test_optimizers_descend_quadratic(name):
    params = {"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))}
    opt = optim.make_optimizer(
        name, lambda s: jnp.asarray(0.1), weight_decay=0.0
    )
    state = opt.init(params)
    for step in range(200):
        g = jax.grad(quad_loss)(params)
        upd, state = opt.update(g, state, params, jnp.asarray(step))
        params = jax.tree.map(lambda p, u: p + u, params, upd)
    assert float(quad_loss(params)) < 0.1 * float(
        quad_loss({"w": jnp.zeros((4, 8)), "b": jnp.zeros((8,))})
    )


def test_adafactor_state_is_factored():
    params = {"w": jnp.zeros((64, 128)), "v": jnp.zeros((64,))}
    opt = optim.adafactor(lambda s: 0.01)
    st = opt.init(params)
    assert set(st["v"]["w"]) == {"vr", "vc"}
    assert st["v"]["w"]["vr"].shape == (64,)
    assert st["v"]["w"]["vc"].shape == (128,)
    assert set(st["v"]["v"]) == {"v"}  # vectors stay unfactored


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = optim.clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    n2 = optim.global_norm(clipped)
    np.testing.assert_allclose(float(n2), 1.0, rtol=1e-5)


def test_warmup_cosine_shape():
    sched = optim.warmup_cosine(1e-3, 1000, warmup_steps=100)
    assert float(sched(jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(sched(jnp.asarray(100))), 1e-3, rtol=1e-5)
    assert float(sched(jnp.asarray(1000))) < 2e-4


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_bounds():
    x = jax.random.normal(KEY, (1000,)) * 5
    q, scale = comp.quantize_int8(x)
    err = jnp.abs(comp.dequantize_int8(q, scale) - x)
    assert float(jnp.max(err)) <= float(scale) * 0.5 + 1e-6


def test_error_feedback_telescopes():
    """Mean compressed update over many steps converges to the true mean
    gradient — the error-feedback guarantee."""
    g = jax.random.normal(KEY, (256,))
    err = {"g": jnp.zeros((256,))}
    total = jnp.zeros((256,))
    n = 200
    for _ in range(n):
        out, err = comp.compress_decompress({"g": g}, err)
        total = total + out["g"]
    np.testing.assert_allclose(
        np.asarray(total / n), np.asarray(g), atol=1e-3
    )


def test_compressed_psum_matches_mean():
    """shard_map wiring on a 1-device mesh: psum of int8 == plain mean."""
    mesh = jax.make_mesh((1,), ("data",))
    g = {"w": jax.random.normal(KEY, (8, 8))}
    e = comp.init_error_state(g)

    from functools import partial
    from jax.sharding import PartitionSpec as P

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
    )
    def fn(gs, es):
        return comp.compressed_psum(gs, es, ("data",))

    out, err = fn(g, e)
    np.testing.assert_allclose(
        np.asarray(out["w"]), np.asarray(g["w"]), atol=0.05
    )
    # feedback + dequantized output reconstruct the input exactly
    np.testing.assert_allclose(
        np.asarray(out["w"] + err["w"]), np.asarray(g["w"]), atol=1e-6
    )


def test_training_with_compression_converges():
    cfg = get_config("smollm-135m", smoke=True)
    hp = TrainHParams(
        peak_lr=1e-3, total_steps=20, warmup_steps=1, compress_grads=True
    )
    state = init_state(KEY, cfg, hp)
    assert state.err is not None
    step = jax.jit(make_train_step(cfg, hp))
    pipe = TokenPipeline(cfg.vocab, 32, 4, seed=0)
    losses = []
    for i in range(10):
        state, m = step(state, pipe.batch_at(i % 2))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def _tiny_state():
    cfg = get_config("smollm-135m", smoke=True)
    hp = TrainHParams(total_steps=10)
    return cfg, hp, init_state(KEY, cfg, hp)


def test_checkpoint_roundtrip_exact():
    cfg, hp, state = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        save(d, 3, state)
        assert latest_step(d) == 3
        restored, step = restore(d, state)
        assert step == 3
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_crash_midwrite():
    """A stale tmp dir (simulated crash) must not shadow the good ckpt."""
    cfg, hp, state = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, state)
        os.makedirs(os.path.join(d, ".tmp-step_000002"))  # crashed save
        assert latest_step(d) == 1
        restored, step = restore(d, state)
        assert step == 1


def test_checkpoint_retention_gc():
    cfg, hp, state = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=2)
        for s in (1, 2, 3, 4):
            ck.save_sync(s, state)
        names = sorted(n for n in os.listdir(d) if n.startswith("step_"))
        assert names == ["step_000003", "step_000004"]


def test_checkpoint_async_overlap_and_wait():
    cfg, hp, state = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, keep=3)
        ck.save_async(5, state)
        ck.wait()
        assert latest_step(d) == 5


def test_restore_shape_mismatch_raises():
    cfg, hp, state = _tiny_state()
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, state)
        bad = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((x.shape or (1,))[:1] + (99,),
                                           x.dtype)
            if hasattr(x, "shape") and len(x.shape) >= 1 else x,
            state,
        )
        with pytest.raises((ValueError, KeyError)):
            restore(d, bad)


# ---------------------------------------------------------------------------
# loop: watchdog, NaN guard, resume
# ---------------------------------------------------------------------------

def test_loop_resume_continues_from_checkpoint():
    cfg, hp, state = _tiny_state()
    step = jax.jit(make_train_step(cfg, hp))
    pipe = TokenPipeline(cfg.vocab, 32, 4, seed=0)
    with tempfile.TemporaryDirectory() as d:
        lc = LoopConfig(total_steps=4, checkpoint_dir=d,
                        checkpoint_every=2, log_every=100)
        run_loop(state, step, pipe.batches(), lc, log=lambda s: None)
        lc2 = LoopConfig(total_steps=8, checkpoint_dir=d,
                         checkpoint_every=2, log_every=100)
        r = run_loop(init_state(KEY, cfg, hp), step, pipe.batches(), lc2,
                     log=lambda s: None)
        assert r.resumed_from == 4
        assert int(r.state.step) == 8


def test_loop_watchdog_flags_straggler():
    cfg, hp, state = _tiny_state()
    inner = jax.jit(make_train_step(cfg, hp))
    # warm the jit cache so the first loop step isn't compile-dominated
    # (a cold first step would seed the EMA with seconds, hiding the
    # synthetic straggler)
    pipe_warm = TokenPipeline(cfg.vocab, 32, 4, seed=0)
    inner(state, pipe_warm.batch_at(0))
    calls = {"n": 0}

    def slow_step(st, b):
        calls["n"] += 1
        if calls["n"] == 9:
            time.sleep(1.0)  # synthetic straggler step
        return inner(st, b)

    pipe = TokenPipeline(cfg.vocab, 32, 4, seed=0)
    lc = LoopConfig(total_steps=10, log_every=100, watchdog_factor=3.0,
                    watchdog_warmup=3)
    r = run_loop(state, slow_step, pipe.batches(), lc, log=lambda s: None)
    assert r.straggler_steps >= 1


def test_loop_nan_guard_saves_postmortem():
    cfg, hp, state = _tiny_state()

    def nan_step(st, b):
        from repro.train.train_step import TrainState
        return TrainState(st.params, st.opt_state, st.step + 1, st.err), {
            "loss": jnp.asarray(float("nan"))
        }

    pipe = TokenPipeline(cfg.vocab, 32, 4, seed=0)
    with tempfile.TemporaryDirectory() as d:
        lc = LoopConfig(total_steps=5, checkpoint_dir=d, log_every=100)
        with pytest.raises(FloatingPointError):
            run_loop(state, nan_step, pipe.batches(), lc, log=lambda s: None)
        assert latest_step(d) is not None
