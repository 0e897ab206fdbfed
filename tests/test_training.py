"""Training substrate tests: optimizer, microbatching, checkpointing,
resilience (failure injection, bit-exact replay), compression."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import (
    checkpoint,
    compression,
    microbatch,
    optim,
    resilience,
)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_on_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = optim.init(params)
    cfg = optim.AdamWConfig(
        lr=0.3, weight_decay=0.0, warmup_steps=1, total_steps=200,
        schedule="constant",
    )
    for _ in range(150):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state, _ = optim.apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_grad_clip_limits_norm():
    g = {"a": jnp.full((10,), 100.0)}
    clipped, norm = optim.clip_by_global_norm(g, 1.0)
    assert float(optim.global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)
    assert float(norm) == pytest.approx(np.sqrt(10 * 100.0 ** 2), rel=1e-5)


def test_schedule_warmup_and_decay():
    cfg = optim.AdamWConfig(
        lr=1.0, warmup_steps=10, total_steps=100, schedule="cosine",
        min_lr_frac=0.1,
    )
    lr5 = float(optim.schedule_lr(cfg, jnp.asarray(5)))
    lr10 = float(optim.schedule_lr(cfg, jnp.asarray(10)))
    lr100 = float(optim.schedule_lr(cfg, jnp.asarray(100)))
    assert lr5 == pytest.approx(0.5, rel=1e-3)
    assert lr10 == pytest.approx(1.0, rel=1e-3)
    assert lr100 == pytest.approx(0.1, rel=1e-2)


def test_microbatch_grads_match_full_batch():
    params = {"w": jnp.arange(4.0)}
    batch = {"x": jnp.arange(8.0).reshape(8, 1)}

    def loss_fn(p, b):
        return jnp.mean((b["x"][:, 0] - jnp.sum(p["w"])) ** 2)

    l1, g1 = microbatch.accumulated_grads(loss_fn, params, batch, 1)
    l4, g4 = microbatch.accumulated_grads(loss_fn, params, batch, 4)
    assert float(l1) == pytest.approx(float(l4), rel=1e-6)
    np.testing.assert_allclose(
        np.asarray(g1["w"]), np.asarray(g4["w"]), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def _tree(seed=0):
    k = jax.random.key(seed)
    return {
        "a": jax.random.normal(k, (4, 3)),
        "nested": {"b": jnp.arange(5), "c": jnp.asarray(2.5)},
    }


def test_checkpoint_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        tree = _tree()
        checkpoint.save(d, 7, tree)
        restored, step = checkpoint.restore(d, tree)
        assert step == 7
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            tree, restored,
        )


def test_checkpoint_keep_last_and_latest_pointer():
    with tempfile.TemporaryDirectory() as d:
        for s in range(6):
            checkpoint.save(d, s, _tree(s), keep_last=2)
        steps = sorted(
            x for x in os.listdir(d) if x.startswith("step_")
        )
        assert len(steps) == 2
        assert checkpoint.latest_step(d) == 5


def test_checkpoint_structure_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, 0, _tree())
        bad = {"a": jnp.zeros((4, 3)), "nested": {"b": jnp.arange(5)}}
        with pytest.raises(ValueError):
            checkpoint.restore(d, bad)


# ---------------------------------------------------------------------------
# resilience
# ---------------------------------------------------------------------------


def test_resilient_run_replays_bit_exact():
    """After an injected failure, the replayed trajectory must land on the
    same final state as an uninterrupted run (stateless step-indexed data +
    checkpoint restore)."""
    params = {"w": jnp.zeros((3,))}
    cfg = optim.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=50,
                            schedule="constant")

    def step(state, batch):
        p, o = state
        grads = jax.grad(
            lambda q: jnp.mean((batch - jnp.sum(q["w"])) ** 2)
        )(p)
        p, o, m = optim.apply_updates(p, grads, o, cfg)
        return (p, o), m

    def batch_fn(s):
        return jnp.asarray(float(s % 5))

    def run(failures):
        with tempfile.TemporaryDirectory() as d:
            rc = resilience.ResilienceConfig(ckpt_dir=d, ckpt_every=4)
            state = ({"w": jnp.zeros((3,))}, optim.init(params))
            hook = resilience.make_scheduled_failures(failures)
            final, report = resilience.run_resilient(
                step, batch_fn, state, 20, rc, failure_hook=hook
            )
            return final, report

    clean, _ = run({})
    faulty, report = run({6: 1, 13: 2})
    assert report.restores == 3
    np.testing.assert_allclose(
        np.asarray(clean[0]["w"]), np.asarray(faulty[0]["w"]), rtol=1e-6
    )


def test_straggler_hook_fires():
    import time

    calls = []

    def step(state, batch):
        if batch == 15:
            time.sleep(0.25)
        else:
            time.sleep(0.01)
        return state, {"loss": jnp.asarray(0.0)}

    with tempfile.TemporaryDirectory() as d:
        rc = resilience.ResilienceConfig(
            ckpt_dir=d, ckpt_every=100, straggler_factor=5.0
        )
        _, report = resilience.run_resilient(
            step, lambda s: s, {"x": jnp.zeros(())}, 20, rc,
            straggler_hook=lambda s, r: calls.append((s, r)),
        )
    assert report.stragglers, "slow step not flagged"
    assert calls


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------


def test_quantize_dequantize_bounded_error():
    g = jax.random.normal(jax.random.key(0), (1000,))
    q, scale = compression.quantize(g)
    err = np.abs(np.asarray(compression.dequantize(q, scale) - g))
    assert err.max() <= float(scale) / 2 + 1e-7


def test_compressed_psum_error_feedback():
    """Mean over the axis is preserved to within int8 quantization noise,
    and the residual carries the quantization error."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    g = {"w": jax.random.normal(jax.random.key(1), (64,))}
    r = compression.init_residual(g)

    def f(gg, rr):
        return compression.compressed_psum(gg, rr, "data")

    with jax.set_mesh(mesh):
        out, new_r = jax.shard_map(
            f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )(g, r)
    # single-device psum: reduced == dequant(quant(g)); residual = g - that
    np.testing.assert_allclose(
        np.asarray(out["w"] + new_r["w"]), np.asarray(g["w"]),
        rtol=1e-5, atol=1e-6,
    )
    scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
    assert float(jnp.abs(new_r["w"]).max()) <= scale
