"""Distribution tests that need multiple devices: run in a subprocess with
--xla_force_host_platform_device_count (device count locks at jax init, so
the main pytest process must keep seeing 1 CPU device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(n_devices: int, body: str) -> dict:
    """Execute `body` in a fresh python with n fake devices; body must print
    a single json object on its last line.

    The prelude imports `make_mesh` (a mesh with Auto axes) from
    repro.launch.mesh.
    """
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import json
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_walk_agrees_with_replicated():
    res = _run(4, """
        from repro.graphs.synthetic import small_test_graph, top_degree_pins
        from repro.core import distributed as D, walk as W
        sg = small_test_graph()
        mesh = make_mesh((2, 2), ("data", "model"))
        shg = D.shard_graph(sg.graph, 2)
        qs = top_degree_pins(sg, 2)
        qp = jnp.asarray([int(qs[0]), int(qs[1]), -1, -1], jnp.int32)
        qw = jnp.asarray([1.0, 0.7, 0.0, 0.0], jnp.float32)
        cfg = D.ShardedWalkConfig(n_supersteps=64, walkers_per_shard=128,
                                  top_k=20)
        with jax.set_mesh(mesh):
            res = D.pixie_walk_sharded(shg, qp, qw, jax.random.key(0), cfg,
                                       mesh)
        wcfg = W.WalkConfig(n_steps=30000, n_walkers=256, bias_beta=0.0,
                            top_k=20, n_p=10**9, n_v=10**9)
        _, ids = W.recommend(sg.graph, qp, qw, jnp.asarray(0, jnp.int32),
                             jax.random.key(1), wcfg)
        ov = len(set(np.asarray(res.top_pins).tolist())
                 & set(np.asarray(ids).tolist()))
        print(json.dumps({"overlap": ov, "dropped": int(res.dropped)}))
    """)
    assert res["overlap"] >= 10, res  # statistical agreement of top-20


def test_sharded_embedding_lookup_matches_replicated():
    res = _run(4, """
        from repro.models import embedding as E
        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = E.MegaTableConfig(feature_rows=(40, 24), dim=8,
                                pad_to_multiple=8)
        table = jax.random.normal(jax.random.key(0),
                                  (cfg.total_rows, cfg.dim))
        ids = jnp.stack([
            jax.random.randint(jax.random.key(1), (16,), 0, 40),
            jax.random.randint(jax.random.key(2), (16,), 0, 24),
        ], axis=1)
        want = E.lookup(table, ids, cfg)
        with jax.set_mesh(mesh):
            got = E.lookup_sharded(table, ids, cfg, mesh)
        err = float(jnp.abs(want - got).max())
        print(json.dumps({"max_err": err}))
    """)
    assert res["max_err"] < 1e-5


def test_checkpoint_reshards_onto_different_mesh():
    """Elastic restart: save on a (4,)-mesh sharded layout, restore onto a
    (2,)-mesh — the checkpoint is topology-agnostic."""
    body_save = """
        import tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training import checkpoint
        mesh = make_mesh((%d,), ("model",))
        x = jnp.arange(32.0).reshape(8, 4)
        sharded = jax.device_put(x, NamedSharding(mesh, P("model", None)))
        checkpoint.save("%s", 3, {"x": sharded})
        restored, step = checkpoint.restore(
            "%s", {"x": jnp.zeros((8, 4))},
            shardings={"x": NamedSharding(mesh, P("model", None))},
        )
        ok = bool(jnp.allclose(restored["x"], x))
        print(json.dumps({"ok": ok, "step": step}))
    """
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        res4 = _run(4, body_save % (4, d, d))
        assert res4["ok"]
        # restore the same checkpoint in a 2-device world
        res2 = _run(2, """
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.training import checkpoint
            mesh = make_mesh((2,), ("model",))
            restored, step = checkpoint.restore(
                "%s", {"x": jnp.zeros((8, 4))},
                shardings={"x": NamedSharding(mesh, P("model", None))},
            )
            want = jnp.arange(32.0).reshape(8, 4)
            ok = bool(jnp.allclose(restored["x"], want))
            n_shards = len(restored["x"].sharding.device_set)
            print(json.dumps({"ok": ok, "step": step,
                              "n_shards": n_shards}))
        """ % d)
        assert res2["ok"] and res2["step"] == 3 and res2["n_shards"] == 2


def test_compressed_psum_averages_across_shards():
    res = _run(4, """
        from jax.sharding import PartitionSpec as P
        from repro.training import compression
        mesh = make_mesh((4,), ("data",))
        # per-shard gradients 0,1,2,3 -> mean 1.5
        g = jnp.repeat(jnp.arange(4.0)[:, None], 8, axis=1)
        r = jnp.zeros_like(g)
        def f(gg, rr):
            out, nr = compression.compressed_psum(
                {"w": gg[0]}, {"w": rr[0]}, "data")
            return out["w"][None], nr["w"][None]
        with jax.set_mesh(mesh):
            out, _ = jax.shard_map(f, mesh=mesh,
                               in_specs=(P("data", None), P("data", None)),
                               out_specs=(P("data", None), P("data", None)),
                               check_vma=False)(g, r)
        err = float(jnp.abs(out - 1.5).max())
        print(json.dumps({"max_err": err}))
    """)
    assert res["max_err"] < 0.02  # within int8 quantization noise
