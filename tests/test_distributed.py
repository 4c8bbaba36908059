"""Distribution tests that need >1 device: run in subprocesses with a
forced host-platform device count (keeps the main test process at 1
device)."""

import json

import pytest

from _mesh_helpers import run_in_forced_mesh as run_sub


def test_sharded_train_step_matches_single_device():
    """Same tiny model, same data: loss on a 2x4 mesh == 1-device loss."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models.zoo import get_model
        from repro.models import sharding as SH
        from repro.launch.train import make_train_step, init_train_state

        cfg = get_config("starcoder2-3b").reduced(
            n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=256)
        import dataclasses
        cfg = dataclasses.replace(cfg, microbatch=2)
        bundle = get_model(cfg)
        rng = jax.random.PRNGKey(0)
        params, opt = init_train_state(bundle, rng)
        batch = {"tokens": jax.random.randint(rng, (8, 32), 0, 256)}
        step = make_train_step(bundle)

        # single device reference
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        # sharded
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        axes = SH.mesh_axes_of(mesh)
        shard = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, P))
        p_sh = shard(SH.param_specs(params, axes, False))
        b_sh = shard({"tokens": SH.batch_spec((8, 32), axes)})
        params_s = jax.device_put(params, p_sh)
        batch_s = jax.device_put(batch, b_sh)
        opt_s = jax.device_put(opt, jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P()), opt))
        p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, None, b_sh))(
            params_s, opt_s, batch_s)
        print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"])}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert abs(r["l1"] - r["l2"]) < 5e-3, r


def test_spmd_pipeline_matches_sequential():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.launch.mesh import auto_mesh
        from repro.runtime.pipeline import spmd_pipeline

        mesh = auto_mesh((4,), ("stage",))
        n_stages, n_mb, mb, d = 4, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), n_stages)
        stage_params = {"w": jax.vmap(
            lambda k: jax.random.normal(k, (d, d)) / np.sqrt(d))(ks)}

        def fn(p, x):
            return jnp.tanh(x @ p["w"])

        x = jax.random.normal(jax.random.PRNGKey(1), (n_mb, mb, d))
        # sequential reference
        ref = x
        for s in range(n_stages):
            ref = fn({"w": stage_params["w"][s]}, ref)
        got = spmd_pipeline(fn, stage_params, x, mesh=mesh,
                            axis_name="stage", n_microbatches=n_mb)
        err = float(jnp.max(jnp.abs(got - ref)))
        print(json.dumps({"err": err}))
    """, devices=4)
    assert json.loads(out.strip().splitlines()[-1])["err"] < 1e-5


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Save under a (2,2) mesh, restore under (4,1) — elastic rescale."""
    out = run_sub(f"""
        import jax, jax.numpy as jnp, numpy as np, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import Checkpointer
        from repro.launch.mesh import auto_mesh

        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        m1 = auto_mesh((2, 2), ("data", "model"))
        t1 = jax.device_put(tree, NamedSharding(m1, P("data", "model")))
        ck = Checkpointer({str(tmp_path)!r})
        ck.save(3, t1, blocking=True)

        m2 = auto_mesh((4, 1), ("data", "model"))
        sh = {{"w": NamedSharding(m2, P("data", None))}}
        step, back = ck.restore(like=tree, shardings=sh)
        ok = bool(np.array_equal(np.asarray(back["w"]),
                                 np.asarray(tree["w"])))
        print(json.dumps({{"step": step, "ok": ok,
            "shards": len(back["w"].sharding.device_set)}}))
    """, devices=4)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["ok"] and r["step"] == 3 and r["shards"] == 4


def test_compressed_allreduce_shardmap():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np, json, functools
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import auto_mesh
        from repro.optim.compression import (allreduce_compressed,
                                             compress_int8)

        mesh = auto_mesh((4,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 128)) * 1e-3

        def body(xs):
            q, s = compress_int8(xs[0])
            return allreduce_compressed(q, s, "pod")[None]

        got = jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                            out_specs=P("pod"), check_vma=False)(x)
        ref = jnp.mean(x, axis=0)
        rel = float(jnp.max(jnp.abs(got[0] - ref)) /
                    (jnp.max(jnp.abs(ref)) + 1e-12))
        print(json.dumps({"rel": rel}))
    """, devices=4)
    assert json.loads(out.strip().splitlines()[-1])["rel"] < 0.1


def test_dryrun_tiny_cell():
    """End-to-end dryrun machinery on a reduced arch x tiny mesh."""
    out = run_sub("""
        import jax, json, dataclasses
        import repro.configs as C
        from repro.configs import get_config
        from repro.launch import dryrun as DR

        # shrink the production mesh for the test
        import repro.launch.mesh as M
        M.make_production_mesh = lambda multi_pod=False: (
            M.auto_mesh((2, 2, 2), ("pod", "data", "model"))
            if multi_pod else M.auto_mesh((2, 2), ("data", "model")))
        cfg = get_config("gemma2-2b").reduced(
            n_layers=2, d_model=64, n_heads=4, d_ff=128, vocab=512)
        cfg = dataclasses.replace(cfg, dtype="bfloat16", microbatch=2,
                                  remat=True)
        C._REGISTRY["gemma2-2b"] = cfg
        C.SHAPES = C.SHAPES  # unchanged; use train_4k semantics w/ small S
        from repro.configs.base import ShapeConfig
        DR.SHAPES["tiny_train"] = ShapeConfig("tiny_train", 64, 8, "train")
        DR.SHAPES["tiny_decode"] = ShapeConfig("tiny_decode", 64, 8,
                                               "decode")
        recs = []
        for shape in ("tiny_train", "tiny_decode"):
            for mp in (False, True):
                r = DR.lower_cell("gemma2-2b", shape, mp)
                recs.append((shape, r["mesh"],
                             r["loop_aware"]["flops"] > 0))
        print(json.dumps(recs))
    """, devices=8)
    recs = json.loads(out.strip().splitlines()[-1])
    assert len(recs) == 4 and all(r[2] for r in recs), recs
