"""Multi-device behaviour via subprocess (8 host devices; unit tests must
keep the default single device, so each case runs in its own process)."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow    # each case compiles in a subprocess (>1 min)

REPO = Path(__file__).resolve().parent.parent


def run_sub(code: str, timeout=420) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"       # a child never reaches for the chip
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_train_step_runs_and_shards():
    out = run_sub("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config, TrainConfig
        from repro.launch import steps
        from repro.launch.mesh import small_test_mesh
        from repro.models.model import build_model

        cfg = get_smoke_config("internlm2-1.8b")
        mesh = small_test_mesh(data=2, model=4)
        model = build_model(cfg, remat=False)
        specs = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
        axes = {"tokens": ("batch", None)}
        with jax.set_mesh(mesh):
            jfn, (p_sh, o_sh, b_sh), opt = steps.make_train_step(
                model, mesh, TrainConfig(microbatches=2), specs, axes)
            params = jax.jit(model.init_params, out_shardings=p_sh)(
                jax.random.PRNGKey(0))
            opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
            batch = jax.device_put({"tokens": jnp.zeros((8, 16), jnp.int32)},
                                   b_sh)
            p2, o2, m = jfn(params, opt_state, batch)
            l1 = float(m["loss"])
            p3, o3, m2 = jfn(p2, o2, batch)
        import numpy as np
        wq = p2["blocks"]["u0"]["attn"]["wq"]
        nshards = len(set(d.id for d in wq.sharding.device_set))
        print(json.dumps({"loss1": l1, "loss2": float(m2["loss"]),
                          "sharded": nshards > 1}))
    """)
    assert out["sharded"]
    assert out["loss2"] < out["loss1"] + 1.0


def test_pipeline_matches_sequential():
    out = run_sub("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_pipeline_mesh
        from repro.parallel.pipeline import PipelineRunner
        cfg = get_smoke_config("internlm2-1.8b").scaled(n_layers=6)
        mesh = make_pipeline_mesh(n_stages=4, data=2, model=1)
        runner = PipelineRunner(cfg, mesh, [[0,1],[2],[3,4],[5]], n_micro=4,
                                remat=False)
        params = runner.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        with jax.set_mesh(mesh):
            y_pipe = jax.jit(runner.forward)(params, x)
        y_seq = runner.sequential_forward(params, x)
        err = float(jnp.max(jnp.abs(y_pipe.astype(jnp.float32)
                                    - y_seq.astype(jnp.float32))))
        print(json.dumps({"err": err}))
    """)
    assert out["err"] < 1e-3


def test_checkpoint_reshard_elastic():
    """Save on a (2,4) mesh, restore onto (4,2) — elastic restart."""
    out = run_sub("""
        import json, tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import small_test_mesh

        tree = {"w": jnp.arange(64*64, dtype=jnp.float32).reshape(64, 64)}
        m1 = small_test_mesh(data=2, model=4)
        sh1 = {"w": NamedSharding(m1, P("data", "model"))}
        t1 = jax.device_put(tree["w"], sh1["w"])
        with tempfile.TemporaryDirectory() as d:
            mgr = CheckpointManager(d)
            mgr.save(1, {"w": t1})
            m2 = small_test_mesh(data=4, model=2)
            sh2 = {"w": NamedSharding(m2, P("data", "model"))}
            restored = mgr.restore(1, tree, sh2)
            same = bool(jnp.all(restored["w"] == tree["w"]))
            resharded = restored["w"].sharding.is_equivalent_to(sh2["w"], 2)
        print(json.dumps({"same": same, "resharded": bool(resharded)}))
    """)
    assert out["same"] and out["resharded"]


def test_compressed_psum_matches_mean():
    out = run_sub("""
        import json, functools, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim import compressed_psum
        from repro.launch.mesh import small_test_mesh
        mesh = small_test_mesh(data=8, model=1)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 64)),
                        jnp.float32)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=P("data"), out_specs=P("data"),
                           check_vma=False)
        def f(xs):
            mean, err = compressed_psum({"g": xs}, "data")
            return mean["g"]

        with jax.set_mesh(mesh):
            got = f(x)
        want = jnp.broadcast_to(x.mean(axis=0, keepdims=True), x.shape)
        rel = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-9))
        print(json.dumps({"rel_err": rel}))
    """)
    assert out["rel_err"] < 0.02   # int8 quantization error bound


def test_dryrun_entry_single_cell(tmp_path):
    """The dry-run CLI itself works end-to-end for one small cell."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"       # a child never reaches for the chip
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "internlm2-1.8b", "--shape", "decode_32k", "--mesh", "single",
         "--outdir", str(tmp_path)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rec = json.loads(
        (tmp_path / "internlm2-1.8b__decode_32k__single.json").read_text())
    assert rec["status"] == "ok"
    assert rec["roofline"]["dominant"] in ("compute", "memory", "collective")
