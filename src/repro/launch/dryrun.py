import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST precede every other import (jax locks the device
# count at first init).  This module is the multi-pod dry-run entry point:
# it lowers + compiles every (architecture x input-shape) cell against the
# production meshes and records memory/cost/roofline evidence.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
#       --shape train_4k --mesh both
#   PYTHONPATH=src python -m repro.launch.dryrun --all
#
# Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import (ARCH_IDS, SHAPES, TrainConfig, get_config,  # noqa: E402
                       shape_applicable)
from ..models.model import analytic_flops, build_model  # noqa: E402
from ..utils.hlo import analyze_hlo  # noqa: E402
from . import steps  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .roofline import roofline_from_cost  # noqa: E402

# per-arch microbatch counts for the train cells (global batch 256); tuned so
# per-device logits/activations stay inside a v5e HBM budget.
MICROBATCHES = {
    "kimi-k2-1t-a32b": 16,
    "qwen3-moe-235b-a22b": 16,
    "qwen3-32b": 8,
    "qwen3-14b": 8,
    "llava-next-mistral-7b": 8,
    "zamba2-7b": 8,
    "minicpm3-4b": 8,
    "internlm2-1.8b": 4,
    "xlstm-350m": 4,
    "whisper-tiny": 4,
}


def train_config(arch: str) -> TrainConfig:
    return TrainConfig(microbatches=MICROBATCHES.get(arch, 8),
                       master_fp32=False)


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               return_text: bool = False):
    """Lower + compile one cell.  Returns the result record
    (+ optionally the compiled HLO text for the perf probe)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    model = build_model(cfg)
    specs, axes = model.input_specs(shape)
    record = {"arch": arch, "shape": shape_name,
              "mesh": "multi" if multi_pod else "single", "chips": chips}

    # perf_counter, not time.time(): wall-clock steps (NTP slew) can make
    # the reported lower/compile splits negative or skewed, and these flow
    # into checked-in bench artifacts.
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tcfg = train_config(arch)
            jfn, (p_sh, o_sh, b_sh), optimizer = steps.make_train_step(
                model, mesh, tcfg, specs, axes, donate=False)
            p_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            o_shapes = jax.eval_shape(optimizer.init, p_shapes)
            lowered = jfn.lower(p_shapes, o_shapes, specs)
        elif shape.kind == "prefill":
            jfn, (p_sh, b_sh) = steps.make_prefill_step(model, mesh, specs, axes)
            p_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            lowered = jfn.lower(p_shapes, specs)
        else:  # decode
            b = shape.global_batch
            jfn, (p_sh, tok_sh, c_sh) = steps.make_decode_step(
                model, mesh, b, shape.seq_len, donate=False)
            p_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
            c_shapes = jax.eval_shape(lambda: model.init_cache(b, shape.seq_len))
            tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
            klen = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jfn.lower(p_shapes, tok, c_shapes, klen)
        t_lower = time.perf_counter() - t0

        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    record["memory"] = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "peak_estimate_bytes": int(mem.argument_size_in_bytes
                                   + mem.output_size_in_bytes
                                   + mem.temp_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    record["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                          "bytes": float(ca.get("bytes accessed", 0.0))}

    hlo_text = compiled.as_text()
    cost = analyze_hlo(hlo_text)
    mf = analytic_flops(cfg, shape)
    rl = roofline_from_cost(cost, chips, mf)
    record["hlo_cost"] = {
        "flops_per_device": cost.flops,
        "bytes_per_device": cost.bytes_accessed,
        "collective_bytes_per_device": cost.collective_bytes,
        "collective_counts": {k: float(v)
                              for k, v in cost.collective_counts.items()},
        "collective_bytes_by_kind": {
            k: float(v) for k, v in cost.collective_bytes_by_kind.items()},
    }
    record["roofline"] = rl.as_dict()
    record["timing"] = {"lower_s": t_lower, "compile_s": t_compile}
    record["status"] = "ok"
    if return_text:
        return record, hlo_text
    return record


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="arch=all shape=all mesh=both")
    ap.add_argument("--outdir", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch in ("all",) or args.all else [args.arch]
    shapes = list(SHAPES) if args.shape in ("all",) or args.all else [args.shape]
    meshes = ([False, True] if args.mesh == "both" or args.all
              else [args.mesh == "multi"])
    outdir = Path(args.outdir)
    outdir.mkdir(parents=True, exist_ok=True)

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for multi in meshes:
                tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
                path = outdir / f"{tag}.json"
                if args.skip_existing and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[skip-existing] {tag}")
                        continue
                try:
                    rec = lower_cell(arch, shape, multi)
                except Exception as e:  # a failure here is a sharding bug
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "multi" if multi else "single",
                           "status": "failed", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-4000:]}
                path.write_text(json.dumps(rec, indent=1))
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_fail += st == "failed"
                if st == "ok":
                    r = rec["roofline"]
                    print(f"[ok]   {tag:60s} compile={rec['timing']['compile_s']:6.1f}s "
                          f"dom={r['dominant']:10s} mfu_bound={r['mfu_bound']:.3f} "
                          f"mem={rec['memory']['peak_estimate_bytes']/2**30:8.2f}GiB/dev")
                elif st == "skipped":
                    print(f"[skip] {tag:60s} {rec['reason'][:60]}")
                else:
                    print(f"[FAIL] {tag:60s} {rec['error'][:120]}")
    print(f"\nsummary: ok={n_ok} skipped={n_skip} failed={n_fail}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
