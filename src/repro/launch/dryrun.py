import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x mesh)
cell on the production mesh, record memory/cost/collective analysis.

The two env lines above MUST run before any jax-importing module: jax locks the
device count at first init, and only the dry-run may see 512 placeholder
devices (smoke tests and benches see the real single device).

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k [--multipod]
  python -m repro.launch.dryrun --all --out-dir results/dryrun   # subprocess/cell
"""

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

import repro  # noqa: F401,E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from repro.configs import cells  # noqa: E402
from repro.configs.triangle_stream import SHAPES as STREAM_SHAPES  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.roofline.flops import cell_analytic_flops  # noqa: E402
from repro.roofline.hlo import collective_stats  # noqa: E402


def _shard(mesh, spec_tree, args_tree):
    is_p = lambda x: isinstance(x, P)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree, is_leaf=is_p
    )


def _analyze(compiled, chips, model_flops, seconds):
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    txt = compiled.as_text()
    coll = collective_stats(txt)
    return {
        "chips": chips,
        "seconds_to_compile": seconds,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        },
        "cost": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "model_flops": model_flops,
        "hlo_size": len(txt),
    }


def run_model_cell(arch: str, shape: str, multi_pod: bool, overrides=None) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = cells.build_cell(arch, shape, tuple(mesh.axis_names), overrides=overrides)
    in_sh = _shard(mesh, cell.in_specs, cell.args)
    out_sh = None if cell.out_specs is None else _shard(mesh, cell.out_specs, None)
    t0 = time.time()
    jf = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh)
    with jax.set_mesh(mesh):  # ambient mesh for with_sharding_constraint(P)
        lowered = jf.lower(*cell.args)
    compiled = lowered.compile()
    rec = _analyze(compiled, mesh.size, cell.model_flops, time.time() - t0)
    fa = cell_analytic_flops(cell)
    rec["cost"]["flops_analytic_total"] = fa  # None -> trust HLO flops
    rec |= {"arch": arch, "shape": shape, "mesh": "multipod" if multi_pod else "pod"}
    print(compiled.memory_analysis())
    print({k: v for k, v in (compiled.cost_analysis() or {}).items()
           if k in ("flops", "bytes accessed")})
    return rec


def run_stream_cell(shape: str, multi_pod: bool, capacity_factor=2.0) -> dict:
    import jax.numpy as jnp

    from repro.core.distributed import make_coordinated_update, make_pjit_update
    from repro.core.state import EstimatorState

    mesh = make_production_mesh(multi_pod=multi_pod)
    spec = STREAM_SHAPES[shape]
    r, s = spec["r"], spec["s"]
    sds = jax.ShapeDtypeStruct
    state = EstimatorState(
        f1=sds((r, 2), jnp.int32),
        chi=sds((r,), jnp.int32),
        f2=sds((r, 2), jnp.int32),
        has_f3=sds((r,), bool),
        m_seen=sds((), jnp.int64),
    )
    W = sds((s, 2), jnp.int32)
    nv = sds((), jnp.int32)
    key = sds((2,), jnp.uint32)
    t0 = time.time()
    if spec["w_mode"] == "shardmap":
        jf = make_coordinated_update(mesh, r=r, s=s, capacity_factor=capacity_factor)
    else:
        jf = make_pjit_update(mesh, w_mode=spec["w_mode"])
    lowered = jf.lower(state, W, nv, key)
    compiled = lowered.compile()
    # useful work floor: one pass of comparisons for sort(2s) + r estimator updates
    import math

    model_flops = 2 * s * max(math.log2(max(s, 2)), 1) + 4 * r
    rec = _analyze(compiled, mesh.size, model_flops, time.time() - t0)
    rec |= {
        "arch": "triangle-stream",
        "shape": shape,
        "mesh": "multipod" if multi_pod else "pod",
    }
    print(compiled.memory_analysis())
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (hillclimb experiments)")
    args = ap.parse_args()
    out_dir = pathlib.Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        todo = [(a, s) for a, s in cells.all_cells()]
        todo += [("triangle-stream", s) for s in STREAM_SHAPES]
        failures = []
        for arch, shape in todo:
            for mp in (False, True):
                tag = f"{arch}__{shape}__{'multipod' if mp else 'pod'}"
                out = out_dir / f"{tag}.json"
                if out.exists() and json.loads(out.read_text()).get("ok"):
                    print(f"[skip] {tag}")
                    continue
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--out-dir", str(out_dir),
                ] + (["--multipod"] if mp else [])
                print(f"[run ] {tag}", flush=True)
                t0 = time.time()
                pr = subprocess.run(cmd, capture_output=True, text=True,
                                    timeout=args.timeout)
                if pr.returncode != 0:
                    failures.append(tag)
                    out.write_text(json.dumps({
                        "arch": arch, "shape": shape,
                        "mesh": "multipod" if mp else "pod", "ok": False,
                        "error": pr.stderr[-4000:],
                    }, indent=1))
                    print(f"[FAIL] {tag}: {pr.stderr[-400:]}", flush=True)
                else:
                    print(f"[ ok ] {tag} ({time.time()-t0:.0f}s)", flush=True)
        print(f"DONE failures={len(failures)}: {failures}")
        sys.exit(1 if failures else 0)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = json.loads(v)
    tag = f"{args.arch}__{args.shape}__{'multipod' if args.multipod else 'pod'}"
    if overrides:
        tag += "__" + "_".join(f"{k}-{v}" for k, v in overrides.items())
    try:
        if args.arch == "triangle-stream":
            rec = run_stream_cell(
                args.shape, args.multipod,
                capacity_factor=overrides.get("capacity_factor", 2.0),
            )
        else:
            rec = run_model_cell(
                args.arch, args.shape, args.multipod, overrides or None
            )
        rec["ok"] = True
        rec["overrides"] = overrides
    except Exception:
        traceback.print_exc()
        rec = {
            "arch": args.arch, "shape": args.shape,
            "mesh": "multipod" if args.multipod else "pod",
            "ok": False, "error": traceback.format_exc()[-4000:],
        }
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    print(json.dumps({k: rec[k] for k in ("arch", "shape", "mesh", "ok")}))
    sys.exit(0 if rec["ok"] else 1)


if __name__ == "__main__":
    main()
