import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder devices.  Do not move
this into conftest/pyproject — smoke tests must see 1 device.

Usage:
  python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
  python -m repro.launch.dryrun --all --subprocess   # isolation per cell

Per cell this prints/records: lower+compile status, memory_analysis,
cost_analysis FLOPs/bytes, per-device collective bytes by op, and the three
roofline terms (launch/hlo_analysis.py).
"""

import argparse
import json
import subprocess
import sys
import time
import traceback


def run_cell(
    arch: str, shape: str, mesh_kind: str, n_micro: int = 4,
    cost_model: bool = True,
) -> dict:
    import jax

    from repro.configs import get_arch
    from repro.launch.cells import build_cell, build_cost_cell, cost_depth
    from repro.launch.hlo_analysis import (
        RooflineTerms, analyze_compiled, collective_bytes,
    )
    from repro.launch.mesh import make_production_mesh

    spec = get_arch(arch)
    cell_spec = next(c for c in spec.shapes if c.name == shape)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = mesh.devices.size

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "n_chips": n_chips, "kind": cell_spec.kind, "status": "start",
    }

    t0 = time.time()
    with jax.set_mesh(mesh):
        # ---- 1. the REAL program: proof-of-compile + memory + schedule ----
        kw = {"n_micro": n_micro} if spec.family == "lm" else {}
        cell = build_cell(spec, cell_spec, mesh, **kw)
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_shardings,
            out_shardings=cell.out_shardings,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        raw = analyze_compiled(compiled, n_chips)
        rec["raw"] = raw.as_dict()
        rec["collectives"] = {
            k: v for k, v in collective_bytes(compiled.as_text()).items()
            if v > 0
        }
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                "argument_size": getattr(ma, "argument_size_in_bytes", None),
                "output_size": getattr(ma, "output_size_in_bytes", None),
                "temp_size": getattr(ma, "temp_size_in_bytes", None),
                "generated_code_size": getattr(
                    ma, "generated_code_size_in_bytes", None
                ),
            }
        except Exception as e:  # CPU backend may not support it
            rec["memory_analysis"] = f"unavailable: {e}"

        # ---- 2. cost model: depth-1/depth-2 loop-free compiles -------------
        depth = cost_depth(spec, cell_spec)
        terms = raw
        if cost_model and depth is not None:
            # extrapolate from depths 2 and 3 (depth 1 sometimes triggers
            # pathological GSPMD layouts that break the linear fit)
            t2 = time.time()
            qs = []
            for k in (2, 3):
                c = build_cost_cell(spec, cell_spec, mesh, k)
                comp = jax.jit(
                    c.fn,
                    in_shardings=c.in_shardings,
                    out_shardings=c.out_shardings,
                    donate_argnums=c.donate,
                ).lower(*c.args).compile()
                qs.append(analyze_compiled(comp, n_chips))
            q1, q2 = qs

            def extrap(a, b):
                return max(a + (depth - 2) * (b - a), 0.0)

            terms = RooflineTerms(
                flops=extrap(q1.flops, q2.flops),
                hbm_bytes=extrap(q1.hbm_bytes, q2.hbm_bytes),
                coll_bytes_per_dev=extrap(
                    q1.coll_bytes_per_dev, q2.coll_bytes_per_dev
                ),
                n_chips=n_chips,
                bytes_per_device=raw.bytes_per_device,
            )
            rec["cost_model"] = {
                "depth": depth,
                "q2_flops": q1.flops, "q3_flops": q2.flops,
                "cost_compile_s": round(time.time() - t2, 1),
            }
        rec.update(terms.as_dict())
        rec["status"] = "ok"
    return rec


def _fmt(rec: dict) -> str:
    if rec["status"] != "ok":
        return f"FAIL {rec['arch']}/{rec['shape']}/{rec['mesh']}: {rec.get('error', '?')}"
    return (
        f"OK {rec['arch']}/{rec['shape']}/{rec['mesh']} "
        f"chips={rec['n_chips']} flops={rec['flops']:.3e} "
        f"hbm={rec['hbm_bytes']:.3e} coll/dev={rec['coll_bytes_per_dev']:.3e} "
        f"tc={rec['t_compute_s']:.2e}s tm={rec['t_memory_s']:.2e}s "
        f"tcoll={rec['t_collective_s']:.2e}s dom={rec['dominant']} "
        f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)"
    )


def all_cells():
    from repro.configs import all_archs, get_arch

    for arch in all_archs():
        spec = get_arch(arch)
        for cell in spec.shapes:
            yield arch, cell.name


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.jsonl")
    ap.add_argument("--subprocess", action="store_true",
                    help="one process per cell (isolation)")
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already recorded OK in --out")
    ap.add_argument("--n-micro", type=int, default=4)
    args = ap.parse_args(argv)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = (
        list(all_cells()) if args.all else [(args.arch, args.shape)]
    )

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") == "ok":
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    failures = 0
    for arch, shape in cells:
        for mesh_kind in meshes:
            if (arch, shape, mesh_kind) in done:
                continue
            if args.subprocess:
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                    "--out", args.out, "--n-micro", str(args.n_micro),
                ]
                r = subprocess.run(cmd)
                if r.returncode != 0:
                    failures += 1
                continue
            try:
                rec = run_cell(
                    arch, shape, mesh_kind, args.n_micro,
                    cost_model=(mesh_kind == "single"),
                )
            except Exception as e:
                rec = {
                    "arch": arch, "shape": shape, "mesh": mesh_kind,
                    "status": "fail", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                failures += 1
            print(_fmt(rec), flush=True)
            with open(args.out, "a") as f:
                slim = {k: v for k, v in rec.items() if k != "traceback"}
                f.write(json.dumps(slim) + "\n")
            if rec["status"] != "ok" and "traceback" in rec:
                print(rec["traceback"], file=sys.stderr, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
